"""The traffic generator: deterministic from the seed, at the mix's rate."""
import types

import pytest

from bench import traffic


def _cameras(n):
    mix = traffic.load_mix("cameras")
    mix["cameras"] = n
    return mix


def test_open_arrivals_are_deterministic_and_at_n_times_fps():
    mix = _cameras(6)
    a = traffic.open_arrivals(mix, 2**33 + 1, 10.0)
    assert a == traffic.open_arrivals(mix, 2**33 + 1, 10.0)
    assert len(a) == 6 * 30 * 10
    assert all(0.0 <= due < 10.0 for due, _ in a)
    assert [d for d, _ in a] == sorted(d for d, _ in a)


def test_every_seed_sees_the_same_arrival_instants():
    mix = _cameras(5)
    a, b = (traffic.open_arrivals(mix, s, 3.0) for s in (1, 2))
    assert [d for d, _ in a] == pytest.approx([d for d, _ in b])
    assert [i for _, i in a] != [i for _, i in b]


def test_percentile_is_linear_interpolation():
    assert traffic.percentile([4, 1, 3, 2], 50) == 2.5
    assert traffic.percentile(list(range(101)), 95) == 95.0
    assert traffic.percentile([], 95) == 0.0


def test_reservoir_is_seeded_and_bounded():
    def draw(seed):
        r = traffic.Reservoir(4, seed)
        for i in range(100):
            r.offer(i)
        return r.items
    assert draw(7) == draw(7) and len(draw(7)) == 4
    assert draw(7) != draw(8)


class FakeScheduler:
    """Serves every queued frame at once, max_batch at a time."""

    def __init__(self, max_batch, clock):
        self.queue, self.next_rid, self.max_batch = [], 0, max_batch
        self.clock = clock

    def submit(self, idx):
        self.queue.append((self.next_rid, idx))
        self.next_rid += 1
        return self.next_rid - 1

    def pump(self):
        take, self.queue = (self.queue[:self.max_batch],
                            self.queue[self.max_batch:])
        self.clock.t += 0.01
        return [types.SimpleNamespace(rid=r, ok=True, result=i)
                for r, i in take]


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


def _drive(mix, seed, seconds):
    clock = Clock()
    s = FakeScheduler(mix["max_batch"], clock)
    d = traffic.LoadGen(mix, submit=s.submit, pump=s.pump,
                        pending=lambda: len(s.queue), outcomes=dict,
                        clock=clock)
    return d.run(seed, seconds, 8)


def test_closed_loop_keeps_frames_in_flight_and_is_seeded():
    mix = traffic.load_mix("sat")
    rec = _drive(mix, 5, 1.0)
    assert [l.batch for l in rec.launches] == [4] * len(rec.launches)
    assert len(rec.ok()) == 4 * len(rec.launches)
    assert len([f for f in rec.frames if f.outcome == "pending"]) == 8
    assert rec.window_s == pytest.approx(0.01 * len(rec.launches))
    again = _drive(mix, 5, 1.0)
    assert [f.idx for f in rec.frames] == [f.idx for f in again.frames]
    assert [i for i, _ in rec.sample] == [i for i, _ in again.sample]
