"""The one traffic generator: reads a mix file and drives the scheduler.

A mix is a JSON file under ``bench/traffic/``.  Its keys:

- ``loop``: ``"closed"`` (a fixed number of frames in flight; a frame is
  sent when one completes) or ``"open"`` (periodic cameras);
- ``in_flight`` (closed): frames kept in flight;
- ``cameras``, ``fps``, ``phase_seed`` (open): N cameras, each sending
  one frame every 1/fps s.  Camera phases are drawn once from
  ``phase_seed``, so every run seed sees the same arrival instants; the
  run seed permutes which camera has which phase and picks the frames;
- ``pool``: frames drawn from a pool of this many, made from the seed;
- ``max_batch``: the largest batch the server makes of queued frames;
- ``deadline_s``: the tenant's ``SLOSpec`` deadline;
- ``warm_batches``: the batch sizes the window produces, warmed in set-up.

Every latency is taken from the frame's due time (closed loop: the time
it was sent) to the stamp after ``block_until_ready``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import random
import time
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def percentile(values, q: float) -> float:
    """q-th percentile (0..100) by sorted linear interpolation (numpy's
    ``linear``).  Empty input reads 0.0."""
    xs = sorted(values)
    if not xs:
        return 0.0
    pos = (min(max(float(q), 0.0), 100.0) / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def load_mix(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


@dataclasses.dataclass
class Frame:
    idx: int                     # index into the frame pool
    due: float                   # seconds from window start
    sent: float = float("nan")   # submit time, seconds from window start
    pump: float = float("nan")   # start of the pump that launched it
    done: float = float("nan")   # stamp after block_until_ready
    outcome: str = "pending"     # pending | ok | shed | rejected | failed


@dataclasses.dataclass
class Launch:
    start: float
    end: float
    batch: int


@dataclasses.dataclass
class Record:
    """What one window did, on the host clock (seconds from its start)."""

    frames: List[Frame]
    launches: List[Launch]
    window_s: float
    sample: list                 # [(pool idx, served output)], seeded

    def finished(self) -> List[Frame]:
        """Frames with a verdict inside the window."""
        return [f for f in self.frames if f.outcome != "pending"]

    def ok(self) -> List[Frame]:
        return [f for f in self.frames if f.outcome == "ok"]

    def lateness(self) -> List[float]:
        """How late the generator sent each frame: sent - due."""
        return [f.sent - f.due for f in self.frames if f.sent == f.sent]


class Reservoir:
    """A uniform sample of at most ``k`` served outputs, drawn from the
    seed, keeping only ``k`` outputs alive on the device."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.seen = 0
        self.items: list = []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = self.rng.randrange(self.seen)
        if j < self.k:
            self.items[j] = item


def open_arrivals(mix: dict, seed: int, seconds: float):
    """(due s, pool idx) of every frame the cameras send in ``seconds``,
    sorted by due time."""
    n, fps = int(mix["cameras"]), float(mix["fps"])
    phases = np.random.default_rng(int(mix["phase_seed"])).uniform(
        0.0, 1.0 / fps, n)
    rng = np.random.default_rng(seed)
    phases = phases[rng.permutation(n)]
    dues = sorted(float(p + k / fps) for p in phases
                  for k in range(int(np.ceil(seconds * fps)) + 1)
                  if p + k / fps < seconds)
    idx = rng.integers(0, int(mix["pool"]), len(dues))
    return list(zip(dues, (int(i) for i in idx)))


class LoadGen:
    """Drives one tenant of an ``SLOScheduler`` through a mix.

    ``submit(idx)`` queues pool frame ``idx`` and returns its request id;
    ``pump()`` makes one launch and returns its completions, each with
    its output ready.  ``outcomes`` maps a request id to the scheduler's
    verdict.  ``span(name)`` wraps each pump and submit (a profiler
    annotation in a traced run)."""

    def __init__(self, mix: dict, *, submit: Callable, pump: Callable,
                 pending: Callable, outcomes: Callable,
                 span: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.mix = mix
        self.submit = submit
        self.pump = pump
        self.pending = pending
        self.outcomes = outcomes
        self.span = span or (lambda name: contextlib.nullcontext())
        self.clock = clock

    def _send(self, frame, t0, by_rid):
        with self.span("bench.submit"):
            rid = self.submit(frame.idx)
        frame.sent = self.clock() - t0
        by_rid[rid] = frame

    def _pump(self, t0, by_rid, launches, sample):
        start = self.clock() - t0
        with self.span("bench.launch"):
            comps = self.pump()
        end = self.clock() - t0
        if comps:
            launches.append(Launch(start, end, len(comps)))
        for c in comps:
            f = by_rid.pop(c.rid)
            f.pump, f.done = start, end
            f.outcome = "ok" if c.ok else "failed"
            if c.ok:
                sample.offer((f.idx, c.result))
        return comps

    def _settle(self, by_rid, keep_pending):
        """Give frames the scheduler dropped (shed, rejected) their
        verdict; with ``keep_pending`` false, a frame still without one
        has failed."""
        verdicts = self.outcomes()
        for rid, f in list(by_rid.items()):
            v = verdicts.get(rid)
            if v in ("shed", "rejected"):
                f.outcome = v
                by_rid.pop(rid)
        for f in by_rid.values():
            if not keep_pending:
                f.outcome = "failed"

    def run(self, seed: int, seconds: float, sample_k: int) -> Record:
        sample = Reservoir(sample_k, seed)
        if self.mix["loop"] == "closed":
            return self._closed(seed, seconds, sample)
        if self.mix["loop"] == "open":
            return self._open(seed, seconds, sample)
        raise ValueError(f"unknown loop {self.mix['loop']!r}")

    def _closed(self, seed, seconds, sample) -> Record:
        rng = np.random.default_rng(seed)
        pool = int(self.mix["pool"])
        frames, launches, by_rid = [], [], {}
        t0 = self.clock()
        for _ in range(int(self.mix["in_flight"])):
            f = Frame(int(rng.integers(pool)), due=self.clock() - t0)
            frames.append(f)
            self._send(f, t0, by_rid)
        end = 0.0
        while end < seconds:
            comps = self._pump(t0, by_rid, launches, sample)
            end = self.clock() - t0
            for _ in comps:
                f = Frame(int(rng.integers(pool)), due=self.clock() - t0)
                frames.append(f)
                self._send(f, t0, by_rid)
            self._settle(by_rid, keep_pending=True)
        # frames still in flight when the window closed stay "pending"
        window = launches[-1].end if launches else end
        return Record(frames, launches, window, sample.items)

    def _open(self, seed, seconds, sample) -> Record:
        arrivals = open_arrivals(self.mix, seed, seconds)
        frames = [Frame(idx, due) for due, idx in arrivals]
        launches, by_rid = [], {}
        i = 0
        t0 = self.clock()
        while i < len(frames) or self.pending():
            now = self.clock() - t0
            while i < len(frames) and frames[i].due <= now:
                self._send(frames[i], t0, by_rid)
                i += 1
            if self.pending():
                self._pump(t0, by_rid, launches, sample)
                self._settle(by_rid, keep_pending=True)
            elif i < len(frames):
                time.sleep(max(0.0, frames[i].due - (self.clock() - t0)))
        self._settle(by_rid, keep_pending=False)
        window = launches[-1].end if launches else self.clock() - t0
        return Record(frames, launches, window, sample.items)
