"""``bench/calibrate.py``: seed lists and the open loop's knee."""
import pytest

from bench import calibrate


def _row(cameras, sustained):
    return {"cameras": cameras, "offered_fps": 30.0 * cameras,
            "sustained": sustained}


def test_seed_lists_and_ranges():
    assert calibrate.seeds("3") == [3]
    assert calibrate.seeds("1,4-6") == [1, 4, 5, 6]
    assert calibrate.seeds("9300000001-9300000003")[-1] == 9300000003


def test_knee_is_the_highest_sustained_rate_below_one_that_is_not():
    rows = [_row(n, n <= 7) for n in range(4, 10)]
    assert calibrate.knee(rows, 30.0) == (210.0, 5)


def test_no_knee_until_the_sweep_goes_past_it():
    assert calibrate.knee([_row(n, True) for n in range(3, 8)],
                          30.0) == (None, None)
    assert calibrate.knee([_row(4, False)], 30.0) == (None, None)


def _line(fps, p95_ms, failed=0):
    return {"failed": failed,
            "metrics": {"throughput_fps": {"value": fps, "unit": "frames/s"},
                        "p95_ms": {"value": p95_ms, "unit": "ms"}}}


@pytest.mark.parametrize("line,kept_up", [
    (_line(29.0, 40.0), True),
    (_line(28.0, 40.0), False),             # under 95% of the offered rate
    (_line(30.0, 100.0), False),            # p95 not inside the deadline
    (_line(30.0, 40.0, failed=1), False),
    ({"failed": 0, "metrics": {}}, False),  # a run that read nothing
])
def test_a_count_is_sustained_at_the_offered_rate_inside_the_deadline(
        line, kept_up):
    assert calibrate.sustained(line, 30.0, 0.1) is kept_up


def test_sweep_runs_each_count_and_prints_the_knee(monkeypatch, capsys):
    import json

    import jax

    from bench import run as bench_run
    from bench import traffic
    tiny = {"name": "tiny", "network": "cnn_chain", "image": [16, 16, 3],
            "channels": [3, 4, 8], "kernel": 3, "pool_window": [2, 2],
            "activation": "relu", "d_model": 8, "dtype": "float32",
            "rel_err_limit": 2e-06}
    mix = {"loop": "open", "cameras": 1, "fps": 30.0, "phase_seed": 0,
           "pool": 8, "max_batch": 4, "deadline_s": 5.0,
           "warm_batches": [1, 2, 3, 4], "warm_s": 0.1}
    monkeypatch.setattr(bench_run, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(bench_run, "peaks_for",
                        lambda kind: {"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(bench_run, "load_config", lambda bench, name: tiny)
    monkeypatch.setattr(traffic, "load_mix", lambda name: dict(mix))
    assert calibrate.main(["sweep", "--workload", "vgg16.sat", "--cameras",
                           "1,400", "--seed", "5", "--seconds", "0.5"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    rows = lines[:-1]
    assert [r["cameras"] for r in rows] == [1, 400]
    assert all(r["attempted"] > 0 for r in rows)
    # whether one camera keeps up with interpreted kernels depends on how
    # busy the host is; 400 (12,000 frames/s) never do
    assert not rows[1]["sustained"]
    knee_fps, cameras = calibrate.knee(rows, 30.0)
    assert knee_fps == (30.0 if rows[0]["sustained"] else None)
    assert lines[-1] == {"knee_fps": knee_fps, "cameras": cameras}
