#!/usr/bin/env python3
"""Readings that set a configuration's output limit and an open mix's load.

    python3 bench/calibrate.py readings --workload vgg16.sat \\
        --seeds 101-112 --control-seeds 201-203 --seconds 3
    python3 bench/calibrate.py sweep --workload <cell with an open mix> \\
        --cameras 4-9 --seed 301 --seconds 20

Both drive ``bench/run.py``'s ``measure`` in one process on the chip, at
the cell's own sizes and load.  The benchmark's own runs run neither.

``readings`` prints one line a seed: the largest relative error of the
served sample, first with the program serving, then with the control in
the program's place: the network's plain reference in three bf16 passes,
``batch(..., passes=3)`` of ``bench/networks/<network>.py``, put where
the program's served entry is by that module's ``replace_served``.
A configuration's ``rel_err_limit`` is set above the first readings and
below the second (``PERF.md``).

``sweep`` serves the cell's open mix at each camera count and prints the
rate, latencies and failures.  A count is sustained when nothing failed,
at least 95% of the offered frames completed ``ok`` at the offered rate
and the p95 is inside the deadline.  The knee is the highest sustained
rate, and holds only if some count above it was not sustained; the mix
then takes floor(0.8 x knee / fps) cameras.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402

SWEEP_METRICS = [{"name": "throughput_fps", "unit": "frames/s"},
                 {"name": "p95_ms", "unit": "ms"}]


def control_in_place(config: dict):
    """The served entry replaced by the network's reference in three bf16
    passes, for every batch the server launches."""
    network = bench_run.network_for(config)
    return network.replace_served(
        lambda served: lambda params, images, **_: network.batch(
            config, params, images, passes=3))


def seeds(text: str):
    """``"1,4-6"`` -> [1, 4, 5, 6]."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def sustained(line: dict, offered_fps: float, deadline_s: float) -> bool:
    """Whether one sweep run kept up with ``offered_fps``."""
    m = {k: v["value"] for k, v in line["metrics"].items()}
    return (line["failed"] == 0
            and m.get("throughput_fps", 0.0) >= 0.95 * offered_fps
            and m.get("p95_ms", float("inf")) < 1e3 * deadline_s)


def knee(rows, fps: float):
    """(knee in frames/s, cameras) from sweep rows, or (None, None) when
    the sweep never went past a sustained count."""
    ok = [r["offered_fps"] for r in rows if r["sustained"]]
    if not ok or not any(r["offered_fps"] > max(ok) for r in rows):
        return None, None
    return max(ok), int(0.8 * max(ok) // fps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--cameras", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    bench = bench_run.load_benchmark()
    cell = bench_run.find(bench["workloads"], args.workload, "workload")
    config = bench_run.load_config(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    try:
        devices = bench_run.require_chips(int(cell["chips"]))
    except bench_run.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    bench_run.use_compile_cache()
    common = dict(devices=devices, traced=False, seconds=args.seconds,
                  peaks=bench_run.peaks_for(devices[0].device_kind),
                  log=lambda s: None)

    if args.what == "readings":
        for arm, arm_seeds in (("program", args.seeds),
                               ("control", args.control_seeds)):
            for seed in seeds(arm_seeds) if arm_seeds else []:
                with (control_in_place(config) if arm == "control"
                      else contextlib.nullcontext()):
                    line = bench_run.measure(
                        args.workload, config, mix, int(cell["chips"]), [],
                        seed=seed, started=time.perf_counter(), **common)
                print(json.dumps({
                    "arm": arm, "config": config["name"], "seed": seed,
                    "max_rel_err": line["checks"]["max_rel_err"]["value"],
                    "limit": config["rel_err_limit"],
                    "correct": line["correct"],
                    "attempted": line["attempted"],
                    "failed": line["failed"]}), flush=True)
        return 0

    rows = []
    for n in seeds(args.cameras):
        line = bench_run.measure(
            args.workload, config, dict(mix, cameras=n), int(cell["chips"]),
            SWEEP_METRICS, seed=args.seed + n, started=time.perf_counter(),
            **common)
        m = {k: v["value"] for k, v in line["metrics"].items()}
        offered = n * float(mix["fps"])
        rows.append(dict(
            cameras=n, offered_fps=offered, **m,
            attempted=line["attempted"], failed=line["failed"],
            sustained=sustained(line, offered, float(mix["deadline_s"]))))
        print(json.dumps(rows[-1]), flush=True)
    rate, cameras = knee(rows, float(mix["fps"]))
    print(json.dumps({"knee_fps": rate, "cameras": cameras}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
