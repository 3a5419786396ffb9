#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload vgg16.sat --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file that entry names, the configuration's
network in ``bench/networks/<network>.py``, its traffic mix in
``bench/traffic/<traffic>.json`` and each metric's reader in
``bench/metrics/<metric>.py`` (see ``bench/README.md``).

A run makes the weights and a frame pool from ``--seed`` on the device,
registers the tenant with ``SLOScheduler`` (on a mesh of the cell's chips
where it asks for more than one), warms the batch sizes the mix
produces, then drives the mix for ``--seconds`` through
``SLOScheduler.submit`` and ``SLOScheduler.run`` (one launch per pump).
``--trace 1`` runs the same window under the profiler and reports the
per-layer metrics in place of the end-to-end ones.

After the window the outputs of a seeded sample of the served frames are
compared with the network's plain f32 reference; the run is ``correct``
when the largest relative error is within the configuration's
``rel_err_limit``.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

_START = time.perf_counter()

import os  # noqa: E402

# The TPU runtime logs under /tmp unless told otherwise; a run writes
# nothing outside its checkout and the directories it is given.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import reference, trace, traffic  # noqa: E402

BENCH_DIR = ROOT / "bench"
TRACE_ROOT = BENCH_DIR / "_out" / "trace"
METRICS_DIR = BENCH_DIR / "metrics"
NETWORKS_DIR = BENCH_DIR / "networks"
# Served outputs compared with the reference, drawn from the seed.
SAMPLE = 64
PEAKS_FILE = BENCH_DIR / "peaks.json"


class NoChip(RuntimeError):
    """JAX finds no TPU, fewer chips than the cell asks for, or the
    checkout holds no program to run."""


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str) -> dict:
    entry = find(bench["configs"], name, "configuration")
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def metrics_for(bench: dict, workload: str, traced: bool) -> List[dict]:
    """The metrics this cell reports: its end-to-end ones, or with a
    trace its per-layer ones."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def load_module(directory: Path, name: str):
    """The module ``<directory>/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory.name}_"
        + name.replace(".", "_").replace("-", "_"),
        directory / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str):
    """``read(ctx)`` from ``bench/metrics/<name>.py``."""
    return load_module(METRICS_DIR, name).read


def network_for(config: dict):
    """The module ``bench/networks/<network>.py`` that the configuration's
    ``network`` names: its weights, reference, work counts and how the
    program is given it (``bench/README.md``)."""
    return load_module(NETWORKS_DIR, config["network"])


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"device {device_kind!r} is not in {PEAKS_FILE}")
    return table[device_kind]


def require_chips(chips: int):
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is "
                     f"{jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices[:chips]


def use_compile_cache() -> str:
    """The program's persistent compilation cache, holding every
    program, however quick to compile, so that set-up after the first
    run of a cell compiles nothing."""
    import jax
    from repro.launch.cache import use_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    workload: str
    config: dict
    mix: dict
    chips: int
    peaks: dict
    setup_s: float
    record: traffic.Record
    events: Optional[list] = None        # trace.Event list (traced run)
    window_ns: Optional[tuple] = None    # bench.window span on trace clock
    planes: Optional[list] = None        # device planes of the cell
    network: Any = None                  # the configuration's network module

    _busy: dict = dataclasses.field(default_factory=dict)

    def busy(self, plane):
        """Disjoint intervals of the window in which ``plane`` ran ops."""
        if plane not in self._busy:
            lo, hi = self.window_ns
            self._busy[plane] = trace.busy(self.events, plane, lo, hi)
        return self._busy[plane]


def serve(config: dict, network, mix: dict, *, seed: int, seconds: float,
          traced: bool, trace_dir: Path, devices, started: float,
          log=print) -> dict:
    """Set up, warm, drive the window, check the outputs.  Everything
    but the look for a chip.  More than one device is served as one
    mesh."""
    import jax
    from repro.core.plan import STATS
    from repro.core.resources import MeshSpec
    from repro.runtime import AdaptiveServer, SLOScheduler, SLOSpec

    params, pool = network.make(config, seed, int(mix["pool"]))
    frames = [pool[i] for i in range(pool.shape[0])]
    jax.block_until_ready(frames)

    def stamp(what):
        log(f"[{time.perf_counter() - started:.3f} s] {what}")

    stamp("weights and frames made")
    mesh = MeshSpec(devices=len(devices)) if len(devices) > 1 else None
    server = AdaptiveServer(max_batch=int(mix["max_batch"]), mesh=mesh)
    sched = SLOScheduler(server)
    tenant = config["name"]
    network.register(sched, tenant, config, params,
                     SLOSpec(deadline_s=float(mix["deadline_s"])))
    stamp("tenant registered")

    def pump():
        comps = sched.run(max_launches=sched.launches + 1)
        jax.block_until_ready([c.result for c in comps if c.ok])
        return comps

    def submit(idx):
        return sched.submit(tenant, frames[idx])

    for b in mix["warm_batches"]:
        for _ in range(2):
            for i in range(b):
                submit(i)
            while sched.pending():
                pump()
    span = None
    if traced:
        span = lambda name: jax.profiler.TraceAnnotation(name)  # noqa: E731
    gen = traffic.LoadGen(mix, submit=submit, pump=pump,
                          pending=sched.pending,
                          outcomes=lambda: sched.outcomes, span=span)
    warm = gen.run(seed ^ 0x5EED, float(mix.get("warm_s", 0.5)), 0)
    while sched.pending():
        pump()
    sched.outcomes.clear()
    stamp("warmed")

    plan = server.plan_for(tenant, int(mix["max_batch"]))
    misses0 = STATS.plan_misses
    setup_s = time.perf_counter() - started
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            record = gen.run(seed, seconds, SAMPLE)
        jax.profiler.stop_trace()
    else:
        record = gen.run(seed, seconds, SAMPLE)
    plan_misses = STATS.plan_misses - misses0
    stamp(f"window closed after {record.window_s:.6f} s")

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    lateness = record.lateness()
    sizes = [l.batch for l in record.launches]
    log(f"plan at batch {mix['max_batch']}:\n{plan.describe()}")
    log(f"launches per batch: {plan.total_launches}; launches in window: "
        f"{len(sizes)}; mean batch {sum(sizes) / max(1, len(sizes)):.4f}")
    log(f"plan misses inside the window: {plan_misses}")
    log(f"generator lateness (sent - due): p95 "
        f"{traffic.percentile(lateness, 95) * 1e3:.6f} ms, max "
        f"{max(lateness, default=0.0) * 1e3:.6f} ms over {len(lateness)} "
        f"frames; warm-up pre-run served {len(warm.ok())} frames")
    late = [f for f in record.ok()
            if f.done - f.due > float(mix["deadline_s"])]
    log(f"frames past the {mix['deadline_s']} s deadline: {len(late)}")
    log(f"peak_bytes_in_use (fullest chip): {peak}")

    # The program's state goes before the reference runs.
    sample = [(idx, jax.device_get(y)) for idx, y in record.sample]
    record.sample = []
    del sched, server, gen, frames
    errs = []
    if sample:
        import numpy as np
        used = sorted({idx for idx, _ in sample})
        ref = network.forward(config, params, pool[np.asarray(used)])
        row = {idx: i for i, idx in enumerate(used)}
        errs = [float(e) for e in reference.rel_errors(
            np.stack([y for _, y in sample]),
            ref[[row[idx] for idx, _ in sample]])]
    stamp("outputs compared")
    return {"record": record, "setup_s": setup_s, "peak": peak,
            "errs": errs}


def reduce_trace(trace_dir: Path, chips_used: int):
    """(events, window span in ns, device planes) of a traced window."""
    events = trace.load_events(trace.find_xplane(trace_dir))
    window = trace.spans(events, "bench.window")
    if not window:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = window[0].start_ns, window[0].end_ns
    planes = trace.device_planes(events)[:chips_used]
    return events, (lo, hi), planes


def result_line(*, correct, record, metrics, device, breakdown, checks):
    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": len(record.finished()),
        "failed": len([f for f in record.finished() if f.outcome != "ok"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def measure(workload: str, config: dict, mix: dict, chips: int,
            metric_entries: List[dict], *, seed: int, seconds: float,
            traced: bool, devices, peaks: dict, started: float,
            log=print) -> dict:
    """One run of a cell on ``devices``; returns the result line."""
    import jax
    trace_dir = TRACE_ROOT / workload
    network = network_for(config)
    out = serve(config, network, mix, seed=seed, seconds=seconds,
                traced=traced, trace_dir=trace_dir, devices=devices,
                started=started, log=log)
    record = out["record"]
    ctx = Context(workload, config, mix, chips, peaks, out["setup_s"],
                  record, network=network)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": out["peak"]}
    breakdown = None
    if traced:
        ctx.events, ctx.window_ns, ctx.planes = reduce_trace(trace_dir,
                                                             chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = ctx.window_ns
        busy = [sum(e - s for s, e in ctx.busy(p)) for p in ctx.planes]
        device["busy_s"] = sum(busy) / max(1, len(busy)) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {
            "device_ops": trace.top_ops(ctx.events, ctx.planes, lo, hi),
            "idle_gaps": (trace.idle_by_host(ctx.events, ctx.planes[0],
                                             lo, hi) if ctx.planes else [])}
        log(f"[{time.perf_counter() - started:.3f} s] trace read: "
            f"{len(ctx.events)} events")
    metrics = {}
    for m in metric_entries:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    limit = config["rel_err_limit"]
    worst = max(out["errs"]) if out["errs"] else float("inf")
    correct = bool(out["errs"]) and limit is not None and worst <= limit
    log(f"outputs compared with the reference: {len(out['errs'])} frames; "
        f"largest relative error {worst!r}")
    checks = {"max_rel_err": {"value": worst, "limit": limit}}
    return result_line(correct=correct, record=record, metrics=metrics,
                       device=device, breakdown=breakdown, checks=checks)


def run(workload: str, seed: int, seconds: float, traced: bool,
        started: float = _START, log=print) -> dict:
    """One run of a cell named in ``BENCHMARK.json``."""
    if not (ROOT / "src" / "repro").is_dir():
        raise NoChip(f"the program is not in this checkout: no "
                     f"{ROOT / 'src' / 'repro'}")
    bench = load_benchmark()
    cell = find(bench["workloads"], workload, "workload")
    config = load_config(bench, cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    chips = int(cell["chips"])
    devices = require_chips(chips)
    log(f"compile cache: {use_compile_cache()}")
    return measure(workload, config, mix, chips,
                   metrics_for(bench, workload, traced), seed=seed,
                   seconds=seconds, traced=traced, devices=devices,
                   peaks=peaks_for(devices[0].device_kind),
                   started=started, log=log)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace),
                   log=lambda s: print(s, flush=True))
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
