"""``bench/run.py`` rehearsed on the CPU at tiny widths.

The look for a chip is skipped by calling ``measure`` directly; the rest
of a run (weights, register, warm-up, window, output check, metric
readers, result line) runs as on the chip, with interpreted kernels.
The faults break the served path underneath and must turn ``correct``
false; so must the control (the reference in three bf16 passes) put in
the served path's place, and on its own it must read above each
configuration's limit.
"""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, reference
from bench import run as bench_run
from bench import traffic
from bench.networks import cnn_chain

ROOT = Path(__file__).resolve().parents[2]
TINY = {"name": "tiny", "network": "cnn_chain", "image": [16, 16, 3],
        "channels": [3, 4, 8], "kernel": 3, "pool_window": [2, 2],
        "activation": "relu", "d_model": 8, "dtype": "float32"}
CLOSED = {"loop": "closed", "in_flight": 8, "pool": 8, "max_batch": 4,
          "deadline_s": 600.0, "warm_batches": [4],
          "warm_s": 0.1}
OPEN = {"loop": "open", "cameras": 3, "fps": 30.0, "phase_seed": 0,
        "pool": 8, "max_batch": 4, "deadline_s": 5.0,
        "warm_batches": [1, 2, 3, 4], "warm_s": 0.1}
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _limit(name):
    return bench_run.load_config(bench_run.load_benchmark(),
                                 name)["rel_err_limit"]


def _measure(workload, mix, traced=False, seconds=0.3, config=None,
             limit_of="vgg16_d"):
    bench = bench_run.load_benchmark()
    cfg = dict(config or TINY, rel_err_limit=_limit(limit_of))
    return bench_run.measure(
        workload, cfg, mix, 1, bench_run.metrics_for(bench, workload, traced),
        seed=2**33 + 3, seconds=seconds, traced=traced,
        devices=jax.devices()[:1], peaks=bench_run.peaks_for("TPU v5 lite"),
        started=time.perf_counter(), log=lambda s: None)


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    r = _run(["--workload", "vgg16.sat", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"correct"' not in r.stdout and '"metrics"' not in r.stdout


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__", "_out"))
    r = _run(["--workload", "lenet5.sat", "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_closed_loop_result_line():
    line = _measure("vgg16.sat", CLOSED)
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"throughput_fps", "p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    check = line["checks"]["max_rel_err"]
    assert check["value"] <= check["limit"]
    assert line["device"]["platform"] == "cpu"
    json.dumps(line)


def test_open_loop_traced_result_line():
    line = _measure("vgg16.sat", OPEN, traced=True)
    assert line["correct"] is True
    # the CPU trace has no TPU plane: only host-clock readers report
    assert set(line["metrics"]) == {"mfu"}
    assert 0 < line["metrics"]["mfu"]["value"] < 100
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["device"]["window_s"] > 0


def _alter_one_answer(y):
    return y.at[0].multiply(1.0 + 1e-4)


def _leave_out_half(y):
    half = y.shape[0] // 2
    return jnp.concatenate([y[:y.shape[0] - half], y[:half]])


@pytest.mark.parametrize("fault", [_alter_one_answer, _leave_out_half])
def test_a_broken_served_path_is_not_correct(fault):
    with cnn_chain.replace_served(
            lambda served: lambda *a, **k: fault(served(*a, **k))):
        line = _measure("vgg16.sat", CLOSED)
    assert line["correct"] is False
    assert line["checks"]["max_rel_err"]["value"] > _limit("vgg16_d")


CONTROL_SIZES = [
    ("vgg16_d", dict(TINY, image=[20, 20, 3], channels=[3, 16, 32],
                     d_model=32)),
    ("lenet5", "lenet5"),
]


def _control_config(name, config):
    if config == "lenet5":
        return bench_run.load_config(bench_run.load_benchmark(), name)
    return config


@pytest.mark.parametrize("name,config", CONTROL_SIZES)
def test_the_control_reads_above_the_limit(name, config):
    config = _control_config(name, config)
    limit = _limit(name)
    for seed in (1, 2, 3):
        params, frames = cnn_chain.make(config, seed, 4)
        exact = cnn_chain.forward(config, params, frames)
        control = cnn_chain.forward(config, params, frames, passes=3)
        assert reference.rel_errors(control, exact).max() > limit


@pytest.mark.parametrize("name,config", CONTROL_SIZES)
def test_the_control_in_the_programs_place_is_not_correct(name, config):
    config = _control_config(name, config)
    with calibrate.control_in_place(config):
        line = _measure("vgg16.sat", CLOSED, config=config, limit_of=name)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False
    assert line["checks"]["max_rel_err"]["value"] > _limit(name)


def test_every_cell_finds_its_files_and_readers():
    bench = bench_run.load_benchmark()
    for cell in bench["workloads"]:
        cfg = bench_run.load_config(bench, cell["config"])
        assert cfg["rel_err_limit"] > 0
        traffic.load_mix(cell["traffic"])
        for traced in (False, True):
            for m in bench_run.metrics_for(bench, cell["name"], traced):
                assert callable(bench_run.load_reader(m["name"]))
