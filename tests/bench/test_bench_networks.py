"""Networks found by name (``bench/networks/<network>.py``).

The chain's weights, frames, reference outputs and work counts are
pinned to what they were before they moved into ``cnn_chain``: the
hashes and counts below were computed from ``bench/reference.py`` and
``bench/flops.py`` as they stood then, at seed 2**33 + 3 with a pool of
two frames.  The reference is pinned on one CPU core, since XLA's CPU
convolution sums in another order when it has more threads.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
import types
from pathlib import Path

import jax
import pytest

from bench import calibrate, trace
from bench import run as bench_run
from bench.networks import cnn_chain
from bench.trace import Event

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 3
CONTRACT = ("make", "batch", "forward", "calls", "frame_flops", "register",
            "replace_served")
TINY = {"name": "tiny", "network": "cnn_chain", "image": [16, 16, 3],
        "channels": [3, 4, 8], "kernel": 3, "pool_window": [2, 2],
        "activation": "relu", "d_model": 8, "dtype": "float32"}
CLOSED = {"loop": "closed", "in_flight": 8, "pool": 8, "max_batch": 4,
          "deadline_s": 600.0, "warm_batches": [4], "warm_s": 0.1}

PINS = {
    "vgg16_d": {
        "make": "333db3707d11425c87506ed52e5265dc"
                "69fa5402dfad8077212779d6993f99cb",
        "forward": "f11d55a10c22a1d5f73d207d153025bf"
                   "c7b75c41b99dcad497ccb8f6eee3d715",
        "control": "a304d22a8f7fb89487d885ebaaf0e513"
                   "f8423af897f12be931ccac6422a4849d",
        "calls": {
            1: [(173479680.0, 3763200), (1753417728.0, 4942080),
                (1595576320.0, 3364864), (1359249408.0, 5705728),
                (471910400.0, 9783296)],
            2: [(346959360.0, 7519488), (3506835456.0, 9589248),
                (3191152640.0, 5550080), (2718498816.0, 6692864),
                (943820800.0, 10129408)],
            3: [(520439040.0, 11275776), (5260253184.0, 14236416),
                (4786728960.0, 7735296), (4077748224.0, 7680000),
                (1415731200.0, 10475520)],
            4: [(693918720.0, 15032064), (7013670912.0, 18883584),
                (6382305280.0, 9920512), (5436997632.0, 8667136),
                (1887641600.0, 10821632)]},
        "ideal_s": {1: 4.050695879779104e-05, 2: 6.934874951456215e-05,
                    3: 9.825749789821684e-05, 4: 0.00012716624628187153},
        "frame_flops": 5366740736.0,
    },
    "lenet5": {
        "make": "550fc05fdef191571f9b763ee5aa97c5"
                "7718cd14758f7e961b981ea8760d8059",
        "forward": "891af3321c3713af323185aef5ea38db"
                   "a12de91893d3d7aa3de9bf0e5da5ae62",
        "control": "e191b72cd635afa181b0a1fbdcaf1003"
                   "02e4ce4e4520acb186ff509a6931ea18",
        "calls": {1: [(239904.0, 9400), (481600.0, 15904)],
                  2: [(479808.0, 18200), (963200.0, 22208)],
                  3: [(719712.0, 27000), (1444800.0, 28512)],
                  4: [(959616.0, 35800), (1926400.0, 34816)]},
        "ideal_s": {1: 3.08962148962149e-08, 2: 4.933821733821734e-08,
                    3: 6.778021978021978e-08, 4: 8.622222222222222e-08},
        "frame_flops": 817504.0,
    },
}

# Run in a child held to one core, so the reference sums in one order.
DIGESTS = textwrap.dedent("""
    import os
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    import hashlib, json, sys
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    import numpy as np
    from bench import run as bench_run
    from bench.networks import cnn_chain

    def digest(tree):
        h = hashlib.sha256()
        for a in jax.tree_util.tree_leaves(tree):
            a = np.asarray(a)
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        return h.hexdigest()

    out = {}
    for name in sys.argv[2:]:
        cfg = bench_run.load_config(bench_run.load_benchmark(), name)
        params, frames = cnn_chain.make(cfg, %d, 2)
        out[name] = {
            "make": digest((params, frames)),
            "forward": digest(cnn_chain.forward(cfg, params, frames)),
            "control": digest(cnn_chain.forward(cfg, params, frames,
                                                passes=3))}
    print(json.dumps(out))
""" % SEED)


def _config(name):
    return bench_run.load_config(bench_run.load_benchmark(), name)


@pytest.fixture(scope="module")
def digests():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", DIGESTS, str(ROOT), *PINS],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", sorted(PINS))
@pytest.mark.parametrize("what", ["make", "forward", "control"])
def test_weights_frames_and_reference_keep_their_pins(digests, name, what):
    assert digests[name][what] == PINS[name][what]


@pytest.mark.parametrize("name", sorted(PINS))
def test_calls_and_frame_flops_keep_their_pins(name):
    cfg, pins = _config(name), PINS[name]
    peaks = bench_run.peaks_for("TPU v5 lite")
    for n, pinned in pins["calls"].items():
        calls = cnn_chain.calls(cfg, n)
        assert [(w.flops, w.bytes) for w in calls] == pinned
        # the roofline's numerator for one launch of n frames
        assert sum(w.ideal_s(peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
                   for w in calls) == pins["ideal_s"][n]
    assert cnn_chain.frame_flops(cfg) == pins["frame_flops"]


def test_every_configuration_names_a_network_with_the_whole_contract():
    bench = bench_run.load_benchmark()
    for entry in bench["configs"]:
        network = bench_run.network_for(_config(entry["name"]))
        assert all(callable(getattr(network, f)) for f in CONTRACT)
    with pytest.raises(KeyError):
        bench_run.network_for({k: v for k, v in TINY.items()
                               if k != "network"})


RECORDER = textwrap.dedent("""
    from pathlib import Path

    from bench.networks import cnn_chain

    LOG = Path(__file__).with_suffix(".log")


    def _recorded(name):
        def call(*args, **kwargs):
            with open(LOG, "a") as f:
                f.write(name + "\\n")
            return getattr(cnn_chain, name)(*args, **kwargs)
        return call


    for _name in %r:
        globals()[_name] = _recorded(_name)
""" % (CONTRACT,))


def _measure(workload, config, traced):
    bench = bench_run.load_benchmark()
    return bench_run.measure(
        workload, config, CLOSED, 1,
        bench_run.metrics_for(bench, workload, traced), seed=SEED,
        seconds=0.3, traced=traced, devices=jax.devices()[:1],
        peaks=bench_run.peaks_for("TPU v5 lite"),
        started=time.perf_counter(), log=lambda s: None)


def test_a_network_module_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "recorder.py").write_text(RECORDER)
    monkeypatch.setattr(bench_run, "NETWORKS_DIR", tmp_path)
    limit = _config("vgg16_d")["rel_err_limit"]
    cfg = dict(TINY, network="recorder", rel_err_limit=limit)

    # traced under the other cell's name: a run's trace directory is named
    # after its cell, and test_bench_run.py traces vgg16.sat meanwhile
    served = _measure("lenet5.sat", cfg, traced=True)
    assert served["correct"] is True
    assert served["metrics"]["mfu"]["value"] > 0
    with calibrate.control_in_place(cfg):
        control = _measure("vgg16.sat", cfg, traced=False)
    assert control["attempted"] > 0 and control["correct"] is False

    dev = "/device:TPU:0"
    kernel = Event(dev, trace.OPS_LINE, 'custom_call_target="tpu_custom_call"',
                   0, 1000)
    record = types.SimpleNamespace(launches=[types.SimpleNamespace(batch=4)])
    network = bench_run.network_for(cfg)
    ctx = bench_run.Context("vgg16.sat", cfg, CLOSED, 1,
                            bench_run.peaks_for("TPU v5 lite"), 0.0, record,
                            events=[kernel], window_ns=(0, 1000),
                            planes=[dev], network=network)
    roofline = bench_run.load_reader("cnn_blocks_roofline")(ctx)
    ideal = sum(w.ideal_s(ctx.peaks["bf16_flops"],
                          ctx.peaks["hbm_bytes_per_s"])
                for w in cnn_chain.calls(cfg, 4))
    assert roofline == pytest.approx(100.0 * ideal / 1e-6)

    called = set((tmp_path / "recorder.log").read_text().split())
    assert called == set(CONTRACT)


# Four CPU devices stand for four chips; the window is short because the
# sharded path traces its step again on every launch.
MESH = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    from bench import run as bench_run
    cfg = json.loads(sys.argv[2])
    mix = json.loads(sys.argv[3])
    logs = []
    line = bench_run.measure(
        "vgg16.sat", cfg, mix, 4, [], seed=%d, seconds=0.3, traced=False,
        devices=jax.devices()[:4], peaks=bench_run.peaks_for("TPU v5 lite"),
        started=time.perf_counter(), log=logs.append)
    print(json.dumps({"line": line, "log": logs}))
""" % SEED)


def test_a_four_chip_cell_is_served_on_a_four_device_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cfg = dict(TINY, rel_err_limit=_config("vgg16_d")["rel_err_limit"])
    r = subprocess.run([sys.executable, "-c", MESH, str(ROOT),
                        json.dumps(cfg), json.dumps(CLOSED)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.splitlines()[-1])
    line = out["line"]
    assert line["correct"] is True and line["attempted"] > 0
    assert line["device"]["count"] == 4
    plan = next(s for s in out["log"] if s.startswith("plan at batch 4:"))
    sites = [s for s in plan.splitlines()[1:] if not s.startswith("TOTAL")]
    assert sites and all(s.endswith(" batchx4") for s in sites)
