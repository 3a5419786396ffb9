"""``chip_smoke.py`` rehearsed on the CPU: its serving path end to end at
tiny widths (kernels interpreted), its refusal without a TPU, and the
two platform decisions it relies on."""
import importlib.util
import types
from pathlib import Path

import jax
import pytest

import repro.kernels
from repro.core import resources

_PATH = Path(__file__).resolve().parent.parent / "chip_smoke.py"
_SPEC = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)

TINY = {"hw": 32, "channels": (3, 8, 16), "d_model": 16}


def test_smoke_serving_matches_reference():
    params, images = chip_smoke.build(seed=0, **TINY)
    imgs = images[chip_smoke.MAX_BATCH:]
    server, done, warm_s, walls = chip_smoke.serve(
        params, imgs, warmup=images[:chip_smoke.MAX_BATCH])
    assert len(done) == chip_smoke.REQUESTS and len(walls) == len(done)
    assert [c.batch_size for c in done] == [chip_smoke.MAX_BATCH] * len(done)
    assert warm_s > 0.0 and all(w >= 0.0 for w in walls)
    errs = chip_smoke.check_outputs(done, chip_smoke.reference(params, imgs),
                                    "tiny")
    assert max(errs) < 1e-5          # interpreted kernels: f32 throughout
    plan = server.plan_for(chip_smoke.TENANT, chip_smoke.MAX_BATCH)
    assert len(plan.sites) == len(TINY["channels"]) - 1


def test_smoke_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_interpret_follows_the_platform():
    assert repro.kernels.interpret() == (jax.default_backend() == "cpu")


def test_unmodeled_accelerator_is_refused(monkeypatch):
    chip = types.SimpleNamespace(device_kind="TPU v4", platform="tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])
    with pytest.raises(RuntimeError, match="TPU v5 lite"):
        resources.check_device()
    chip.device_kind = resources.DEVICE_KIND
    resources.check_device()
