"""``bench/flops.py`` and the chain network's work counts
(``bench/networks/cnn_chain.py``) against hand counts."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.networks import cnn_chain

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_vgg16_d_conv_macs():
    # 222^2*64*27 + 109^2*128*576 + 52^2*256*1152 + 24^2*512*2304
    # + 10^2*512*4608
    assert cnn_chain.conv_macs(_config("vgg16_d")) == 2_673_974_016


def test_lenet5_conv_macs():
    # C1: 28*28*6*25*1, C3: 10*10*16*25*6
    assert cnn_chain.conv_macs(_config("lenet5")) == 357_600


def test_final_positions():
    assert cnn_chain.final_positions(_config("vgg16_d")) == (25, 512)
    assert cnn_chain.final_positions(_config("lenet5")) == (25, 16)


def test_block_work_by_hand():
    # 6x6x1 input, one 3x3 conv to 2 channels, 2x2 pool: conv out 4x4x2,
    # pooled 2x2x2 = 8 outputs.
    w = cnn_chain.block_work(1, 6, 6, 1, 2, 3, 2, 2)
    assert w.flops == 2 * 16 * 2 * 9 + 8 * 3 + 8
    assert w.bytes == 4 * (36 + 18 + 8)


def test_work_scales_with_batch_and_frame_flops():
    cfg = _config("vgg16_d")
    one, four = cnn_chain.blocks_work(cfg, 1), cnn_chain.blocks_work(cfg, 4)
    assert four.flops == pytest.approx(4 * one.flops)
    # weights are read once per call, not once per frame
    assert four.bytes < 4 * one.bytes
    s, c = cnn_chain.final_positions(cfg)
    assert cnn_chain.frame_flops(cfg) == pytest.approx(
        one.flops + 2 * s * c * cfg["d_model"])
    assert cnn_chain.frame_flops(cfg) == pytest.approx(5.36e9, rel=0.01)


def test_ideal_time_is_the_larger_bound():
    w = flops.Work(flops=2e12, bytes=1e9)
    assert w.ideal_s(1e15, 1e12) == pytest.approx(2e-3)
    assert w.ideal_s(1e16, 1e11) == pytest.approx(1e-2)
