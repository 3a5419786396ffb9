"""Operations and minimum HBM bytes of the CNN frontend, from its shapes.

The frontend is a chain of blocks, each a valid KxK conv (stride 1), a
non-overlapping max pool and an activation, then a per-position
projection to ``d_model``.  The counts depend only on the shapes, not on
which kernel member or fusion runs a block:

- operations: 2 per conv multiply-add, ``window - 1`` compares per pooled
  output, 1 per activated output, 2 per projection multiply-add;
- minimum bytes: a block reads its input and weights once and writes its
  pooled, activated output once; the projection reads its input and
  weights once and writes its output once.  Intermediates that a fused
  kernel keeps on chip are not counted, so an unfused chain reads as
  further from its roofline than a fused one.
"""
from __future__ import annotations

import dataclasses
from typing import List


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and minimum HBM bytes of one call."""

    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def ideal_s(self, peak_flops: float, hbm_bytes_per_s: float) -> float:
        """The least time the chip could take: the larger of the compute
        bound and the bandwidth bound."""
        return max(self.flops / peak_flops, self.bytes / hbm_bytes_per_s)


def block_shapes(config) -> List[tuple]:
    """(h, w, cin, cout, k, ph, pw) of each block, chained by shape."""
    h, w, _ = config["image"]
    k = config["kernel"]
    ph, pw = config["pool_window"]
    out = []
    chans = config["channels"]
    for cin, cout in zip(chans[:-1], chans[1:]):
        out.append((h, w, cin, cout, k, ph, pw))
        h, w = (h - k + 1) // ph, (w - k + 1) // pw
    return out


def block_work(n, h, w, cin, cout, k, ph, pw, itemsize=4) -> Work:
    """One conv -> max pool -> activation block on a batch of ``n``."""
    ho, wo = h - k + 1, w - k + 1
    po, qo = ho // ph, wo // pw
    outs = n * po * qo * cout
    flops = (2.0 * n * ho * wo * cout * k * k * cin
             + outs * (ph * pw - 1) + outs)
    nbytes = itemsize * (n * h * w * cin + k * k * cin * cout + outs)
    return Work(flops, nbytes)


def conv_macs(config) -> float:
    """Multiply-adds of the convs for one frame."""
    return sum((h - k + 1) * (w - k + 1) * cout * k * k * cin
               for h, w, cin, cout, k, _, _ in block_shapes(config))


def final_positions(config):
    """(positions S, channels C) the projection sees."""
    h, w, cin, cout, k, ph, pw = block_shapes(config)[-1]
    return ((h - k + 1) // ph) * ((w - k + 1) // pw), cout


def projection_work(config, n, itemsize=4) -> Work:
    s, c = final_positions(config)
    d = config["d_model"]
    return Work(2.0 * n * s * c * d, itemsize * (n * s * c + c * d + n * s * d))


def blocks_work(config, n) -> Work:
    """Every block's kernel call on a batch of ``n``."""
    total = Work(0.0, 0.0)
    for shape in block_shapes(config):
        total = total + block_work(n, *shape)
    return total


def blocks_ideal_s(config, n, peak_flops, hbm_bytes_per_s) -> float:
    """Sum over the blocks' kernel calls on a batch of ``n`` of each
    call's ideal time."""
    return sum(block_work(n, *shape).ideal_s(peak_flops, hbm_bytes_per_s)
               for shape in block_shapes(config))


def frame_flops(config) -> float:
    """Operations of the whole frontend for one frame, projection
    included."""
    return blocks_work(config, 1).flops + projection_work(config, 1).flops
