"""TPU resource model — the target-hardware vector the selector adapts to.

The paper adapts convolution IPs to the FPGA resource vector
(DSP slices, LUT/CLB fabric, BRAM).  On TPU v5e the analogous vector is
(MXU passes, VPU ops, VMEM bytes, HBM bytes/bandwidth, ICI bandwidth).
``ResourceBudget`` is the machine-readable "available resources" a
deployment hands to the selector; ``Footprint`` is what one kernel IP
costs against that budget for a concrete shape.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

# ---------------------------------------------------------------------------
# TPU v5e hardware constants (per chip).  These are the numbers the roofline
# analysis and the selector cost model share; keep them in one place.
# ---------------------------------------------------------------------------
PEAK_BF16_FLOPS = 197e12          # bf16 MXU peak, FLOP/s
PEAK_INT8_OPS = 394e12            # int8 MXU peak, OP/s (2x bf16)
HBM_BYTES = 16 * 1024**3          # 16 GiB HBM
HBM_BW = 819e9                    # bytes/s
# VMEM as the v5e compiler enforces it: a kernel may raise its scoped
# limit (vmem_limit_bytes, default 16 MiB) up to the chip's 128 MiB; a
# kernel holding 126 MiB of scratch compiles, one holding 128.03 MiB is
# refused ("Used 128.03M of 128.00M vmem").
VMEM_BYTES = 128 * 1024 * 1024
# What the planner grants one kernel: the chip's VMEM less the headroom
# kernels.vmem_limit adds on top of a footprint for the compiler's own
# temporaries.
KERNEL_VMEM_BYTES = VMEM_BYTES - 8 * 1024 * 1024
ICI_BW_PER_LINK = 50e9            # bytes/s per ICI link (given)
ICI_LINKS = 4                     # v5e 2D torus: 4 links/chip
VPU_LANES = 8 * 128               # (8, 128) vector registers
VPU_OPS_PER_CYCLE = 4 * VPU_LANES # 4 ALUs per lane pair (approx)
CLOCK_HZ = 940e6                  # v5e core clock
MXU_DIM = 128                     # systolic array is 128x128
# bf16 MXU passes one f32 dot takes at Precision.HIGHEST (the 6-pass
# bf16 decomposition); bf16 and int8 dots take one.
F32_HIGHEST_PASSES = 6
LANE = 128                        # last-dim tile
SUBLANE = 8                       # second-to-last-dim tile (fp32)
# Collective pricing unit: bytes one ICI link moves per core cycle —
# what a sharded site's collective traffic is converted to cycles with
# (the FPGA analogy is the inter-board serial links of a multi-FPGA
# deployment; a deployment with slower links overrides it per MeshSpec).
ICI_BYTES_PER_CYCLE = ICI_BW_PER_LINK / CLOCK_HZ
# The chip these constants describe, as JAX reports its device_kind.
DEVICE_KIND = "TPU v5 lite"


def check_device() -> None:
    """Refuse to plan for an accelerator these constants do not describe.
    On the CPU the kernels are interpreted and the model is a simulation
    target, so any host passes."""
    import jax
    if jax.default_backend() == "cpu":
        return
    kinds = sorted({d.device_kind for d in jax.devices()})
    if kinds != [DEVICE_KIND]:
        raise RuntimeError(
            f"the resource model describes {DEVICE_KIND!r} chips; this "
            f"process sees {kinds}")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A device mesh the planner may spread one plan across.

    The paper sizes one network against ONE fabric; the scale-out story
    (multi-FPGA boards, TPU slices) offers ``devices`` identical fabrics
    joined by links of finite bandwidth.  ``MeshSpec`` is the planner's
    view of that grant: how many devices, the mesh-axis name execution
    shards over, and the link bandwidth collective traffic is priced at
    (``ici_bytes_per_cycle``; cycles here are core cycles, the same unit
    as ``Footprint.est_cycles``).  Hashable — it participates in plan
    cache keys.
    """

    devices: int = 1
    axis: str = "shard"
    ici_bytes_per_cycle: float = ICI_BYTES_PER_CYCLE

    def __post_init__(self):
        if self.devices < 1:
            raise ValueError(f"mesh needs >= 1 device, got {self.devices}")
        if self.ici_bytes_per_cycle <= 0.0:
            raise ValueError("ici_bytes_per_cycle must be positive")

    def ici_cycles(self, n_bytes: float) -> float:
        """Cycles to move ``n_bytes`` across one link."""
        return n_bytes / self.ici_bytes_per_cycle

    def all_gather_cycles(self, n_bytes: float) -> float:
        """Ring all-gather of a tensor of GLOBAL size ``n_bytes``: each
        device receives the (devices-1)/devices of it that it does not
        already hold."""
        d = self.devices
        if d <= 1:
            return 0.0
        return self.ici_cycles(n_bytes * (d - 1) / d)

    def all_reduce_cycles(self, n_bytes: float) -> float:
        """Ring all-reduce (reduce-scatter + all-gather) of a tensor of
        size ``n_bytes``: 2 * (d-1)/d of it crosses each link — the cost
        a channel-split conv pays to sum its partial outputs."""
        d = self.devices
        if d <= 1:
            return 0.0
        return self.ici_cycles(2.0 * n_bytes * (d - 1) / d)

    def halo_cycles(self, n_bytes: float) -> float:
        """Neighbor exchange of ``n_bytes`` of boundary rows — what a
        spatial conv split pays per step (both edges move in parallel
        over distinct links, so one halo's bytes price the exchange)."""
        if self.devices <= 1:
            return 0.0
        return self.ici_cycles(n_bytes)


@dataclasses.dataclass(frozen=True)
class ResourceBudget:
    """Available resources a kernel IP may consume — the paper's
    "available FPGA resources", TPU-native.

    ``mxu_available`` mirrors "DSP availability": a deployment where the
    MXU is saturated by co-resident ops (or absent, e.g. pure-VPU debug
    paths) sets it False, steering the selector to Conv1-style logic-only
    variants.  ``precision_bits`` mirrors the paper's operand-width limits
    (Conv3 is only legal up to 8-bit operands).
    """

    vmem_bytes: int = KERNEL_VMEM_BYTES
    hbm_bytes: int = HBM_BYTES
    mxu_available: bool = True
    mxu_passes_budget: Optional[int] = None   # None = unlimited
    vpu_ops_budget: Optional[int] = None      # None = unlimited
    precision_bits: int = 16                  # max operand width required
    prefer_parallel_streams: bool = False     # paper: "demand high parallelism"

    def scaled(self, fraction: float) -> "ResourceBudget":
        """A fractional slice of this budget (e.g. per co-resident op).

        Every *quantitative* column scales — capacity (vmem/hbm) and the
        optional pass/op ceilings alike; the qualitative knobs
        (mxu_available, precision_bits, prefer_parallel_streams) describe
        the deployment, not an amount, and pass through unchanged.  The
        network planner's budget partitioning depends on the ceilings
        scaling with the slice.
        """
        def _slice(v):
            return None if v is None else int(v * fraction)

        return dataclasses.replace(
            self,
            vmem_bytes=int(self.vmem_bytes * fraction),
            hbm_bytes=int(self.hbm_bytes * fraction),
            mxu_passes_budget=_slice(self.mxu_passes_budget),
            vpu_ops_budget=_slice(self.vpu_ops_budget),
        )


@dataclasses.dataclass(frozen=True)
class Footprint:
    """What one IP costs for one concrete call — paper Table II, machine-readable.

    FPGA column mapping: DSPs -> mxu_passes, LUTs/CLBs -> vpu_ops,
    BRAM -> vmem_bytes, DDR traffic -> hbm_bytes, WNS -> est_cycles
    (the timing-role metric), convs/cycle -> outputs_per_pass.
    """

    vmem_bytes: int
    hbm_bytes: int
    mxu_passes: int
    vpu_ops: int
    est_cycles: float
    outputs_per_pass: int = 1       # Conv3/Conv4 produce 2 convolutions/pass
    max_operand_bits: int = 32      # Conv3 is limited to 8
    launches: int = 1               # pallas_call launches per invocation;
                                    # a fused conv->pool->act member is 1
                                    # where the unfused chain costs 3
    comm_cycles: float = 0.0        # collective traffic a sharded site
                                    # pays (ICI cycles; 0 for the
                                    # single-device footprints families
                                    # price) — folded into est_cycles

    @property
    def compute_cycles(self) -> float:
        """The compute term of the additive ``cost_cycles`` split:
        ``est_cycles`` minus the DMA cycles its ``hbm_bytes`` price in
        and minus its collective ``comm_cycles`` (clamped at zero for
        footprints priced under an older rule).  These are the
        analytical axes the measurement-calibrated cost model
        (``core/calibrate_cost.py``) regresses over."""
        return max(self.est_cycles - hbm_cycles(self.hbm_bytes)
                   - self.comm_cycles, 0.0)

    def calibrated_cycles(self, calibration, member: str) -> float:
        """This footprint's cost under a measurement-derived
        ``CalibrationTable`` (cycle units; ``member`` is the calibration
        key, see ``calibrate_cost.member_key``).  ``calibration=None``
        is the identity: the analytical ``est_cycles``."""
        if calibration is None:
            return self.est_cycles
        return calibration.calibrated_cycles(self, member)

    def fits(self, budget: ResourceBudget) -> bool:
        if self.vmem_bytes > budget.vmem_bytes:
            return False
        if self.hbm_bytes > budget.hbm_bytes:
            return False
        if self.mxu_passes > 0 and not budget.mxu_available:
            return False
        if (budget.mxu_passes_budget is not None
                and self.mxu_passes > budget.mxu_passes_budget):
            return False
        if (budget.vpu_ops_budget is not None
                and self.vpu_ops > budget.vpu_ops_budget):
            return False
        if budget.precision_bits > self.max_operand_bits:
            return False
        return True


def cost_cycles(compute_cycles: float, hbm_bytes: int,
                comm_cycles: float = 0.0) -> float:
    """The shared est-cycles rule every footprint prices with: a kernel
    launch pays its compute AND its DMA traffic AND (for sharded sites)
    its collective traffic.

    The earlier model took ``max(compute, dma)`` (perfect overlap), which
    made HBM round-trips free whenever compute dominated — exactly the
    traffic layer fusion removes.  Accounting DMA bytes additively is the
    conservative serial model (the paper's DDR-traffic column is a cost
    column, not an overlap hint), and it is what lets a fused
    conv->pool->act member's saved intermediate reads+writes show up as
    a counted est-cycles drop (docs/adaptive_ips.md, "Fusion contract").
    ``comm_cycles`` extends the same serial rule to collectives: a
    sharded site pays its halo/psum/all-gather bytes at the mesh's link
    bandwidth (docs/adaptive_ips.md, "Sharding contract").
    """
    return compute_cycles + hbm_cycles(hbm_bytes) + comm_cycles


def mxu_pass_cycles(m: int, k: int, n: int) -> float:
    """Cycles for an (m,k)x(k,n) matmul streamed through the 128x128 MXU."""
    import math
    tiles = (math.ceil(m / MXU_DIM) * math.ceil(k / MXU_DIM)
             * math.ceil(n / MXU_DIM))
    return tiles * MXU_DIM  # one column of results per cycle per tile


def vpu_op_cycles(n_ops: int) -> float:
    """Cycles for ``n_ops`` scalar-equivalent elementwise ops on the VPU."""
    return n_ops / VPU_OPS_PER_CYCLE


def hbm_cycles(n_bytes: int) -> float:
    """Cycles to move ``n_bytes`` HBM<->VMEM at full bandwidth."""
    return n_bytes / HBM_BW * CLOCK_HZ
