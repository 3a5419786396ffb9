"""KernelIP — one entry of the adaptive IP library.

The paper ships four VHDL IPs, each a (behaviour, resource-contract)
pair.  Here an IP is a callable plus a ``footprint(shape)`` function that
prices it against the TPU resource vector, plus the static capability
bits from paper Table I (operand-width ceiling, outputs per pass,
whether it needs the MXU).

``SiteSpec`` / ``SiteRequest`` are the planner-facing half of the
contract: a family registers a *site adapter* (``IPFamily.site_adapter``,
populated in ``core/library.py``) that translates a declarative op site
— family, shapes, dtype, knobs — into the candidate set and footprint
arguments the generic selection engine (``core/plan.py``) prices.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from repro.core.resources import Footprint, ResourceBudget


def _freeze(value):
    """Normalize knob/shape values to hashable, JSON-stable forms."""
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


# Widths a precision ladder may assign — exactly the widths the quant
# execution layer (repro.quant.ops) can run — and the fixed-point dtype
# a lowered site is priced (and, for int8, executed) at.  A native-width
# rung is never "lowered", so 32 is deliberately NOT a legal ladder
# entry: it would plan a lowering the runtime rejects.
LADDER_WIDTHS = (16, 8)
WIDTH_DTYPES = {8: "int8", 16: "int16"}


def kernel_dtype(dtype: str) -> str:
    """The dtype a site's operands reach its kernel in: the 16-bit rung
    is fake-quant, float32 arithmetic on the int16 grid
    (``repro.quant.ops``); every other width runs as itself."""
    return "float32" if dtype == WIDTH_DTYPES[16] else dtype


@dataclasses.dataclass(frozen=True)
class SiteSpec:
    """One op site of a network graph, declaratively.

    Hashable (it is the planner's cache-key unit) and JSON-serializable.
    ``shapes`` holds the operand shapes the family adapter expects (e.g.
    ``(x_shape, w_shape)`` for conv2d); ``knobs`` are the op-level
    switches (``dual``, ``mode``, ``kind``, ``window``...) as a sorted
    tuple of pairs so equal specs hash equally.

    ``ladder`` is the site's *precision ladder*: the operand widths (in
    bits, e.g. ``(16, 8)``) the planner may quantize this site down to
    when it cannot fit at its native width (docs/adaptive_ips.md,
    "Precision contract").  Empty means the native width is the only
    legal one — the pre-ladder behavior.
    """

    name: str
    family: str
    shapes: Tuple[Tuple[int, ...], ...]
    dtype: str = "float32"
    knobs: Tuple[Tuple[str, Any], ...] = ()
    ladder: Tuple[int, ...] = ()

    @classmethod
    def make(cls, name: str, family: str, shapes, dtype="float32",
             ladder=(), **knobs) -> "SiteSpec":
        import jax.numpy as jnp
        norm_shapes = tuple(tuple(int(d) for d in s) for s in shapes)
        norm_knobs = tuple(sorted((k, _freeze(v)) for k, v in knobs.items()))
        norm_ladder = tuple(sorted({int(b) for b in ladder}, reverse=True))
        for b in norm_ladder:
            if b not in LADDER_WIDTHS:
                raise ValueError(f"unsupported ladder width {b}; "
                                 f"have {sorted(LADDER_WIDTHS)}")
        return cls(name=name, family=family, shapes=norm_shapes,
                   dtype=jnp.dtype(dtype).name, knobs=norm_knobs,
                   ladder=norm_ladder)

    def knob(self, key: str, default=None):
        for k, v in self.knobs:
            if k == key:
                return v
        return default

    @property
    def native_bits(self) -> int:
        """Physical width of the caller's operands at this site."""
        import jax.numpy as jnp
        return jnp.dtype(self.dtype).itemsize * 8

    def widths(self) -> Tuple[int, ...]:
        """Widths the planner may try, native first then the ladder's
        strictly-narrower rungs in descending order."""
        native = self.native_bits
        return (native,) + tuple(b for b in self.ladder if b < native)

    def at_precision(self, bits: int) -> "SiteSpec":
        """This site lowered to ``bits``-wide fixed-point operands (the
        spec the family adapter prices); native width returns self."""
        if bits >= self.native_bits:
            return self
        return dataclasses.replace(self, dtype=WIDTH_DTYPES[bits])

    def to_dict(self) -> dict:
        return {"name": self.name, "family": self.family,
                "shapes": [list(s) for s in self.shapes],
                "dtype": self.dtype,
                "knobs": {k: list(v) if isinstance(v, tuple) else v
                          for k, v in self.knobs},
                "ladder": list(self.ladder)}

    @classmethod
    def from_dict(cls, d: dict) -> "SiteSpec":
        return cls.make(d["name"], d["family"], d["shapes"], d["dtype"],
                        ladder=d.get("ladder", ()), **d.get("knobs", {}))


@dataclasses.dataclass(frozen=True)
class SiteRequest:
    """What a family's site adapter hands the selection engine: the
    candidate members to price, the arguments their footprint functions
    take for this site, and the physical operand width of the caller's
    data (0 when the member re-encodes on ingest — see
    docs/adaptive_ips.md)."""

    candidates: Tuple["KernelIP", ...]
    fp_args: Tuple
    fp_kwargs: Tuple[Tuple[str, Any], ...] = ()
    op_bits: int = 32


@dataclasses.dataclass(frozen=True)
class KernelIP:
    name: str                 # e.g. "conv2d.ip3_packed"
    family: str               # "conv2d" | "matmul" | "attention"
    impl: Callable[..., Any]  # the jit-able implementation
    footprint_fn: Callable[..., Footprint]
    description: str = ""
    # Static capability bits (paper Table I columns):
    uses_mxu: bool = True
    max_operand_bits: int = 32
    outputs_per_pass: int = 1
    supports_dtypes: Tuple[str, ...] = ("int8", "bfloat16", "float32")
    # Operand dtypes Mosaic compiles this member for on the TPU; the
    # interpreter runs every dtype.  The planner never offers a member at
    # a dtype it cannot compile (``IPFamily.plan_site``).
    compiled_dtypes: Tuple[str, ...] = ("int8", "bfloat16", "float32")
    tags: Tuple[str, ...] = ()

    def footprint(self, *shape_args, **shape_kwargs) -> Footprint:
        fp = self.footprint_fn(*shape_args, **shape_kwargs)
        # The static ceiling is authoritative; a footprint_fn may tighten
        # it per-shape but never widen it.
        return dataclasses.replace(
            fp, max_operand_bits=min(fp.max_operand_bits, self.max_operand_bits),
            outputs_per_pass=self.outputs_per_pass)

    def feasible(self, budget: ResourceBudget, *shape_args, **shape_kwargs) -> bool:
        return self.footprint(*shape_args, **shape_kwargs).fits(budget)

    def compiles(self, dtype: str) -> bool:
        """Whether this member runs on the current backend at a site of
        ``dtype`` (a ``SiteSpec.dtype``)."""
        from repro.kernels import interpret
        return interpret() or kernel_dtype(dtype) in self.compiled_dtypes

    def __call__(self, *args, **kwargs):
        return self.impl(*args, **kwargs)


@dataclasses.dataclass
class IPFamily:
    """All IPs implementing one op contract (same ref.py oracle).

    ``site_adapter`` makes the family plannable: it maps a ``SiteSpec``
    to a ``SiteRequest`` so the generic engine in ``core/plan.py`` can
    select for this family without family-specific code.

    ``quantizable`` gates the precision ladder: only families with a
    real fixed-point execution path (``repro.quant.ops``) may have their
    sites lowered below native width.  Attention and the SSM scan have
    no integer kernels, so pricing them at int8 would promise a plan the
    runtime cannot execute.

    **Fusion contract** (docs/adaptive_ips.md, "Fusion contract"): a
    family whose members absorb a *chain* of op families into one launch
    declares its chains in ``fuse_chains`` (each in program order, e.g.
    ``(("conv2d", "pool2d", "activation"),)``; several chains longest
    first) and registers a ``fuse_sites`` adapter mapping that many
    adjacent SiteSpecs to the single fused SiteSpec — or ``None`` when
    the run is not fusable (wrong knobs, shapes that don't chain).
    ``plan_network(..., fuse=True)`` scans every planned graph for such
    runs generically; it never hard-codes a family.
    """

    name: str
    members: Dict[str, KernelIP] = dataclasses.field(default_factory=dict)
    reference: Optional[Callable[..., Any]] = None
    site_adapter: Optional[Callable[[SiteSpec], SiteRequest]] = None
    quantizable: bool = True
    fuse_sites: Optional[Callable[[Tuple[SiteSpec, ...]],
                                  Optional[SiteSpec]]] = None
    fuse_chains: Tuple[Tuple[str, ...], ...] = ()

    def plan_site(self, spec: SiteSpec) -> SiteRequest:
        if spec.family != self.name:
            raise ValueError(f"site {spec.name!r} is a {spec.family!r} site, "
                             f"not {self.name!r}")
        if self.site_adapter is None:
            raise NotImplementedError(
                f"family {self.name!r} has no site adapter registered; "
                "it cannot be planned (see docs/adaptive_ips.md)")
        req = self.site_adapter(spec)
        runnable = tuple(ip for ip in req.candidates
                         if ip.compiles(spec.dtype))
        if runnable == req.candidates:
            return req
        return dataclasses.replace(req, candidates=runnable)

    def register(self, ip: KernelIP) -> KernelIP:
        if ip.name in self.members:
            raise ValueError(f"duplicate IP {ip.name!r} in family {self.name!r}")
        self.members[ip.name] = ip
        return ip

    def __iter__(self):
        return iter(self.members.values())

    def __getitem__(self, name: str) -> KernelIP:
        if name in self.members:
            return self.members[name]
        # allow short names: "ip3_packed" for "conv2d.ip3_packed"
        qual = f"{self.name}.{name}"
        if qual in self.members:
            return self.members[qual]
        raise KeyError(f"no IP {name!r} in family {self.name!r}; "
                       f"have {sorted(self.members)}")

    def names(self) -> Sequence[str]:
        return sorted(self.members)
