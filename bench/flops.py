"""Operations and minimum HBM bytes of one kernel call.

Each network counts its own calls from the configuration's shapes
(``calls`` and ``frame_flops`` in ``bench/networks/<network>.py``).
"""
from __future__ import annotations

import dataclasses


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
