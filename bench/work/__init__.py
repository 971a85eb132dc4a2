"""Operations and least bytes of each kernel family and each model step,
from logical shapes only (never block shapes or padding), each operand at
the narrowest dtype its semantics allow. A share computed from these
counts against a time at least the least time cannot pass 100%."""

from __future__ import annotations

from bench.peaks import rate


def least_s(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the peak of their precision and bytes over the HBM bandwidth."""
    return max(work["ops"] / rate(peaks, work["precision"]),
               work["bytes"] / peaks["hbm_bytes_per_s"])

