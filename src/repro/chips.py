"""Peak rates and memory of the TPU chips this repository targets, keyed by
``jax.Device.device_kind``.

One table serves every consumer: the TPU bridge's block-shape MIP
(`core/tpu_bridge.py`), the kernels' VMEM request and the chip smoke run,
which refuses a device whose kind is missing here instead of assuming
another chip's peaks.
"""

from __future__ import annotations

import dataclasses

MiB = 1 << 20


@dataclasses.dataclass(frozen=True)
class Chip:
    bf16_flops: float          # peak MXU rate, bf16, per chip
    int8_ops: float            # peak MXU rate, int8, per chip
    hbm_bytes: int
    hbm_bw: float              # bytes/s
    ici_bw: float              # bytes/s per link
    ici_links: int             # usable links per chip on the 2D torus
    vmem_bytes: int            # VMEM of one TensorCore
    source: str


CHIPS = {
    "TPU v5 lite": Chip(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16 * 1024 * MiB,
        hbm_bw=819e9, ici_bw=50e9, ici_links=4, vmem_bytes=128 * MiB,
        source="Google Cloud documentation, 'TPU v5e' (peaks, HBM, "
               "1,600 Gbit/s ICI over 4 links); VMEM from the Pallas TPU "
               "docs"),
}

#: The chip the bridge and the kernels plan for.
TARGET_KIND = "TPU v5 lite"


def chip(kind: str = TARGET_KIND) -> Chip:
    """Table entry for ``kind``; an unknown kind is an error, not a
    default."""
    try:
        return CHIPS[kind]
    except KeyError:
        raise ValueError(f"no peaks known for device kind {kind!r}; "
                         f"known: {sorted(CHIPS)}") from None


#: Scoped VMEM the kernels ask the compiler for (the default scope on v5e
#: is 16 MiB). Three quarters of the core: the bridge budgets half of it
#: for the blocks eq. 9 counts, and Pallas also double-buffers the output
#: block, which eq. 9 does not count.
VMEM_LIMIT_BYTES = chip().vmem_bytes * 3 // 4
