"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB of HBM at 819 GB/s per chip.
A device whose kind is not in the table is an error, never a default.
"""

from __future__ import annotations

SOURCE = "Google Cloud documentation, 'TPU v5e' system architecture"

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 1024 ** 3,
    },
}


def peaks(kind: str) -> dict:
    """The table's row for ``kind``; an unknown kind raises."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no peaks known for device kind {kind!r} "
                         f"(known: {sorted(PEAKS)})") from None


def rate(row: dict, precision: str) -> float:
    """A table row's peak operations per second at ``precision``
    (``bf16`` or ``int8``)."""
    return {"bf16": row["bf16_flops"], "int8": row["int8_ops"]}[precision]
