"""Public flash-attention op over (B, L, H, hd) layout."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bh
from repro.tracing import count, region


def legal_block(l: int, requested: int) -> int:
    """Largest block <= ``requested`` that tiles a length-``l`` sequence
    exactly, preferring sublane (8) multiples. Real sequence lengths are
    not always 128-multiples (e.g. VLM prefill = text + patch tokens), and
    the Pallas grid needs exact tiling — so bridge/default picks are
    clamped to a divisor instead of failing the kernel's assert."""
    divs = [b for b in range(1, min(requested, l) + 1) if l % b == 0]
    aligned = [b for b in divs if b % 8 == 0]
    return max(aligned or divs)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False) -> jax.Array:
    """q, k, v: (B, L, H, hd) with H already GQA-expanded. Block sizes are
    clamped to exact divisors of L (`legal_block`).

    Regions (``repro.tracing``): ``flash_attention.layout`` around the fold
    to (B*H, L, hd) and, apart, around the unfold; ``flash_attention.kernel``
    around the kernel. An eager call counts ``flash_attention.calls``."""
    b, l, h, hd = q.shape
    if not isinstance(q, jax.core.Tracer):
        count("flash_attention.calls")
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], hd)
    with region("flash_attention.layout"):
        qf, kf, vf = fold(q), fold(k), fold(v)
    with region("flash_attention.kernel"):
        out = flash_attention_bh(qf, kf, vf, causal=causal,
                                 block_q=legal_block(l, block_q),
                                 block_k=legal_block(k.shape[1], block_k),
                                 interpret=interpret)
    with region("flash_attention.layout"):
        return out.reshape(b, h, l, hd).transpose(0, 2, 1, 3)
