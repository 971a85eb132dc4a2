"""The SSD intra-chunk term, ``y_t = sum_{s <= t in t's chunk} (C_t . B_s)
exp(S_t - S_s) dt_s x_s``, over every chunk and head of ``batch`` sequences
of ``length`` positions: one ``ssd_scan`` call. The score and the value
products count 2 * (state + head_dim) operations per visible pair of a
chunk; B and C are read once per group, the cumulative decay and dt once
per head in float32, x read and y written once in bf16."""

from __future__ import annotations

from bench.work.flash_attention import causal_pairs


def chunk_pairs(length: int, chunk: int) -> int:
    """Visible (t, s) pairs within the chunks of ``length`` positions: the
    whole chunks' and the last, partial one's."""
    whole, rest = divmod(length, chunk)
    return whole * causal_pairs(chunk, chunk) + causal_pairs(rest, rest)


def work(batch: int, length: int, heads: int, groups: int, state: int,
         head_dim: int, chunk: int) -> dict:
    ops = 2.0 * (state + head_dim) * chunk_pairs(length, chunk) * heads \
        * batch
    rows = batch * length
    nbytes = rows * (2 * groups * state * 2 + 2 * heads * 4
                     + 2 * heads * head_dim * 2)
    return {"ops": ops, "bytes": float(nbytes), "precision": "bf16"}
