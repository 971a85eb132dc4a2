"""A dense decoder step: pre-norm attention and a (gated) MLP in every
layer. ``m`` is the configuration's ``model`` dict; weights bf16 and norm
scales f32, as the model holds them. A prefill's LM head counts only the
last position, the one the step returns."""

from __future__ import annotations

import math

BF16, F32 = 2, 4


def _dims(m: dict) -> dict:
    d = m["d_model"]
    return {"d": d, "hd": m.get("head_dim") or d // m["n_heads"],
            "vocab_rows": math.ceil(m["vocab_size"] / 2048) * 2048}


def _layer_matmul(m: dict, x: dict) -> int:
    d, hd = x["d"], x["hd"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return attn + (3 if m.get("gated_mlp", True) else 2) * d * m["d_ff"]


def weight_bytes(m: dict) -> int:
    x = _dims(m)
    d = x["d"]
    layer = _layer_matmul(m, x) * BF16 + 2 * d * F32
    tables = (1 if m.get("tie_embeddings") else 2) * x["vocab_rows"] * d \
        * BF16
    return m["n_layers"] * layer + tables + d * F32


def _streamed_weight_bytes(m: dict, x: dict) -> int:
    d = x["d"]
    return (m["n_layers"] * (_layer_matmul(m, x) * BF16 + 2 * d * F32)
            + d * F32 + m["vocab_size"] * d * BF16)


def prefill(m: dict, batch: int, length: int) -> dict:
    x = _dims(m)
    pairs = length * (length + 1) // 2
    ops = (batch * length * m["n_layers"] * 2 * _layer_matmul(m, x)
           + m["n_layers"] * batch * m["n_heads"] * 4 * x["hd"] * pairs
           + 2 * batch * x["d"] * m["vocab_size"])
    kv = m["n_layers"] * batch * length * m["n_kv_heads"] * x["hd"] * 2 \
        * BF16
    nbytes = (_streamed_weight_bytes(m, x) + batch * length * x["d"] * BF16
              + kv + batch * m["vocab_size"] * BF16)
    return {"ops": float(ops), "bytes": float(nbytes), "precision": "bf16"}


def decode(m: dict, batch: int, context: int) -> dict:
    x = _dims(m)
    ops = (batch * m["n_layers"] * 2 * _layer_matmul(m, x)
           + m["n_layers"] * batch * m["n_heads"] * 4 * x["hd"] * context
           + 2 * batch * x["d"] * m["vocab_size"])
    kv = m["n_layers"] * batch * context * m["n_kv_heads"] * x["hd"] * 2 \
        * BF16
    nbytes = (_streamed_weight_bytes(m, x) + batch * x["d"] * BF16 + kv
              + batch * m["vocab_size"] * BF16)
    return {"ops": float(ops), "bytes": float(nbytes), "precision": "bf16"}
