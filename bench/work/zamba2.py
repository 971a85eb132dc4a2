"""A Zamba2 step (``bench/refs/zamba2.py`` gives the layer equations): a
Mamba2 mixer in every layer, and on each hybrid layer one application of
a shared transformer block to concat(hidden, embedding) with its own
adapters and linear. ``m`` is the configuration's ``model`` dict; weights
bf16, norm scales and the SSM's per-head parameters f32, as the model
holds them. A shared block's weights count once per application: the
applications depend on each other in turn and a block does not stay in
on-chip memory between them. The SSM state is read and written in f32
for every layer and row, the conv state in bf16. A prefill's LM head
counts only the last position, the one the step returns; its SSD counts
each chunk's causal pairs and the chunk states' products."""

from __future__ import annotations

import math

from bench.work.flash_attention import causal_pairs
from bench.work.ssd_scan import chunk_pairs

BF16, F32 = 2, 4
CHUNK = 256


def _dims(m: dict) -> dict:
    d = m["d_model"]
    d_in = m["ssm_expand"] * d
    g, n, p = m["ssm_groups"], m["ssm_state"], m["ssm_head_dim"]
    heads = d_in // p
    hd = m.get("head_dim") or 2 * d // m["n_heads"]
    r = m.get("adapter_rank", 0)
    ff = m["d_ff"]
    conv_dim = d_in + 2 * g * n
    adapters = 0
    if m.get("shared_attn_adapter"):
        adapters += r * (3 * 2 * d + hd * (m["n_heads"]
                                           + 2 * m["n_kv_heads"]))
    if m.get("shared_mlp_adapter"):
        adapters += r * (d + 2 * ff)
    return {
        "d": d, "d_in": d_in, "g": g, "n": n, "p": p, "heads": heads,
        "hd": hd, "conv_dim": conv_dim, "apps": len(m["hybrid_layer_ids"]),
        # products with a weight, per token
        "mamba_mm": d * (2 * d_in + 2 * g * n + heads) + d_in * d,
        "shared_mm": 2 * d * hd * (m["n_heads"] + 2 * m["n_kv_heads"])
        + m["n_heads"] * hd * d + 3 * d * ff,
        "app_mm": d * d + adapters,
        "vocab_rows": math.ceil(m["vocab_size"] / 2048) * 2048,
    }


def _layer_bytes(x: dict, m: dict) -> int:
    """One Mamba layer's parameters."""
    return (x["mamba_mm"] * BF16 + (m["ssm_conv"] + 1) * x["conv_dim"] * BF16
            + 3 * x["heads"] * F32 + x["d_in"] * F32 + x["d"] * F32)


def _app_bytes(x: dict) -> int:
    """One application's reads of weights: its shared block's and its own."""
    return (x["shared_mm"] + x["app_mm"]) * BF16 + 3 * x["d"] * F32


def weight_bytes(m: dict) -> int:
    x = _dims(m)
    blocks = m["num_mem_blocks"] * (x["shared_mm"] * BF16 + 3 * x["d"] * F32)
    return (m["n_layers"] * _layer_bytes(x, m) + blocks
            + x["apps"] * x["app_mm"] * BF16
            + x["vocab_rows"] * x["d"] * BF16 + x["d"] * F32)


def _streamed_weight_bytes(m: dict, x: dict) -> int:
    return (m["n_layers"] * _layer_bytes(x, m) + x["apps"] * _app_bytes(x)
            + x["d"] * F32 + m["vocab_size"] * x["d"] * BF16)


def _per_token_ops(m: dict, x: dict) -> int:
    """Products with a weight and the convolution, one token, all layers."""
    return 2 * (m["n_layers"] * (x["mamba_mm"] + m["ssm_conv"]
                                 * x["conv_dim"])
                + x["apps"] * (x["shared_mm"] + x["app_mm"]))


def decode(m: dict, batch: int, context: int) -> dict:
    x = _dims(m)
    state = x["heads"] * x["p"] * x["n"]
    ops = (batch * _per_token_ops(m, x)
           + m["n_layers"] * batch * 4 * state          # update, readout
           + x["apps"] * batch * m["n_heads"] * 4 * x["hd"] * context
           + 2 * batch * x["d"] * m["vocab_size"])
    kv = x["apps"] * batch * context * m["n_kv_heads"] * x["hd"] * 2 * BF16
    states = m["n_layers"] * batch * 2 * (
        state * F32 + (m["ssm_conv"] - 1) * x["conv_dim"] * BF16)
    nbytes = (_streamed_weight_bytes(m, x) + batch * x["d"] * BF16 + kv
              + states + batch * m["vocab_size"] * BF16)
    return {"ops": float(ops), "bytes": float(nbytes), "precision": "bf16"}


def prefill(m: dict, batch: int, length: int) -> dict:
    x = _dims(m)
    # the intra-chunk pairs' products, and each position's into its
    # chunk's state and out of the state before it
    ssd = x["heads"] * (2 * (x["n"] + x["p"]) * chunk_pairs(length, CHUNK)
                        + 4 * length * x["n"] * x["p"])
    ops = (batch * length * _per_token_ops(m, x)
           + m["n_layers"] * batch * ssd
           + x["apps"] * batch * m["n_heads"] * 4 * x["hd"]
           * causal_pairs(length, length)
           + 2 * batch * x["d"] * m["vocab_size"])
    kv = x["apps"] * batch * length * m["n_kv_heads"] * x["hd"] * 2 * BF16
    states = m["n_layers"] * batch * (
        x["heads"] * x["p"] * x["n"] * F32
        + (m["ssm_conv"] - 1) * x["conv_dim"] * BF16)
    nbytes = (_streamed_weight_bytes(m, x) + batch * length * x["d"] * BF16
              + kv + states + batch * m["vocab_size"] * BF16)
    return {"ops": float(ops), "bytes": float(nbytes), "precision": "bf16"}
