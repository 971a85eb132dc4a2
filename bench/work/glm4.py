"""A GLM-4 decode step split over the chips of a tensor-parallel mesh:
the dense step (``bench/work/dense.py``) plus the bias on the query, key
and value projections, divided evenly over ``chips``. A share against
one chip's peaks then reads the same as the whole step's against the
mesh's. Copies that the split itself adds (the K/V projections held on
every chip, the collectives) are not in the least work."""

from __future__ import annotations

from bench.work import dense


def decode(m: dict, batch: int, context: int, chips: int) -> dict:
    step = dense.decode(m, batch, context)
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    bias = m["n_layers"] * hd * (m["n_heads"] + 2 * m["n_kv_heads"])
    return {"ops": (step["ops"] + batch * bias) / chips,
            "bytes": (step["bytes"] + bias * dense.BF16) / chips,
            "precision": step["precision"]}
