"""Softmax attention of ``lq`` queries over ``lk`` keys, per head: the
score and the value products count 2 * hd operations per visible pair
each; q, k, v and the output are read or written once, in bf16."""


def causal_pairs(lq: int, lk: int) -> int:
    """Visible (query, key) pairs when the queries are the last ``lq`` of
    ``lk`` positions: L(L+1)/2 where lq == lk."""
    off = lk - lq
    return sum(min(lk, i + 1 + off) for i in range(lq))


def work(b: int, lq: int, lk: int, h: int, hd: int, causal: bool = True,
         kv_heads: int | None = None) -> dict:
    kv_heads = h if kv_heads is None else kv_heads
    pairs = causal_pairs(lq, lk) if causal else lq * lk
    return {"ops": 4.0 * hd * b * h * pairs,
            "bytes": 2.0 * b * hd * (2 * h * lq + 2 * kv_heads * lk),
            "precision": "bf16"}
