"""W8A8 matmul (M, K) @ (K, N): int8 operands, a bf16 result."""


def work(m: int, k: int, n: int) -> dict:
    return {"ops": 2.0 * m * k * n,
            "bytes": float(m * k + k * n + 2 * m * n),
            "precision": "int8"}
