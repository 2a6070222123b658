"""Least work of one exact search call of `nq` query rows: one read of
the float32 corpus and the queries, and a dot product of every query
with every row, counted at the bf16 peak."""


def least(cfg: dict, nq: int) -> dict:
    n, dim = cfg["rows"], cfg["dim"]
    return {"bytes": (n + nq) * dim * 4, "ops": 2 * nq * n * dim,
            "ops_dtype": "bf16"}
