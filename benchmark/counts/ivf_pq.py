"""Least work of one refined IVF-PQ search call of `nq` query rows,
from the configuration's shapes alone.

bytes: the PQ codes of the distinct lists the call must probe (at most
every list), the refine shortlist's float32 rows, the queries and the
coarse centers. ops: the coarse dot products against every center and
the refine dot products over the shortlist, counted at the bf16 peak.
"""


def least(cfg: dict, nq: int) -> dict:
    n, dim, k = cfg["rows"], cfg["dim"], cfg["k"]
    ix, se = cfg["index"], cfg["search"]
    n_lists = ix["n_lists"]
    shortlist = se["refine_mult"] * k
    lists = min(n_lists, nq * se["n_probes"])
    code_bytes = lists * (n / n_lists) * ix["pq_dim"] * ix["pq_bits"] / 8
    byts = (code_bytes + nq * shortlist * dim * 4 + nq * dim * 4
            + n_lists * dim * 4)
    ops = 2 * nq * n_lists * dim + 2 * nq * shortlist * dim
    return {"bytes": byts, "ops": ops, "ops_dtype": "bf16"}
