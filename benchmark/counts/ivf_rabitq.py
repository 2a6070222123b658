"""Least work of one re-ranked IVF-RaBitQ search call of `nq` query
rows, from the configuration's shapes alone, in `ivf_pq.py`'s
convention.

bytes: the packed sign codes (one bit a rotated dimension, rounded up
to whole 32-bit words) and the two float32 corrections of each row of
the distinct lists the call must probe (at most every list), the
re-rank shortlist's float32 rows, the queries, the coarse centers (in
the rotated space) and the rotation. ops: the rotation of the queries,
the coarse dot products against every center and the re-rank dot
products over the shortlist, counted at the bf16 peak.

The bit-plane AND and popcount of the codes is left out: the chip has
no peak for it in `peaks.json`, so it cannot be given a least time.
"""


def least(cfg: dict, nq: int) -> dict:
    n, dim, k = cfg["rows"], cfg["dim"], cfg["k"]
    ix, se = cfg["index"], cfg["search"]
    n_lists = ix["n_lists"]
    words = -(-dim // 32)
    rot = 32 * words
    shortlist = se["rerank_mult"] * k
    lists = min(n_lists, nq * se["n_probes"])
    code_bytes = lists * (n / n_lists) * (4 * words + 2 * 4)
    byts = (code_bytes + nq * shortlist * dim * 4 + nq * dim * 4
            + n_lists * rot * 4 + rot * dim * 4)
    ops = 2 * nq * dim * rot + 2 * nq * n_lists * rot + 2 * nq * shortlist * dim
    return {"bytes": byts, "ops": ops, "ops_dtype": "bf16"}
