"""Device self time of the IVF-PQ select-k stages, `ivf_pq.trim` (the
per-superblock approximate top-k) and `ivf_pq.merge` (the regroup and
the final select-k), per search call in the window, in ms."""

from benchmark.scopes import per_call_ms


def read(run):
    return per_call_ms(run, "ivf_pq.trim", "ivf_pq.merge")
