"""Device self time of the `ivf_pq.scan` stage (list scoring and the
query-row gather) per search call in the window, in ms."""

from benchmark.scopes import per_call_ms


def read(run):
    return per_call_ms(run, "ivf_pq.scan")
