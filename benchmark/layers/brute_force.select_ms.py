"""Device self time of the `brute_force.select` stage (the per-tile
select-k and the running merge) per served batch in the window, in ms."""

from benchmark.scopes import per_call_ms


def read(run):
    return per_call_ms(run, "brute_force.select")
