"""Padded rows over bucket rows dispatched in the window, in %, from the
server's per-batch valid and bucket row counts."""


def read(run):
    bucket = sum(b for _, b in run.dispatched)
    if not bucket:
        return None
    return 100.0 * (bucket - sum(v for v, _ in run.dispatched)) / bucket
