"""Time of the IVF-PQ build's coarse stage (`ivf_pq.build.coarse`:
trainset and balanced k-means, ended by a sync on its outputs) in
set-up, in s, from `run.setup_spans` ({name: (count, seconds)})."""


def read(run):
    spans = getattr(run, "setup_spans", None) or {}
    count, total = spans.get("ivf_pq.build.coarse", (0, 0.0))
    return total if count else None
