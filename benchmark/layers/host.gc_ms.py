"""Garbage-collection pauses inside the window, in ms: the `gc` entry of
`run.spans` ({name: (count, seconds)}, the program's spans in the
window's profiler trace), which holds the `raft_tpu.gc` span the obs
hook writes for each collection. A run that recorded its spans with obs
enabled, and so with the hook installed, reads 0 when the window held
no collection; a run with no `run.spans` reads nothing."""


def read(run):
    spans = getattr(run, "spans", None)
    if spans is None:
        return None
    return 1e3 * spans.get("gc", (0, 0.0))[1]
