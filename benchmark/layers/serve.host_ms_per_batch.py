"""Host time of the serve worker's own steps per served batch, in ms:
the window's spans `serve.take`, `.merge`, `.dispatch`, `.scatter` and
`.maintain` over its count of `serve.batch`, from `run.spans` ({name:
(count, seconds)}, the program's spans in the window's profiler trace).

Read with the profiler's Python tracer off: with it on, the tracer's
own cost on the worker's Python is most of these spans' time."""

STEPS = ("serve.take", "serve.merge", "serve.dispatch", "serve.scatter",
         "serve.maintain")


def read(run):
    spans = getattr(run, "spans", None) or {}
    batches = spans.get("serve.batch", (0, 0.0))[0]
    if not batches or not any(spans.get(s, (0,))[0] for s in STEPS):
        return None
    return 1e3 * sum(spans.get(s, (0, 0.0))[1] for s in STEPS) / batches
