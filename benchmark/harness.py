"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result.

    cell = Cell(root, "deep1m-ivfpq.serve", seed, t_start)
    cell.setup()                      # data, build: set-up
    win = cell.window(seconds, trace) # warm-up (set-up), then the window
    numbers = cell.compare(win)       # after the window, off the clock

`run_cell` strings these together as the contract orders them: the
peak memory is read and the program's state freed before the reference
runs. Everything a later PR adds (a configuration, a traffic mix, an
index kind, a per-layer metric) is found by name through `spec`.
"""

from __future__ import annotations

import gc
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np

from benchmark import datagen, load, reference, spec, traces
from raft_tpu import obs, serve

#: how long after the window's close an answer may still come
LATE_WAIT_S = 60.0
#: the serve stage histograms (raft_tpu.obs.trace) a traced run reads
STAGES = ("serve.stage.queue_wait_s", "serve.stage.linger_s")


def log(**fields) -> None:
    """An earlier line of standard output (the result is the last)."""
    print(json.dumps(fields, default=str), flush=True)


class CompileMeter:
    """Sums JAX's own compile and persistent-cache-read durations while
    installed; `take()` returns and resets them."""

    _EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._acc = {"compile_s": 0.0, "cache_read_s": 0.0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key is None:
            return
        with self._lock:
            self._acc[key] += float(duration)
            if key == "compile_s":
                self._acc["compiles"] += 1

    def take(self) -> dict:
        with self._lock:
            out = dict(self._acc)
            self._acc = {k: 0 * v for k, v in out.items()}
        return out


def peak_bytes():
    """Peak device memory of the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _Window:
    """The measured window: host clock, the `bench.window` host span
    that the trace reduction reads, and (traced runs) the profiler."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.logdir = None

    def __enter__(self):
        if self.trace:
            self.logdir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.logdir)
        self._span = jax.profiler.TraceAnnotation(traces.WINDOW)
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)
        if self.trace:
            jax.profiler.stop_trace()
        return False

    def reduce(self):
        """The trace's numbers (None if it holds no device op); the
        trace's files are deleted once read."""
        if self.logdir is None:
            return None
        try:
            return traces.reduce(traces.load(traces.find_xplane(self.logdir)))
        finally:
            shutil.rmtree(self.logdir, ignore_errors=True)


class Stamps(serve.ServerMetrics):
    """A `serve.ServerMetrics` that also stamps when each tracked
    request's reply was set. The engine calls `observe_batch` right after
    it sets a batch's replies, and `observe_failed`/`observe_expired`
    after it fails requests; each call stamps the tracked requests that
    are done by then."""

    def __init__(self, scan_limit: int):
        super().__init__()
        self._stamp_lock = threading.Lock()
        self._pending = {}  # request index -> future, in submit order
        self.done_t = {}
        self.dispatched = []  # (valid rows, bucket rows) per batch
        self._scan_limit = scan_limit

    def track(self, i: int, fut) -> None:
        with self._stamp_lock:
            if fut.done():
                self.done_t[i] = time.monotonic()
            else:
                self._pending[i] = fut

    def outstanding(self) -> int:
        with self._stamp_lock:
            return len(self._pending)

    def _stamp(self) -> None:
        # replies go out nearly in submit order: stop after a run of
        # not-done requests longer than any batch
        now = time.monotonic()
        with self._stamp_lock:
            done, misses = [], 0
            for i, fut in self._pending.items():
                if fut.done():
                    done.append(i)
                    misses = 0
                else:
                    misses += 1
                    if misses > self._scan_limit:
                        break
            for i in done:
                del self._pending[i]
                self.done_t[i] = now

    def observe_batch(self, n_requests, valid_rows, bucket_rows,
                      latencies_s, coverage=None):
        self._stamp()
        with self._stamp_lock:
            self.dispatched.append((int(valid_rows), int(bucket_rows)))
        super().observe_batch(n_requests, valid_rows, bucket_rows,
                              latencies_s, coverage)

    def observe_failed(self, n=1):
        self._stamp()
        super().observe_failed(n)

    def observe_expired(self, n=1, wait_s=None):
        self._stamp()
        super().observe_expired(n, wait_s)


def _stage_totals():
    return {s: (obs.histogram(s).count, obs.histogram(s).total)
            for s in STAGES}


class Cell:
    """One cell's run, in the steps the module docstring lists."""

    def __init__(self, root: str, name: str, seed: int, t_start: float):
        self.root, self.name, self.seed = root, name, int(seed)
        self.t_start = t_start
        self.bench = spec.load_bench(root)
        self.cell = spec.workload(self.bench, name)
        self.cfg = spec.config(root, self.bench, self.cell["config"])
        self.traffic = spec.traffic(root, self.cell["traffic"])
        self.kind = spec.module(root, "indexes", self.cfg["kind"])
        try:
            self.counts = spec.module(root, "counts", self.cfg["kind"])
        except FileNotFoundError:
            self.counts = None
        self.meter = CompileMeter()

    def setup(self) -> None:
        t = time.perf_counter()
        self.dataset, self.queries = datagen.make(self.cfg, self.seed)
        self.pool = np.asarray(self.queries)
        data_s = time.perf_counter() - t
        t = time.perf_counter()
        self.state = self.kind.build(
            self.cfg, self.dataset, datagen.corpus_seed(self.cfg, self.seed))
        self.build_s = time.perf_counter() - t
        log(phase="data_build", cell=self.name, seed=self.seed,
            data_s=data_s, build_s=self.build_s, **self.meter.take())

    def window(self, seconds: float, trace: bool = False,
               variant: str = "program") -> SimpleNamespace:
        loop = {"closed": self._closed, "open": self._open}
        win = loop[self.traffic["loop"]](seconds, trace, variant)
        win.setup_s = win.w.t0 - self.t_start
        log(phase="window", variant=variant, setup_s=win.setup_s,
            window_s=win.w.t1 - win.w.t0, attempted=win.attempted,
            failed=win.failed, device_calls=len(win.calls),
            compiles_in_window=win.compiles_in_window, **win.extra)
        return win

    # -- closed loop: one call in flight ------------------------------

    def _closed(self, seconds, trace, variant):
        tr = self.traffic
        k, rows = int(tr["k"]), int(tr["rows_per_call"])
        # the pool's first rows, in an order drawn from the seed
        order = load.rng_for(self.seed, 2).permutation(rows)
        q = jax.block_until_ready(self.queries[jax.numpy.asarray(order)])
        fn = self.kind.search_fn(self.state, self.cfg, variant)
        jax.block_until_ready(fn(q, k))  # compiles this call's programs
        log(phase="warm", **self.meter.take())
        outs = []
        with _Window(trace) as w:
            while True:
                with jax.profiler.TraceAnnotation("bench.call"):
                    outs.append(jax.block_until_ready(fn(q, k)))
                if time.perf_counter() - w.t0 >= seconds:
                    break
        compiles = self.meter.take()["compiles"]
        # a sample of (call, row) answers, drawn from the seed
        rng = load.rng_for(self.seed, 1)
        n_check = min(int(tr["check_rows"]), len(outs) * rows)
        pick = rng.choice(len(outs) * rows, n_check, replace=False)
        calls, rws = pick // rows, pick % rows
        vals = np.empty((n_check, k), np.float32)
        ids = np.empty((n_check, k), np.int64)
        for c in np.unique(calls):
            sel = calls == c
            vals[sel] = np.asarray(outs[c][0])[rws[sel]]
            ids[sel] = np.asarray(outs[c][1])[rws[sel]]
        return SimpleNamespace(
            w=w, attempted=len(outs), failed=0, missing=0,
            e2e={"qps": len(outs) * rows / (w.t1 - w.t0)},
            calls=[rows] * len(outs), dispatched=[], stages=None,
            check=(order[rws], vals, ids), compiles_in_window=compiles,
            extra={})

    # -- open loop: Poisson arrivals into SearchServer.submit ---------

    def _open(self, seconds, trace, variant):
        tr = self.traffic
        k = int(tr["k"])
        sched = load.open_schedule(tr, self.seed, seconds)
        reqs = [self.pool[r] for r in sched.rows]
        n = len(reqs)
        if trace:
            obs.enable()
        config = serve.ServerConfig(
            warmup_k=k,
            # room for the whole burst, and no probe ever shed: shedding
            # changes answers
            admission=serve.AdmissionConfig(
                max_pending_rows=int(tr["queue_rows"]), min_probe_scale=1.0))
        stamps = Stamps(scan_limit=2 * max(config.buckets))
        server = serve.SearchServer(
            self.kind.searcher(self.state, self.cfg, variant,
                               config.buckets),
            config, metrics=stamps)
        server.start()  # compiles every bucket at k
        log(phase="warm", buckets=list(config.buckets), **self.meter.take())
        stages0 = _stage_totals() if trace else None
        late = np.zeros(n)
        replies = [None] * n
        errors = {}
        try:
            with _Window(trace) as w:
                t0 = time.monotonic()
                futs = {}
                backlog_mid = 0
                for i in range(n):
                    due = t0 + sched.due_s[i]
                    now = time.monotonic()
                    if due > now:
                        with jax.profiler.TraceAnnotation("bench.wait"):
                            time.sleep(due - now)
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        late[i] = time.monotonic() - due
                        try:
                            futs[i] = server.submit(reqs[i], k)
                        except Exception as e:  # rejected: never answered
                            errors[i] = repr(e)
                            continue
                    stamps.track(i, futs[i])
                    if i == n // 2:
                        backlog_mid = stamps.outstanding()
                backlog = stamps.outstanding()
                with jax.profiler.TraceAnnotation("bench.collect"):
                    close = t0 + seconds + LATE_WAIT_S
                    for i, fut in futs.items():
                        try:
                            replies[i] = fut.result(
                                timeout=max(0.0, close - time.monotonic()))
                        except Exception as e:  # failed, or never came
                            errors[i] = repr(e)
        finally:
            server.stop()
        compiles = self.meter.take()["compiles"]
        now = time.monotonic()
        lat = np.array([(stamps.done_t.get(i, now) - t0 - sched.due_s[i])
                        for i in range(n) if replies[i] is not None]) * 1e3
        stages = None
        if trace:
            s1 = _stage_totals()
            stages = {s: (s1[s][0] - stages0[s][0], s1[s][1] - stages0[s][1])
                      for s in STAGES}
        # the requests compared: a sample drawn from the seed, with the
        # longest among them
        rng = load.rng_for(self.seed, 1)
        done = [i for i in range(n) if replies[i] is not None]
        longest = max((len(reqs[i]) for i in done), default=0)
        top = [i for i in done if len(reqs[i]) == longest][:16]
        rest = [i for i in done if len(reqs[i]) != longest]
        pick = top + list(rng.choice(rest, min(len(rest),
                                               int(tr["check_requests"])),
                                     replace=False))
        check = None
        if pick:
            check = (np.concatenate([sched.rows[i] for i in pick]),
                     np.concatenate([np.asarray(replies[i].values)
                                     for i in pick]).astype(np.float32),
                     np.concatenate([np.asarray(replies[i].ids)
                                     for i in pick]).astype(np.int64))
        last = max(stamps.done_t.values(), default=t0)
        e2e = {}
        if lat.size:
            e2e = {"p50_ms": float(np.percentile(lat, 50)),
                   "p99_ms": float(np.percentile(lat, 99))}
        if errors:
            log(phase="errors", count=len(errors),
                first=sorted(errors.items())[:3])
        return SimpleNamespace(
            w=w, attempted=n, failed=len(errors), missing=len(errors),
            e2e=e2e, calls=[v for v, _ in stamps.dispatched],
            dispatched=list(stamps.dispatched), stages=stages,
            check=check, compiles_in_window=compiles,
            extra={"backlog_mid": backlog_mid, "backlog_at_close": backlog,
                   **load.lateness_ms(late),
                   "batches": len(stamps.dispatched),
                   "served_per_s": len(done) / max(last - t0, 1e-9)})

    # -- after the window ---------------------------------------------

    def compare(self, win) -> dict:
        """The compared numbers; with no answer to compare, only
        `missing` (the others then fail as absent)."""
        numbers = {"missing": win.missing}
        if win.check is None:
            return numbers
        rows, vals, ids = win.check
        t = time.perf_counter()
        numbers.update(reference.compare(
            self.dataset, self.pool[rows], vals, ids, int(self.traffic["k"])))
        log(phase="reference", rows=len(rows),
            reference_s=time.perf_counter() - t, **self.meter.take())
        return numbers

    def free_program(self) -> None:
        self.state = None
        gc.collect()

    def layer_metrics(self, win, summary) -> dict:
        """Each listed per-layer metric's reader, by name; a reader that
        finds nothing to read returns None and its metric is left out."""
        peak = None
        if summary is not None:
            peak = spec.peaks(self.root, jax.devices()[0].device_kind)
        run = SimpleNamespace(
            cfg=self.cfg, traffic=self.traffic, cell=self.cell,
            calls=win.calls, dispatched=win.dispatched, stages=win.stages,
            trace=summary, peak=peak, counts=self.counts)
        out = {}
        for m in spec.metrics(self.bench, "per_layer", self.name):
            v = spec.module(self.root, "layers", m["name"]).read(run)
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out

    def end_to_end(self, win) -> dict:
        values = dict(win.e2e, setup_s=win.setup_s,
                      build_rows_per_s=self.cfg["rows"] / self.build_s)
        return {m["name"]: {"value": float(values[m["name"]]),
                            "unit": m["unit"]}
                for m in spec.metrics(self.bench, "end_to_end", self.name)
                if m["name"] in values}


def _finite(v):
    return v if v is None or math.isfinite(v) else None


def run_cell(root: str, name: str, seed: int, seconds: float,
             trace: bool = False, t_start: float = None,
             variant: str = "program") -> dict:
    """One whole run; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, name, seed, t_start)
    cell.setup()
    win = cell.window(seconds, trace, variant)
    summary = win.w.reduce()
    device = jax.devices()[0]
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak_bytes()}
    if summary is not None:
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    cell.free_program()
    numbers = cell.compare(win)
    checks = reference.judge(numbers, cell.cfg["limits"])
    for cname, c in checks.items():
        print(f"check {cname} {c['value']} {c['rule']} {c['limit']} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    metrics = (cell.layer_metrics(win, summary) if trace
               else cell.end_to_end(win))
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": dev}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = {n: {"value": _finite(c["value"]), "limit": c["limit"],
                         "rule": c["rule"]} for n, c in checks.items()}
    return out
