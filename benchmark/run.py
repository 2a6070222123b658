"""Run one benchmark cell on the chips of this machine.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before any work; it never falls back. The last line of
standard output is the result's JSON object; the numbers compared with
the reference are also the last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def configure_jax() -> None:
    """The benchmark's compile cache, for every program of the process
    (before JAX is imported, so program code that reads the variable
    finds the same directory)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def preflight(chips: int):
    """The chips, or SystemExit: a TPU, as many chips as the cell asks
    for, and a row for their kind in the peak table."""
    import jax

    from benchmark import spec

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (JAX platform is "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: {len(devs)} chips, the cell needs "
                         f"{chips}")
    spec.peaks(ROOT, devs[0].device_kind)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import spec

    cell = spec.workload(spec.load_bench(ROOT), args.workload)
    configure_jax()
    preflight(int(cell["chips"]))
    from benchmark import harness

    out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                           trace=bool(args.trace), t_start=T_START)
    if args.trace and "busy_s" not in out["device"]:
        raise SystemExit("benchmark: the trace holds no device operation "
                         "in the window")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
