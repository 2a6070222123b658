"""Readings that the limits in a configuration's `limits` are set from:
the compared numbers of the program over many seeds and of its control
(the program's own lower-precision path: `variant="control"` in
`indexes/<kind>.py`) over a few, at the cell's own size and load, in
one process. Not part of a benchmark run.

    python3 -m benchmark.control --workload deep1m-ivfpq.batch \\
        --seeds 1,2,3 --control-seeds 1,2 --seconds 3
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

from benchmark import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    run.configure_jax()
    run.preflight(1)
    from benchmark import harness

    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(run.ROOT, args.workload, seed, run.T_START)
        cell.setup()
        for variant in ("program", "control") if seed in controls \
                else ("program",):
            win = cell.window(args.seconds, variant=variant)
            print(json.dumps({"readings": args.workload, "seed": seed,
                              "variant": variant, **cell.compare(win),
                              **win.e2e}), flush=True)
        del cell, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
