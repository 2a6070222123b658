"""Knee sweep for one open-loop cell, outside the per-run path: one
process sets the cell up once, then offers its traffic at each rate in
turn and prints one line per rate.

    python3 -m benchmark.sweep --workload deep1m-ivfpq.serve --seed 5 \\
        --seconds 10 --rates 500,1000,2000

The knee is the highest offered rate whose served rate keeps up and
whose backlog does not grow across the window; the cell's traffic file
then fixes its rate, as a number, at 0.8 x the knee.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    run.configure_jax()
    run.preflight(1)
    from benchmark import harness

    cell = harness.Cell(run.ROOT, args.workload, args.seed, run.T_START)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep: the cell's traffic is not open-loop")
    cell.setup()
    base = dict(cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(base, rate_per_s=rate)
        win = cell.window(args.seconds)
        print(json.dumps({"sweep": args.workload, "offered_per_s": rate,
                          **win.e2e, **win.extra, "failed": win.failed}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
