"""Readings for the limits of the correctness check: the compared numbers
of a cell on several seeds, for the program as it is (``--swap none``), for
the control (``--swap control``: the reference at one precision below the
configuration's in the kernel layer's place) or for a planted fault (see
``faults.py``).

    python3 bench/control.py --workload taxi.solve --swap control step_bf16 \\
        --seeds 11 12 13 --seconds 1

Runs every swap on every seed in one process, on the chip, at the cell's
own sizes, and prints one JSON line per run: the numbers compared and their
limits.  The benchmark's own runs never do this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import run


def readings(spec: dict, swap: str, seeds, seconds: float):
    """Yield (seed, result line) for each seed under ``swap``."""
    import faults

    for seed in seeds:
        ctx = (contextlib.nullcontext() if swap == "none"
               else faults.SWAPS[swap](spec["config"]))
        with ctx:
            yield seed, run.run_cell(spec, seed, seconds, False)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--swap", nargs="+", default=["control"],
                   help="'none' or names in faults.SWAPS, each run on every seed")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--out", default=None, help="also append the lines to this file")
    args = p.parse_args(argv)
    run.start_process()
    spec = run.load_cell(args.workload)
    for swap in args.swap:
        for seed, res in readings(spec, swap, args.seeds, args.seconds):
            line = json.dumps({"workload": args.workload, "swap": swap, "seed": seed,
                               "correct": res["correct"], "attempted": res["attempted"],
                               "failed": res["failed"], "checks": res["checks"],
                               "metrics": res["metrics"]})
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
