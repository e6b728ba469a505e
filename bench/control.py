"""Readings of the float8 control for a cell's correctness limit, many
seeds in one process.

    python3 -m bench.control --workload <name> --seeds 1,2,3 --seconds 12

Each seed is one :func:`bench.run.run_cell` with ``control=True``: the
cell's set-up, load and window as a run has them, and the same sample of
finished requests through the same ``correct`` decision, with the tokens
that the reference at float8 puts first in the served tokens' place.  Each
line should read ``"correct": false``; the smallest ``logit_gap_max`` is
the upper reading that the configuration's limit lies below.  Needs a TPU,
as ``bench.run``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench.run import prepare, run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    cell, devices = prepare(args.workload)
    if cell is None:
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(cell, seed, args.seconds, False, devices,
                       t_start=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "compared": res["compared"], "checked": res["checked"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
