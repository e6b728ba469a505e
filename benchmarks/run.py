"""Benchmark driver: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7]

Prints ``name,us_per_call,derived`` CSV.  The roofline table (§Roofline)
needs 512 placeholder devices, so it runs as a subprocess
(``python -m benchmarks.roofline``) and is included via --roofline.
"""

from __future__ import annotations

import argparse
import subprocess
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="substring filter of suite names")
    ap.add_argument("--roofline", action="store_true",
                    help="also run the (slow) roofline sweep subprocess")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    from . import (
        dispatch_bench,
        fig2a_overhead_ratio,
        fig2b_sched_minimized,
        fig7_inference,
        fig8_training,
        table1_multistream,
    )

    use_compile_cache()
    suites = {
        "fig2a": fig2a_overhead_ratio.run,
        "fig2b": fig2b_sched_minimized.run,
        "fig7": fig7_inference.run,
        "table1": table1_multistream.run,
        "fig8": fig8_training.run,
        "dispatch": dispatch_bench.run,
    }
    print("name,us_per_call,derived")
    for name, suite in suites.items():
        if args.only and args.only not in name:
            continue
        for row in suite():
            print(",".join(str(x) for x in row))
            sys.stdout.flush()

    if args.roofline:
        subprocess.run(
            [sys.executable, "-m", "benchmarks.roofline"], check=True
        )


if __name__ == "__main__":
    main()
