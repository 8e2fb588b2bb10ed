"""Readings of the control at a cell's own size, for setting its limits.

    python benchmarks/control.py --workload gdelt.heatmap_pow2 \\
        --seeds 11,12,13 --seconds 5

The control is the plain reference computed in bfloat16, the precision
below the f32 the configurations state, put in the program's place: each
run serves a window at the cell's own load, then every answer is replaced
by the bf16 reference's answer to the same request before the comparison.
Each seed prints the program's run as the benchmark prints it (its result
line, whose ``correct`` must read true) and, on a ``# control`` line, the
control's numbers against the same limits, whose ``correct`` must read
false. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        harness.run(args.workload, seed, args.seconds, False,
                    time.monotonic(), control=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
