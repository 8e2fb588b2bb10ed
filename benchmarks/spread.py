"""Repeated runs of one cell and the spread of each metric, for its bounds.

    python benchmarks/spread.py --workload gdelt.heatmap_pow2 \\
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 30 \\
        --trace-seeds 17,18,19 --extra-seeds 20,21,22 --out runs/heatmap_pow2

Each run is a new process (``benchmarks/run.py``), as every run of the
benchmark is. The sets run the same seeds in the same order. Per set and metric
it prints the median and the spread: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; ``setup_s`` of a set's first run, which may compile, is shown
apart. Then the traced and extra seeds, whose ``correct`` counts toward
the dozen seeds a cell is proven on. Every run's output is kept under
``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def one(workload, seed, seconds, trace, out_dir):
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(int(trace))], capture_output=True, text=True,
        cwd=os.path.dirname(HERE))
    wall = time.monotonic() - t0
    tag = f"{workload}.{seed}.t{int(trace)}.{int(time.time())}"
    with open(os.path.join(out_dir, tag + ".out"), "w") as f:
        f.write(p.stdout)
    with open(os.path.join(out_dir, tag + ".err"), "w") as f:
        f.write(p.stderr)
    lines = p.stdout.strip().splitlines()
    res = None
    if p.returncode == 0 and lines and lines[-1].startswith("{"):
        res = json.loads(lines[-1])
    info = [ln for ln in lines if ln.startswith("#")]
    print(json.dumps({"seed": seed, "trace": trace, "rc": p.returncode,
                      "wall_s": round(wall, 2),
                      "correct": res and res["correct"],
                      "metrics": res and {k: v["value"] for k, v in
                                          res["metrics"].items()},
                      "checks": res and {k: v["value"] for k, v in
                                         res["checks"].items()},
                      "device": res and res["device"],
                      "info": info}), flush=True)
    if res is None:
        print(p.stderr[-3000:], file=sys.stderr, flush=True)
    return res


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--extra-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    summary = {}
    for k in range(args.sets if seeds else 0):
        runs = [one(args.workload, s, args.seconds, False, args.out)
                for s in seeds]
        ok = [r for r in runs if r]
        names = sorted({m for r in ok for m in r["metrics"]})
        for m in names:
            vals = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if m == "setup_s":
                summary[f"set{k}.setup_s.first"] = vals[0] if vals else None
                vals = vals[1:]
            summary[f"set{k}.{m}"] = {
                "median": statistics.median(vals) if vals else None,
                "spread": spread(vals), "n": len(vals), "values": vals}
        print(json.dumps({"set": k, "summary": {
            m: v for m, v in summary.items() if m.startswith(f"set{k}.")}}),
            flush=True)
    for s in (int(x) for x in args.trace_seeds.split(",") if x):
        one(args.workload, s, args.seconds, True, args.out)
    for s in (int(x) for x in args.extra_seeds.split(",") if x):
        one(args.workload, s, args.seconds, False, args.out)
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
