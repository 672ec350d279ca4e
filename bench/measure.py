#!/usr/bin/env python3
"""Repeated runs of cells, each a fresh process, and their spreads.

    python3 bench/measure.py --cells A B --seeds 1 2 3 4 5 6 --sets 2 \
        --seconds 10 [--trace-seeds 7 8 9] [--out F]

Runs ``bench/run.py`` once per (set, seed) for each cell, in that order,
then once per trace seed with ``--trace 1``, appends every result line
to ``--out`` and prints, for each cell and metric, each set's median
and spread: the distance between the first and third quartiles of
``statistics.quantiles(values, n=4)`` as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def run_once(cell, seed, seconds, trace, timeout=1200):
    cmd = [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
           cell, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          timeout=timeout)
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    result = None
    if proc.returncode == 0 and lines:
        result = json.loads(lines[-1])
    tail = "\n".join(proc.stderr.strip().splitlines()[-8:])
    return {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": wall, "result": result, "stderr_tail": tail}


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return statistics.median(values), (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    out = open(args.out, "a") if args.out else None
    for cell in args.cells:
        sets = []
        for s in range(args.sets):
            rows = []
            for seed in args.seeds:
                row = run_once(cell, seed, args.seconds, 0)
                row["set"] = s
                rows.append(row)
                print(json.dumps(row), flush=True)
                if out:
                    out.write(json.dumps(row) + "\n")
                    out.flush()
            sets.append(rows)
        for seed in args.trace_seeds:
            row = run_once(cell, seed, args.seconds, 1)
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
        for i, rows in enumerate(sets):
            good = [r["result"] for r in rows if r["result"]]
            for name in (good[0]["metrics"] if good else {}):
                vals = [g["metrics"][name]["value"] for g in good]
                if len(vals) >= 2:
                    med, sp = spread(vals)
                    print(f"SPREAD {cell} set{i} {name} median={med!r} "
                          f"spread={sp!r} n={len(vals)} "
                          f"correct={sum(g['correct'] for g in good)}",
                          flush=True)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
