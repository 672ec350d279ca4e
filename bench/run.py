#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, placement, one warm fit) is timed as
``setup_s``; then fits run back to back for ``--seconds``, and
``fit_s`` is the window over the fits completed.  With ``--trace 1`` the
window runs under the profiler and the line carries the cell's
per-layer metrics instead.  The last line of standard output is one
JSON object; the numbers compared with the reference, each beside its
limit, are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the window's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    # the script's own directory would shadow the standard library
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != ROOT / "bench"]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        result, lines = harness.execute(
            args.workload, args.seed, args.seconds, bool(args.trace),
            keep_trace=args.keep_trace, t_start=T_START)
    except harness.BenchError as exc:
        print(f"bench: {exc}", file=sys.stderr, flush=True)
        return 3
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
