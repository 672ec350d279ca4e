#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --config <config> --seeds 1 2 3 ... [--out F]

For each seed, at the configuration's own size, on the chip this
process finds: the program's fit of each cell of the configuration
(the sound runs: the lower readings), the controls (the upper
reading), and the faults planted in the reference put in the program's
place, each compared with the plain reference by the configuration's
own comparison.  Prints one JSON line per seed and appends them to
``--out``.  ``--limits-from F`` prints the limits that the lines of F
give (:func:`limits`).  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def _program_fit(cell, ds, params, version=None):
    from repro.api import make_estimator
    est = make_estimator(cell.config["workload"],
                         version=version or cell.config["version"],
                         system=ds.system, **params)
    est.fit(ds)
    return {k: np.asarray(getattr(est, k)) for k in cell.config["outputs"]}


def control_log(cell, data, ds, seed, ref_mod, ref):
    """LOG: the program's own lower-precision path, ``hyb_lut`` (8-bit
    inputs, 16-bit weights, 16-bit dot products) in place of int32, and
    the reference with its products in one bfloat16 pass."""
    params = cell.fit_params(seed)
    params["fuse_steps"] = params[cell.config["iters_param"]]
    low = ref_mod.fit(**data, **cell.reference_params(seed),
                      dtype="bfloat16")
    return {"hyb_lut": ref_mod.compare(
                _program_fit(cell, ds, params, "hyb_lut"), ref, **data),
            "bfloat16": ref_mod.compare(low, ref, **data)}


def control_kme(cell, data, ds, seed, ref_mod, ref):
    """KME: the reference on int8 data (the step below int16)."""
    low = ref_mod.fit(**data, **cell.reference_params(seed), quant=127)
    return {"int8": ref_mod.compare(low, ref, **data)}


def witness_kme(cell, data, ds, seed, ref_mod, ref):
    """KME: the program's own float32 path (``fp32``: float data,
    distances and cluster sums, no quantization), a second witness
    beside the reference where the int16 path departs from it."""
    return {"program_fp32": ref_mod.compare(
        _program_fit(cell, ds, cell.fit_params(seed), "fp32"), ref, **data)}


CONTROLS = {"logreg": control_log, "kmeans": control_kme}
WITNESSES = {"kmeans": witness_kme}


def alter(outputs: dict, first: str) -> dict:
    """An answer altered where it is produced: the first entry (or row)
    of the first output takes the value of the second."""
    out = {k: np.array(v, copy=True) for k, v in outputs.items()}
    a = out[first].reshape(len(out[first]), -1)
    a[0] = a[1]
    return out


def faults(cell, data, seed, ref_mod, ref, n_cores=64):
    """Faults planted in the reference put in the program's place: the
    rest of the batch left out with the mean over what is left (half of
    it; all but one core's shard, as if the exchange between cores were
    left out), and an answer altered.  A state left unchanged reads 1
    by construction for LOG and is read by the tests."""
    n = cell.n
    out = {}
    for name, rows in (("half_batch", n // 2),
                       ("one_core", -(-n // n_cores))):
        got = ref_mod.fit(**data, **cell.reference_params(seed), rows=rows)
        out[name] = ref_mod.compare(got, ref, **data)
    out["answer_altered"] = ref_mod.compare(
        alter(ref, cell.config["outputs"][0]), ref, **data)
    return out


def calibrate(config: str, seeds, shape=None, log=print):
    from bench import harness
    from repro.systems import make_system
    cells = [c for c in (p.stem for p in (ROOT / "bench" / "cells").glob(
        "*.json")) if harness.load_json(
            ROOT / "bench" / "cells" / f"{c}.json")["config"] == config]
    cells = [harness.resolve_cell(c, shape=shape) for c in sorted(cells)]
    base = cells[0]
    ref_mod = harness.load_plugin("ref", base.config["reference"])
    gen = harness.load_plugin("data", base.config["data"])
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        data = gen.generate(seed, base.n, base.n_features,
                            **base.config.get("data_params", {}))
        ds = make_system("pim").put(data["X"], data.get("y"))
        programs = {c.name: _program_fit(c, ds, c.fit_params(seed))
                    for c in cells}
        ref = ref_mod.fit(**data, **base.reference_params(seed))
        row = {"seed": seed,
               "program": {k: ref_mod.compare(v, ref, **data)
                           for k, v in programs.items()},
               "control": CONTROLS[base.config["workload"]](
                   base, data, ds, seed, ref_mod, ref)}
        if base.config["workload"] in WITNESSES:
            row["witness"] = WITNESSES[base.config["workload"]](
                base, data, ds, seed, ref_mod, ref)
        del ds, programs
        gc.collect()
        row["faults"] = faults(base, data, seed, ref_mod, ref)
        row["seconds"] = time.perf_counter() - t0
        log(json.dumps(row))
        rows.append(row)
        del data
        gc.collect()
    return rows


def limits(rows: list, control_ratio=3.0, fault_ratio=10.0) -> dict:
    """Each number's readings and its limit.  Lower: the largest reading
    of the program over all seeds and cells.  Upper: the smallest
    reading of a control that reads ``control_ratio`` times the lower or
    more, or of a fault that reads ``fault_ratio`` times it or more.
    The limit lies between them, nearer the upper (0.65 of the way on a
    log scale, or a quarter of the upper where the lower is 0)."""
    out = {}
    for num in rows[0]["control"][next(iter(rows[0]["control"]))]:
        low = max(v[num] for r in rows for v in r["program"].values())
        mins = {}
        for kind in ("control", "faults"):
            for r in rows:
                for name, vals in r[kind].items():
                    key = f"{kind}.{name}"
                    mins[key] = min(mins.get(key, np.inf), vals[num])
        cands = [v for k, v in mins.items()
                 if v > 0 and v >= (control_ratio if k.startswith("control")
                                    else fault_ratio) * low]
        up = min(cands) if cands else None
        lim = None
        if up is not None:
            lim = up / 4 if low == 0 else low ** 0.35 * up ** 0.65
        out[num] = {"lower": low, "upper": up, "limit": lim, "minima": mins}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default=None)
    ap.add_argument("--limits-from", default=None,
                    help="print the limits that the readings in this "
                    "file give, and run nothing")
    ap.add_argument("--write", action="store_true",
                    help="with --limits-from: write them into the "
                    "configuration's file")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != ROOT / "bench"]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if args.limits_from:
        with open(args.limits_from) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        found = limits(rows)
        print(json.dumps(found, indent=1))
        if args.write:
            path = ROOT / "bench" / "configs" / f"{args.config}.json"
            conf = json.loads(path.read_text())
            conf["limits"] = {k: v["limit"] for k, v in found.items()}
            path.write_text(json.dumps(conf, indent=2) + "\n")
        return 0
    import jax
    from bench import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    rows = calibrate(args.config, args.seeds, log=lambda s: print(s, flush=True))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as fh:
            for row in rows:
                fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
