"""Fig. 13-17 / Tables 5-7: the PIM vs host-CPU vs GPU comparison,
driven end-to-end through the single ``System`` API (DESIGN.md §10.5).

For each of the paper's four workloads, the SAME ``Workload`` object
fits on all three execution targets:

  pim        the paper's best PIM version (INT32/BUI ladder for GD,
             int16 Lloyd's), wall-clock measured on the semantic model
             and DPU seconds from the hierarchical cost model
             (``HierarchicalCostModel`` — Fig. 8-12 calibration, with
             rank-serialized broadcast/gather legs, DESIGN.md §12);
  host       the processor-centric fp32 baseline, wall-clock measured
             in this container (replacing the deleted ad-hoc
             ``train_cpu_baseline`` loops), DRAM traffic counted;
  gpu-model  HostSystem numerics priced on the calibrated A100
             roofline (``launch/roofline.GpuRoofline``) — replacing the
             previously echoed paper constants with a model fed by the
             measured FLOPs/bytes of the compiled programs.

The paper's reported speedups ride along as reference columns so the
reproduction stays auditable.  Output: an aligned table on stdout and a
JSON record (default ``benchmarks/out/compare.json``).

  PYTHONPATH=src python -m repro.launch.compare --tiny
  make compare
"""
from __future__ import annotations

import argparse
import time

from repro.api import HierarchicalCostModel, get_workload, make_system
from repro.obs import Column, render_table, write_json
from repro.data.synthetic import (make_blobs, make_classification,
                                  make_linear_dataset, make_recsys)

SYSTEMS = ("pim", "host", "gpu-model")

#: the paper's reported cross-target ratios (reference columns only —
#: the gpu-model rows are computed, not echoed)
PAPER_REFERENCE = {
    "linreg": {"gpu_over_pim": 4.1},       # §5.4.1, GPU vs LIN-BUI
    "logreg": {"pim_over_cpu": 3.9},       # LOG-BUI-LUT vs CPU
    "dtree": {"pim_over_cpu": 27.0, "pim_over_gpu": 1.34},
    "kmeans": {"pim_over_cpu": 2.8, "pim_over_gpu": 3.2},
}

#: per-target workload versions: PIM runs the paper's quantized
#: versions, the processor-centric targets run fp32 (no quantization
#: round-trip, exact transcendentals)
PLAN = [
    {"workload": "linreg", "versions": {"pim": "int32", "host": "fp32",
                                        "gpu-model": "fp32"},
     "cost": ("lin", "int32")},
    {"workload": "logreg", "versions": {"pim": "int32_lut_wram",
                                        "host": "fp32",
                                        "gpu-model": "fp32"},
     "cost": ("log", "int32_lut_wram")},
    {"workload": "dtree", "versions": {k: "fp32" for k in SYSTEMS},
     "cost": ("dtr", "fp32")},
    {"workload": "kmeans", "versions": {"pim": "int16", "host": "fp32",
                                        "gpu-model": "fp32"},
     "cost": ("kme", "int16")},
    # the EMB extension (DESIGN.md §15): PIM runs the Q(frac_bits)
    # fixed-point tables with a deferred-update window, the
    # processor-centric targets the eager fp32 baseline
    {"workload": "emb", "versions": {"pim": "int32", "host": "fp32",
                                     "gpu-model": "fp32"},
     "cost": ("emb", "int32")},
]


def _make_data(workload: str, n: int, f: int, seed: int = 0):
    if workload == "kmeans":
        X, _, _ = make_blobs(n, f, centers=8, seed=seed)
        return X, None
    if workload == "dtree":
        return make_classification(n, f, seed=seed, class_sep=1.4)
    if workload == "emb":
        # f rides as the embedding dim elsewhere; the pair width is 2
        return make_recsys(n, n_users=max(64, n // 16),
                           n_items=max(48, n // 24), dim=f, seed=seed)
    X, y, _ = make_linear_dataset(n, f, seed=seed)
    return X, y


def _shapes(tiny: bool) -> dict:
    if tiny:
        return {"linreg": (1024, 8, {"n_iters": 30}),
                "logreg": (1024, 8, {"n_iters": 30}),
                "dtree": (2048, 8, {"max_depth": 4}),
                "kmeans": (1024, 8, {"n_clusters": 4, "max_iter": 15}),
                "emb": (1024, 4, {"n_iters": 30, "batch": 32, "dim": 4,
                                  "lr": 1.0, "frac_bits": 12,
                                  "flush_every": 4})}
    return {"linreg": (8192, 16, {"n_iters": 300}),
            "logreg": (8192, 16, {"n_iters": 300}),
            "dtree": (60_000, 16, {"max_depth": 10}),
            "kmeans": (20_000, 16, {"n_clusters": 16, "max_iter": 100}),
            "emb": (16_384, 8, {"n_iters": 300, "batch": 256, "dim": 8,
                                "lr": 1.0, "frac_bits": 12,
                                "flush_every": 8})}


def _iterations(workload: str, result, params: dict) -> int:
    """Training passes the fit performed (sizes the PIM cost model)."""
    if workload == "kmeans":
        return int(result.attributes["n_iter_"])
    if workload == "dtree":
        # one split-evaluate + one commit pass per grown node pair
        return 2 * int(result.attributes["n_nodes_"])
    return int(params["n_iters"])


def run_compare(tiny: bool = False, cores: int = 16,
                seed: int = 0) -> dict:
    """Fit all four workloads on all three systems; return the record."""
    rows = []
    for plan in PLAN:
        name = plan["workload"]
        wl = get_workload(name)
        n, f, params = _shapes(tiny)[name]
        X, y = _make_data(name, n, f, seed)
        per_system: dict = {}
        for kind in SYSTEMS:
            system = make_system(kind, n_cores=cores)
            ds = system.put(X, y)
            spec = wl.spec(plan["versions"][kind], **params)
            wl.fit(ds, spec)           # warm: compile + materialize views
            snap = system.stats.snapshot()
            gpu_snap = system.gpu.snapshot() if kind == "gpu-model" else None
            t0 = time.perf_counter()
            result = wl.fit(ds, spec)  # measured: the session steady state
            wall_s = time.perf_counter() - t0
            score = (wl.score(result, X) if wl.unsupervised
                     else wl.score(result, X, y))
            s = system.stats.delta(snap)
            row = {
                "workload": name,
                "system": kind,
                "version": spec.version,
                "samples": n,
                "features": f,
                "wall_s": wall_s,
                "score": score,
                "kernel_launches": s.kernel_launches,
                "dram_bytes": s.dram_bytes,
                "cpu_to_pim_bytes": s.cpu_to_pim,
                "pim_to_cpu_bytes": s.pim_to_cpu,
            }
            iters = _iterations(name, result, params)
            row["iterations"] = iters
            if kind == "pim":
                cost_wl, cost_ver = plan["cost"]
                model = HierarchicalCostModel(system.topology)
                # the model's free k knob: cluster count (KME) or
                # minibatch size (EMB); inert for the GD workloads
                kern = params.get("n_clusters", params.get("batch", 16))
                kernel_s = iters * model.workload_seconds(
                    cost_wl, cost_ver, n, f, cores,
                    system.config.n_threads, k=kern)
                row["modeled_s"] = iters * model.step_seconds(
                    cost_wl, cost_ver, n, f, n_cores=cores,
                    n_threads=system.config.n_threads, k=kern)
                # the topology split: per-DPU kernel vs the rank-
                # serialized host-link legs (DESIGN.md §12)
                row["modeled_kernel_s"] = kernel_s
                row["modeled_transfer_s"] = row["modeled_s"] - kernel_s
            elif kind == "gpu-model":
                gpu = system.gpu.delta(gpu_snap)
                row["modeled_s"] = gpu.modeled_seconds
                row["modeled_energy_j"] = gpu.modeled_energy_j
                row["modeled_flops"] = gpu.flops
            else:
                row["modeled_s"] = wall_s    # host: measured IS the model
            # drift accounting (DESIGN.md §13.5): this container's wall
            # time over the target's model — trivially 1.0 on host,
            # where the measurement IS the model
            row["drift_ratio"] = (wall_s / row["modeled_s"]
                                  if row["modeled_s"] > 0 else None)
            per_system[kind] = row
            rows.append(row)
        # cross-target ratios (the paper's headline numbers)
        pim_s = per_system["pim"]["modeled_s"]
        host_s = per_system["host"]["modeled_s"]
        gpu_s = per_system["gpu-model"]["modeled_s"]
        ratios = {
            "pim_over_host": host_s / max(pim_s, 1e-12),
            "pim_over_gpu_model": gpu_s / max(pim_s, 1e-12),
            "paper_reference": PAPER_REFERENCE.get(name, {}),
        }
        for row in per_system.values():
            row["ratios"] = ratios
    return {"meta": {"tiny": tiny, "cores": cores, "seed": seed,
                     "systems": list(SYSTEMS)},
            "rows": rows}


#: the comparison table columns (repro.obs.format — shared formatter)
COMPARE_COLUMNS = (
    Column("workload", width=9, align="<"),
    Column("system", width=10, align="<"),
    Column("version", width=15, align="<"),
    Column("wall_s", "wall s", width=9, spec=".3f"),
    Column("modeled_s", "model s", width=10, spec=".3e"),
    Column("drift_ratio", "drift", width=9, spec=".3g"),
    Column("score", width=11, spec=".4f"),
    Column("kernel_launches", "launches", width=9, spec="d"),
)


def _ratio_note(row: dict) -> str:
    r = row.get("ratios", {})
    if row["system"] == "host":
        return f"pim {r.get('pim_over_host', 0.0):.2f}x faster"
    if row["system"] == "gpu-model":
        return (f"pim {r.get('pim_over_gpu_model', 0.0):.2f}x; "
                f"paper {r.get('paper_reference', {})}")
    return ""


def render_compare_table(record: dict) -> str:
    return render_table(record["rows"], COMPARE_COLUMNS,
                        extra=_ratio_note, rule=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes (seconds, CI-friendly)")
    ap.add_argument("--cores", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="benchmarks/out/compare.json",
                    help="JSON record path ('' disables)")
    args = ap.parse_args(argv)

    record = run_compare(tiny=args.tiny, cores=args.cores, seed=args.seed)
    print(render_compare_table(record))
    if args.out:
        # run-metadata envelope (DESIGN.md §13.7): git sha, timestamp,
        # jax version — the record stays attributable across PRs
        record = write_json(args.out, record)
        print(f"\nrecorded -> {args.out}")
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
