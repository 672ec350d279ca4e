"""Multi-pod dry-run (deliverable (e)).

Lowers + compiles every (architecture x input-shape) cell on the
single-pod (16 data x 16 model = 256) and multi-pod (2 pod x 16 x 16 =
512) meshes, printing memory_analysis() and cost_analysis() and appending
structured results to experiments/dryrun_results.json (resumable — done
cells are skipped on re-run).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod-only
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, shape_for, supports
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        opt_state_shardings,
                                        param_shardings,
                                        param_shardings_fsdp)
from repro.launch.analytic import model_flops
from repro.launch.hlo_analysis import (corrected_totals,
                                       normalize_cost_analysis)
from repro.launch.mesh import describe, make_production_mesh
from repro.models.api import Model, input_specs
from repro.optim.adam import AdamW
from repro.train.loop import make_train_step

RESULTS_PATH = "experiments/dryrun_results.json"


def _result_key(arch, shape, multi_pod):
    return f"{arch}|{shape}|{'2pod' if multi_pod else '1pod'}"


def load_results(path=RESULTS_PATH) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return {}


def save_results(results: dict, path=RESULTS_PATH):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


# ---------------------------------------------------------------------------
# Collective-byte extraction from HLO text (for §Roofline).
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "u64": 8}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(\([^)]*\)|[a-z0-9\[\],\s]+?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u32|s16|u16|s8|u8|pred|"
                       r"f8e4m3fn|f8e5m2|u64)\[([0-9,]*)\]")


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Sum output-shape bytes of every collective op, by kind.

    Output bytes are the per-device payload GSPMD materializes; for
    all-reduce in/out sizes match, for all-gather the output is the
    gathered buffer (upper bound on wire bytes per device).
    """
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo):
        shapes_txt, kind = m.group(2), m.group(3)
        nbytes = 0
        for sm in _SHAPE_RE.finditer(shapes_txt):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0) + nbytes
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


# ---------------------------------------------------------------------------
# Cell lowering.
# ---------------------------------------------------------------------------

def build_step(arch: str, shape_name: str, mesh, cfg_overrides=None):
    """Returns (jitted_fn, example_args_as_ShapeDtypeStructs)."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    model = Model(cfg)
    shape = shape_for(cfg, shape_name)
    specs = input_specs(cfg, shape)
    pshapes = model.param_shapes()
    pshard = (param_shardings_fsdp(mesh, pshapes) if cfg.fsdp
              else param_shardings(mesh, pshapes,
                                   tp_dense=cfg.tp_dense))

    if shape.kind == "train":
        opt = AdamW(lr=3e-4)
        ostate_shapes = opt.init_shapes(pshapes)
        oshard = _opt_shardings(mesh, ostate_shapes,
                                opt_state_shardings(mesh, pshapes))
        step = make_train_step(model, opt,
                               microbatches=shape.microbatches)
        bshard = batch_shardings(mesh, specs)
        fn = jax.jit(step,
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
        return fn, (pshapes, ostate_shapes, specs)

    if shape.kind == "prefill":
        bshard = batch_shardings(mesh, {k: v for k, v in specs.items()})

        def prefill_fn(params, batch):
            return model.prefill(params, batch, max_seq=shape.seq_len)

        fn = jax.jit(prefill_fn, in_shardings=(pshard, bshard))
        return fn, (pshapes, specs)

    # decode: serve_step(params, tokens, cache) -> (logits, cache)
    cache_shapes = specs["cache"]
    cshard = cache_shardings(mesh, cache_shapes)
    tok_shard = batch_shardings(mesh, {"tokens": specs["tokens"]})["tokens"]

    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    fn = jax.jit(serve_step,
                 in_shardings=(pshard, tok_shard, cshard),
                 out_shardings=(None, cshard),
                 donate_argnums=(2,))
    return fn, (pshapes, specs["tokens"], cache_shapes)


def _opt_shardings(mesh, ostate_shapes, pshard):
    """Adam m/v inherit param shardings; step is replicated."""
    from repro.optim.adam import AdamState
    rep = NamedSharding(mesh, P())
    return AdamState(step=rep, m=pshard, v=pshard)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             results: dict, verbose: bool = True,
             mesh_shape: tuple = ()) -> dict:
    """mesh_shape: optional (data, model) override for §Perf mesh
    experiments (e.g. --mesh-shape 64,4); production meshes otherwise."""
    key = _result_key(arch, shape_name, multi_pod)
    if mesh_shape:
        key += f"|mesh{mesh_shape[0]}x{mesh_shape[1]}"
    cfg = get_config(arch)
    ok, reason = supports(cfg, shape_name)
    if not ok:
        entry = {"status": "skipped", "reason": reason}
        results[key] = entry
        save_results(results)
        return entry

    mesh = (jax.make_mesh(mesh_shape, ("data", "model")) if mesh_shape
            else make_production_mesh(multi_pod=multi_pod))
    t0 = time.perf_counter()
    try:
        from repro.distributed import act_sharding
        fn, args = build_step(arch, shape_name, mesh)
        with mesh, act_sharding.use_mesh(mesh):
            lowered = fn.lower(*args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = normalize_cost_analysis(compiled.cost_analysis())
            hlo = compiled.as_text()
        coll = collective_bytes_from_hlo(hlo)
        corrected = corrected_totals(hlo)
        analytic = model_flops(cfg, shape_for(cfg, shape_name))
        entry = {
            "status": "ok",
            "mesh": describe(mesh),
            "n_devices": int(np.prod(list(mesh.shape.values()))),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes",
                                          0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
            "collectives": coll,
            "corrected": corrected,
            "analytic": analytic,
            "hlo_ops": len(hlo.splitlines()),
        }
        if verbose:
            print(f"[OK] {key}: compile={t_compile:.0f}s "
                  f"flops={corrected['flops']:.3e} "
                  f"(model {analytic['model_flops']:.3e}) "
                  f"coll={corrected['collective_bytes']:.3e}B "
                  f"args={entry['argument_bytes']/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — failures are data here
        entry = {"status": "error", "error": f"{type(e).__name__}: {e}",
                 "traceback": traceback.format_exc()[-2000:]}
        if verbose:
            print(f"[FAIL] {key}: {entry['error']}")
    results[key] = entry
    save_results(results)
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="re-run cells that already have results")
    ap.add_argument("--mesh-shape", default="",
                    help="logical (data,model) override, e.g. 64,4 — "
                         "reproduces the §Perf mesh experiments")
    args = ap.parse_args()
    mesh_shape = tuple(int(x) for x in args.mesh_shape.split(",")) \
        if args.mesh_shape else ()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    pods = [False, True]
    if args.multi_pod_only:
        pods = [True]
    if args.single_pod_only:
        pods = [False]

    results = load_results()
    n_ok = n_fail = n_skip = 0
    for multi_pod in pods:
        for arch in archs:
            for shape in shapes:
                key = _result_key(arch, shape, multi_pod)
                if mesh_shape:
                    key += f"|mesh{mesh_shape[0]}x{mesh_shape[1]}"
                if not args.force and results.get(key, {}).get(
                        "status") in ("ok", "skipped"):
                    print(f"[cached] {key}: {results[key]['status']}")
                    continue
                entry = run_cell(arch, shape, multi_pod, results,
                                 mesh_shape=mesh_shape)
                s = entry["status"]
                n_ok += s == "ok"
                n_fail += s == "error"
                n_skip += s == "skipped"
    print(f"\ndone: {n_ok} ok, {n_fail} failed, {n_skip} skipped "
          f"(results in {RESULTS_PATH})")


if __name__ == "__main__":
    # a CPU-only dry run over 512 host devices; set here, not at
    # import, so importing this module never touches XLA_FLAGS
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count"
                               "=512")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
