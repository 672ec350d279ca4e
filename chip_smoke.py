#!/usr/bin/env python3
"""Smoke run of the PIM-ML training path on a TPU chip.

    python chip_smoke.py [--seed N]        # one chip: all five workloads
    python chip_smoke.py --four-chips      # 2x2 host: shard_map vs vmap

One process drives the chip.  Every phase goes through the entry points
a user calls — ``make_system("pim")`` -> ``system.put(X, y)`` ->
``make_estimator(..., system=system).fit(ds)`` — at the paper's dataset
shapes (SUSY 5,000,000 x 18, Higgs 11,000,000 x 28; benchmarks/
fig13_17_compare.py), on random data drawn from ``--seed`` by
``repro.data.synthetic``, with the kernels on the default backend
(``pallas_tpu`` on a TPU).  Each fit is checked against a float32 fit
of the same data on ``make_system("host")``, with the tolerance bands
the repository's tests use, and prints one line: workload, version,
shape, bytes placed on the banks, fit seconds (compile included — not a
benchmark), score, and the kernel ops it traced with their backend.

Any failed phase makes the script exit non-zero and withholds the last
line, which is otherwise exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU it exits 2 before any phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

SUSY = (5_000_000, 18)
HIGGS = (11_000_000, 28)
EMB_ROWS, EMB_DIM, EMB_BATCH = 1_048_576, 128, 4096
GD_ITERS, KME_ITERS, DTR_DEPTH, EMB_ITERS = 20, 5, 4, 20
#: LOG sits at chance (about 47 % training error) after 20 iterations on
#: this data, where any fit would pass its check; by 100 it is near 4 %
LOG_ITERS = 100


def _fit(name, version, ds, **params):
    """One fit through the estimator facade: (estimator, line fields for
    the bytes placed, the seconds taken and the kernel ops traced).

    A fit on the ``host`` system is the float32 reference, so its
    matmuls run at float32 precision (a TPU's default is one bfloat16
    pass); the system under test keeps the defaults."""
    import jax
    from repro.api import make_estimator
    from repro.kernels import dispatch
    before = dict(dispatch.launch_counts)
    placed = ds.system.stats.shard_bytes
    precision = "highest" if ds.system.kind == "host" else None
    t0 = time.perf_counter()
    with jax.default_matmul_precision(precision):
        est = make_estimator(name, version=version, system=ds.system,
                             **params).fit(ds)
    secs = time.perf_counter() - t0
    ops = sorted(op for op, n in dispatch.launch_counts.items()
                 if n != before.get(op, 0))
    be = dispatch.default_backend().value
    fields = {"placed_bytes": ds.system.stats.shard_bytes - placed,
              "fit_s": f"{secs:.3f}",
              "ops": ",".join(f"{op}@{be}" for op in ops) or "-"}
    return est, fields


def _line(phase, version, shape, **fields):
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"{phase} {version} shape={'x'.join(map(str, shape))} {kv}",
          flush=True)


def _check(ok, what):
    if not ok:
        raise AssertionError(what)


def phase_lin_log(seed, n=SUSY[0], f=SUSY[1]):
    """LIN int32 (serial and fused: bitwise equal) and hyb; LOG int32
    and int32_lut_wram — all on one SUSY-shaped dataset."""
    from repro.core.metrics import training_error_rate
    from repro.data.synthetic import make_linear_dataset
    from repro.systems import make_system
    X, y, _ = make_linear_dataset(n, f, seed=seed)
    shape = (n, f)

    def lin_err(est):        # regression output thresholded at 0.5
        return training_error_rate(est.predict(X), y)

    def log_err(est):        # logit thresholded at 0
        return training_error_rate(est.decision_function(X), y, 0.0)

    for name, phase, err, iters, versions in (
            ("linreg", "LIN", lin_err, GD_ITERS,
             (("int32", 1), ("int32", GD_ITERS), ("hyb", 1))),
            ("logreg", "LOG", log_err, LOG_ITERS,
             (("int32", 1), ("int32_lut_wram", 1)))):
        ref, fields = _fit(name, "fp32", make_system("host").put(X, y),
                           n_iters=iters)
        ref_err = err(ref)
        _line(phase, "fp32@host", shape, **fields,
              train_err_pct=f"{ref_err:.4f}")
        del ref
        ds = make_system("pim").put(X, y)
        errs, coefs = {}, []
        for version, fuse in versions:
            est, fields = _fit(name, version, ds, n_iters=iters,
                               fuse_steps=fuse)
            errs[version] = err(est)
            if phase == "LIN" and version == "int32":
                coefs.append((est.coef_.copy(), est.intercept_))
            _line(phase, f"{version} fuse_steps={fuse} n_iters={iters}",
                  shape, **fields,
                  train_err_pct=f"{errs[version]:.4f}",
                  ref_err_pct=f"{ref_err:.4f}")
        if phase == "LIN":
            (w1, b1), (w20, b20) = coefs
            same = bool(np.array_equal(w1, w20) and b1 == b20)
            print(f"LIN int32 fuse_steps=1 vs fuse_steps={GD_ITERS} "
                  f"bitwise_equal={same}", flush=True)
            _check(same, "LIN int32 fused fit differs from the serial fit")
            # tests/test_quality_repro.py: integer versions within
            # 1 pt (int32) and 1.5 pt (hyb) of fp32
            _check(abs(errs["int32"] - ref_err) < 1.0, f"LIN int32 {errs}")
            _check(abs(errs["hyb"] - ref_err) < 1.5, f"LIN hyb {errs}")
        else:
            # tests/test_quality_repro.py: LUT no worse than Taylor
            # (+0.25 pt); the integer Taylor version within the LIN
            # integer band of the float32 reference
            _check(errs["int32_lut_wram"] <= errs["int32"] + 0.25,
                   f"LOG lut vs taylor {errs}")
            _check(abs(errs["int32"] - ref_err) < 1.0, f"LOG int32 {errs}")
        del ds
        gc.collect()


def phase_kme(seed, n=HIGGS[0], f=HIGGS[1]):
    from repro.core.metrics import adjusted_rand_index, calinski_harabasz
    from repro.data.synthetic import make_blobs
    from repro.systems import make_system
    X, _, _ = make_blobs(n, f, centers=16, seed=seed)
    shape = (n, f)
    params = dict(n_clusters=16, max_iter=KME_ITERS, seed=seed)
    ref, fields = _fit("kmeans", "fp32", make_system("host").put(X),
                       **params)
    ref_labels = ref.labels_
    _line("KME", "fp32@host", shape, **fields,
          inertia=f"{ref.inertia_:.6g}")
    del ref, fields
    gc.collect()
    est, fields = _fit("kmeans", "int16", make_system("pim").put(X),
                       **params)
    ari = adjusted_rand_index(est.labels_, ref_labels)
    ch, ch_ref = (calinski_harabasz(X, est.labels_),
                  calinski_harabasz(X, ref_labels))
    _line("KME", "int16", shape, **fields, ari_vs_ref=f"{ari:.6f}",
          ch_rel_diff=f"{abs(ch - ch_ref) / ch_ref:.6f}")
    # tests/test_quality_repro.py: ARI > 0.95, CH within 2 %
    _check(ari > 0.95, f"KME ARI {ari}")
    _check(abs(ch - ch_ref) <= 0.02 * ch_ref, f"KME CH {ch} vs {ch_ref}")


def phase_dtr(seed, n=HIGGS[0], f=HIGGS[1]):
    from repro.core.metrics import accuracy
    from repro.data.synthetic import make_classification
    from repro.systems import make_system
    X, y = make_classification(n, f, seed=seed)
    shape = (n, f)
    params = dict(max_depth=DTR_DEPTH, seed=seed)
    accs = {}
    for kind in ("host", "pim"):
        est, fields = _fit("dtree", "fp32", make_system(kind).put(X, y),
                           **params)
        accs[kind] = accuracy(est.predict(X), y)
        _line("DTR", "fp32" + ("@host" if kind == "host" else ""), shape,
              **fields, n_nodes=est.n_nodes_,
              train_acc=f"{accs[kind]:.6f}")
        del est
        gc.collect()
    # tests/test_quality_repro.py: PIM within 0.04 of the CPU accuracy
    _check(abs(accs["pim"] - accs["host"]) < 0.04, f"DTR {accs}")


def phase_emb(seed, n_rows=EMB_ROWS, dim=EMB_DIM, batch=EMB_BATCH,
              n_samples=EMB_ROWS):
    """Two 1,048,576 x 128 tables (8 MiB per core shard at 64 cores)."""
    from repro.data.synthetic import make_recsys
    from repro.systems import make_system
    X, y = make_recsys(n_samples, n_rows, n_rows, dim=dim, seed=seed)
    shape = (n_rows, dim)
    # Q16 keeps lr / batch = 2^-12 exact in the int32 version
    params = dict(n_iters=EMB_ITERS, batch=batch, dim=dim, lr=1.0,
                  frac_bits=16, n_users=n_rows, n_items=n_rows,
                  record_every=1, seed=seed)
    fits = {}
    for kind, version in (("host", "fp32"), ("pim", "fp32"),
                          ("pim", "int32")):
        est, fields = _fit("emb", version, make_system(kind).put(X, y),
                           **params)
        hist = [m for _, m in est.result_.model.history]
        fits[(kind, version)] = (est.result_.model, hist)
        _line("EMB", version + ("@host" if kind == "host" else ""), shape,
              **fields, batch=batch, first_mse=f"{hist[0]:.6g}",
              last_mse=f"{hist[-1]:.6g}")
        del est
        gc.collect()
    ref, ref_hist = fits[("host", "fp32")]
    got, _ = fits[("pim", "fp32")]
    same = bool(np.array_equal(got.user_raw, ref.user_raw)
                and np.array_equal(got.item_raw, ref.item_raw))
    print(f"EMB fp32 pim vs host tables bitwise_equal={same}", flush=True)
    q_hist = fits[("pim", "int32")][1]
    print(f"EMB int32 final_mse/fp32_final_mse="
          f"{q_hist[-1] / ref_hist[-1]:.6f}", flush=True)
    # tests/test_emb.py: host and pim fp32 tables agree bit for bit
    # (test_host_matches_pim_bitwise); both versions learn
    # (test_eager_learns_both_versions)
    _check(same, "EMB fp32 tables differ between pim and host")
    for (kind, version), (_, hist) in fits.items():
        _check(hist[-1] < hist[0], f"EMB {kind} {version} did not learn")


def phase_four_chips(seed, lin_shape=SUSY, kme_shape=HIGGS):
    """LIN int32 and KME int16 with one PIM core per chip (shard_map over
    four devices) against the same fits with four cores vmapped on one
    chip: bitwise equal."""
    from repro.data.synthetic import make_blobs, make_linear_dataset
    from repro.systems import make_system
    X, y, _ = make_linear_dataset(*lin_shape, seed=seed)
    out = {}
    for backend in ("vmap", "shard_map"):
        est, fields = _fit("linreg", "int32",
                           make_system("pim", n_cores=4,
                                       backend=backend).put(X, y),
                           n_iters=GD_ITERS)
        out[backend] = (est.coef_.copy(), est.intercept_)
        _line("LIN", f"int32 cores=4 backend={backend}", lin_shape,
              **fields)
    same = bool(np.array_equal(out["vmap"][0], out["shard_map"][0])
                and out["vmap"][1] == out["shard_map"][1])
    print(f"LIN int32 shard_map vs vmap bitwise_equal={same}", flush=True)
    _check(same, "LIN int32 shard_map fit differs from vmap")
    del X, y
    X, _, _ = make_blobs(*kme_shape, centers=16, seed=seed)
    out = {}
    for backend in ("vmap", "shard_map"):
        est, fields = _fit("kmeans", "int16",
                           make_system("pim", n_cores=4,
                                       backend=backend).put(X),
                           n_clusters=16, max_iter=KME_ITERS, seed=seed)
        out[backend] = (est.cluster_centers_.copy(), est.labels_.copy())
        _line("KME", f"int16 cores=4 backend={backend}", kme_shape,
              **fields, inertia=f"{est.inertia_:.6g}")
    same = all(np.array_equal(a, b)
               for a, b in zip(out["vmap"], out["shard_map"]))
    print(f"KME int16 shard_map vs vmap bitwise_equal={same}", flush=True)
    _check(same, "KME int16 shard_map fit differs from vmap")


ONE_CHIP = (phase_lin_log, phase_kme, phase_dtr, phase_emb)


def run(phases, seed) -> bool:
    """Run every phase; a failure is reported and the rest still run."""
    ok = True
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(seed)
        except Exception:  # noqa: BLE001 - reported, and fails the run
            ok = False
            traceback.print_exc()
            print(f"{phase.__name__} FAILED", flush=True)
        else:
            print(f"{phase.__name__} passed in "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
        gc.collect()
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the shard_map-over-four-chips comparison")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if args.four_chips and n_dev < 4:
        print(f"--four-chips needs 4 devices, found {n_dev}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev.device_kind} x{n_dev}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    phases = (phase_four_chips,) if args.four_chips else ONE_CHIP
    if not run(phases, args.seed):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
