"""The backend-portable System protocol (repro/systems; DESIGN.md §10).

Covers cross-system parity — fp32 fits on HostSystem match the
PimSystem fabric path within float tolerance; the integer PIM versions
stay bit-identical through the old import path after the move;
ModeledGpuSystem returns HostSystem numerics EXACTLY while reporting
A100-roofline time/energy — plus per-system TransferStats semantics,
step fusion on host targets, the mixed PIM+host scheduler queue with
attributable per-job stats, the compare driver, and the legacy
``pim=``-only call paths (one DeprecationWarning, identical results —
pattern of tests/test_deprecation.py).
"""
import json
import warnings

import numpy as np
import pytest

from repro.api import (PimConfig, PimSystem, get_workload,
                       make_estimator, make_system)
from repro.core import dtree, kmeans, linreg, logreg
from repro.data.synthetic import (make_blobs, make_classification,
                                  make_linear_dataset)
from repro.sched import JobState, PimScheduler
from repro.systems import (HostSystem, ModeledGpuSystem, System,
                           TransferStats)

N, F, CORES = 256, 6, 8


@pytest.fixture(scope="module")
def lin_data():
    X, y, _ = make_linear_dataset(N, F, seed=0)
    return X, y


@pytest.fixture(scope="module")
def log_data(lin_data):
    X, y = lin_data
    return X, (y > np.median(y)).astype(np.float32)


def _fit_lin(system, X, y, version, **kw):
    return linreg.fit(system.put(X, y),
                      linreg.GdConfig(version=version, n_iters=30, **kw))


# ---------------------------------------------------------------------------
# Construction + identity.
# ---------------------------------------------------------------------------

def test_make_system_kinds():
    assert isinstance(make_system("pim", n_cores=4), PimSystem)
    assert isinstance(make_system("host"), HostSystem)
    gpu = make_system("gpu-model")
    assert isinstance(gpu, ModeledGpuSystem)
    assert isinstance(gpu, HostSystem)          # numerics by inheritance
    for kind, sys_ in (("pim", make_system("pim", n_cores=2)),
                       ("host", make_system("host")),
                       ("gpu-model", gpu)):
        assert isinstance(sys_, System)
        assert sys_.kind == kind
    with pytest.raises(ValueError, match="unknown system kind"):
        make_system("tpu")


def test_pim_system_move_is_behavior_preserving(lin_data):
    """The legacy import path IS the moved class, and an INT32 fit
    through it matches the new path bit for bit (the move cannot have
    forked the implementation)."""
    from repro.core.pim import PimConfig as OldCfg, PimSystem as OldSys
    from repro.systems.pim import PimSystem as NewSys
    assert OldSys is NewSys
    X, y = lin_data
    r_old = _fit_lin(OldSys(OldCfg(n_cores=CORES)), X, y, "int32")
    r_new = _fit_lin(make_system("pim", n_cores=CORES), X, y, "int32")
    assert np.array_equal(r_old.w, r_new.w) and r_old.b == r_new.b


def test_n_shards_semantics():
    assert make_system("pim", n_cores=4).n_shards == 4
    host = make_system("host", n_cores=4)     # 4 scheduling lanes...
    assert host.n_shards == 1                 # ...but ONE resident image
    x = np.arange(10, dtype=np.float32)
    assert host.shard_rows(x).shape == (1, 10)
    assert np.asarray(host.row_validity_mask(10)).all()


# ---------------------------------------------------------------------------
# Cross-system numeric parity.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("version", ("fp32",))
def test_lin_fp32_host_matches_pim_fabric(lin_data, version):
    """fp32 GD on one resident image vs 8 fabric-reduced shards: same
    math, different summation order — float-tolerance equal."""
    X, y = lin_data
    r_pim = _fit_lin(make_system("pim", n_cores=CORES), X, y, version)
    r_host = _fit_lin(make_system("host"), X, y, version)
    np.testing.assert_allclose(r_host.w, r_pim.w, rtol=1e-4, atol=1e-5)
    assert r_host.b == pytest.approx(r_pim.b, rel=1e-4, abs=1e-5)


def test_log_fp32_host_matches_pim_within_tolerance(log_data):
    """Host fp32 uses the exact sigmoid, PIM fp32 the DPU Taylor
    expansion — decisions agree within tolerance (paper Fig. 7)."""
    X, y = log_data
    cfg = logreg.LogRegConfig(version="fp32", n_iters=40)
    r_pim = logreg.fit(make_system("pim", n_cores=CORES).put(X, y), cfg)
    r_host = logreg.fit(make_system("host").put(X, y), cfg)
    np.testing.assert_allclose(r_host.w, r_pim.w, rtol=5e-2, atol=5e-3)
    # the exact-vs-Taylor distinction is visible in the kernel registry
    host2 = make_system("host")
    logreg.fit(host2.put(X, y), cfg)
    assert any("fp32x" in k for k in host2.registered_kernels())


def test_integer_versions_run_unmodified_on_host(lin_data):
    """The quantized trainers are system-agnostic: int32 on a host
    target runs the identical integer math over one shard."""
    X, y = lin_data
    r_pim = _fit_lin(make_system("pim", n_cores=1), X, y, "int32")
    r_host = _fit_lin(make_system("host"), X, y, "int32")
    # one PIM core == one host image: the same serial reduction order,
    # the same integer bits
    assert np.array_equal(r_pim.w, r_host.w) and r_pim.b == r_host.b


def test_kmeans_fp32_host_vs_int16_pim(lin_data):
    """The fp32 K-Means version (the paper's float baseline) clusters
    like the quantized PIM version (ARI ~1, paper §5.1.4)."""
    from repro.core.metrics import adjusted_rand_index
    X, _, _ = make_blobs(400, 5, centers=4, seed=2)
    cfg = dict(k=4, max_iters=30, seed=1)
    r_pim = kmeans.fit(make_system("pim", n_cores=CORES).put(X),
                       kmeans.KMeansConfig(version="int16", **cfg))
    r_host = kmeans.fit(make_system("host").put(X),
                        kmeans.KMeansConfig(version="fp32", **cfg))
    assert adjusted_rand_index(r_pim.labels, r_host.labels) > 0.95
    np.testing.assert_allclose(r_host.centroids, r_pim.centroids,
                               rtol=0.05, atol=0.05)


def test_dtree_runs_on_all_three_systems():
    X, y = make_classification(512, 16, seed=4, class_sep=1.5)
    cfg = dtree.TreeConfig(max_depth=3, seed=0)
    trees = [dtree.fit(make_system(kind, n_cores=CORES).put(X, y), cfg)
             for kind in ("pim", "host", "gpu-model")]
    # same rng stream + exact integer split counts on every target:
    # identical trees
    for t in trees[1:]:
        assert t.n_nodes == trees[0].n_nodes
        assert np.array_equal(t.predict(X), trees[0].predict(X))


def test_gpu_model_returns_host_numerics_exactly(lin_data, log_data):
    """ModeledGpuSystem is HostSystem numerics + a roofline report —
    results must be IDENTICAL arrays, and the report must be filled."""
    X, y = lin_data
    r_host = _fit_lin(make_system("host"), X, y, "fp32")
    gpu = make_system("gpu-model")
    r_gpu = _fit_lin(gpu, X, y, "fp32")
    assert np.array_equal(r_host.w, r_gpu.w) and r_host.b == r_gpu.b
    assert gpu.gpu.launches == 30
    assert gpu.gpu.modeled_seconds > 0
    assert gpu.gpu.modeled_energy_j > 0
    # roofline floor: every launch pays the dispatch overhead
    assert gpu.gpu.modeled_seconds >= 30 * gpu.roofline.launch_overhead_s


# ---------------------------------------------------------------------------
# Per-system TransferStats semantics.
# ---------------------------------------------------------------------------

def test_hierarchical_reduce_on_host_keeps_pim_counters_zero(lin_data):
    """A hierarchical config on a host target (lane count divisible by
    the group size) must NOT leak the PIM-only rank->host counter: the
    strategy's byte accounting routes through the system hooks."""
    X, y = lin_data
    host = make_system("host", n_cores=8, reduce="hierarchical")
    linreg.fit(host.put(X, y), linreg.GdConfig(version="fp32", n_iters=3))
    assert host.stats.inter_core_via_host == 0
    assert host.stats.pim_to_cpu == 0 and host.stats.cpu_to_pim == 0


def test_host_stats_count_dram_not_transfers(lin_data):
    X, y = lin_data
    host = make_system("host")
    _fit_lin(host, X, y, "fp32")
    s = host.stats
    assert s.cpu_to_pim == 0 and s.pim_to_cpu == 0
    assert s.inter_core_via_host == 0
    # 30 launches x (X + y + mask + w + b) streamed from DRAM
    per_pass = X.size * 4 + y.size * 4 + N * 4 + F * 4 + 4
    assert s.dram_bytes == 30 * per_pass
    assert s.kernel_launches == 30 and s.host_syncs == 30
    assert s.shard_transfers == 2          # X and y views, paid once


def test_pim_stats_unchanged_by_refactor(lin_data):
    """The PIM byte accounting is exactly the pre-refactor arithmetic
    (the same closed-form the step-fusion tests pin)."""
    X, y = lin_data
    pim = make_system("pim", n_cores=CORES)
    ds = pim.put(X, y)
    cfg = linreg.GdConfig(version="int32", n_iters=5)
    linreg.fit(ds, cfg)
    snap = pim.stats.snapshot()
    linreg.fit(ds, cfg)
    d = pim.stats.delta(snap)
    assert d.dram_bytes == 0
    # per step: fabric reduce ships (gw:(F, 2), gb:(2,)) int32 fx_sum
    # pairs per core; broadcast ships (w:(F,), b:()) int32 per core
    assert d.pim_to_cpu == 5 * (F + 1) * 2 * 4 * CORES
    assert d.cpu_to_pim == 5 * (F + 1) * 4 * CORES


def test_step_fusion_on_host_system(lin_data):
    """HostSystem fuses trivially (no reduce leg): one launch per
    chunk, bit-identical integer trajectory."""
    X, y = lin_data
    host1 = make_system("host")
    r1 = _fit_lin(host1, X, y, "int32")
    hostk = make_system("host")
    rk = _fit_lin(hostk, X, y, "int32", fuse_steps=8)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b
    assert host1.stats.kernel_launches == 30
    assert hostk.stats.kernel_launches == 4      # chunks of 8,8,8,6
    assert hostk.stats.host_syncs == 4


# ---------------------------------------------------------------------------
# Scheduler: mixed PIM + host machine.
# ---------------------------------------------------------------------------

def test_scheduler_runs_mixed_pim_host_queue(lin_data):
    X, y = lin_data
    pim = PimSystem(PimConfig(n_cores=CORES))
    host = make_system("host", n_cores=4)
    sched = PimScheduler({"pim": pim, "host": host},
                         rank_size=CORES // 2)
    n_iters = 12
    h_pim = sched.submit("linreg", (X, y), version="int32",
                         n_iters=n_iters)
    h_host = sched.submit("linreg", (X, y), version="fp32",
                          n_iters=n_iters, target="host")
    h_kme = sched.submit("kmeans", (X, None), version="fp32",
                         n_clusters=3, max_iter=6, target="host")
    sched.drain()
    assert all(h.state is JobState.DONE for h in (h_pim, h_host, h_kme))
    assert (h_pim.target, h_host.target) == ("pim", "host")
    # attributable per-job deltas carry each target's OWN semantics
    assert h_pim.transfer.cpu_to_pim > 0 and h_pim.transfer.dram_bytes == 0
    assert h_host.transfer.dram_bytes > 0 and h_host.transfer.cpu_to_pim == 0
    assert h_host.transfer.kernel_launches == n_iters
    # DPU cycle accounting only applies to the PIM target
    assert h_pim.modeled_seconds > 0
    assert h_host.modeled_seconds == 0 and h_kme.modeled_seconds == 0
    # the host job matches a solo host fit bit for bit
    solo = linreg.fit(make_system("host").put(X, y),
                      linreg.GdConfig(version="fp32", n_iters=n_iters))
    assert np.array_equal(h_host.result.attributes["coef_"], solo.w)
    # per-target occupancy is visible and released
    st = sched.stats()
    assert set(st["targets"]) == {"pim", "host"}
    assert st["targets"]["host"]["cores_used"] == 0


def test_unknown_target_rejected(lin_data):
    X, y = lin_data
    sched = PimScheduler(PimSystem(PimConfig(n_cores=CORES)))
    with pytest.raises(ValueError, match="unknown target"):
        sched.submit("linreg", (X, y), version="int32", target="host")


def test_full_pim_machine_does_not_stall_host_admissions(lin_data):
    """Head-of-line blocking is per target on a mixed machine."""
    X, y = lin_data
    sched = PimScheduler({"pim": PimSystem(PimConfig(n_cores=CORES)),
                          "host": make_system("host", n_cores=2)},
                         rank_size=CORES)
    h1 = sched.submit("linreg", (X, y), version="int32", n_iters=4,
                      n_cores=CORES)
    h2 = sched.submit("linreg", (X, y), version="int32", n_iters=4,
                      n_cores=CORES)          # queued behind h1
    h3 = sched.submit("linreg", (X, y), version="fp32", n_iters=4,
                      target="host")
    sched.step()
    # h2 cannot start (machine full) but the host job was admitted
    assert h1.state is JobState.RUNNING
    assert h2.state is JobState.QUEUED
    assert h3.state is JobState.RUNNING
    sched.drain()
    assert all(h.state is JobState.DONE for h in (h1, h2, h3))


# ---------------------------------------------------------------------------
# The compare driver (acceptance: all four workloads, three systems).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_compare_tiny_produces_three_way_table(tmp_path):
    from repro.launch import compare
    record = compare.main(["--tiny", "--cores", "4",
                           "--out", str(tmp_path / "compare.json")])
    with open(tmp_path / "compare.json") as fh:
        on_disk = json.load(fh)
    assert on_disk["meta"]["systems"] == ["pim", "host", "gpu-model"]
    rows = record["rows"]
    seen = {(r["workload"], r["system"]) for r in rows}
    assert seen == {(w, s)
                    for w in ("linreg", "logreg", "dtree", "kmeans", "emb")
                    for s in ("pim", "host", "gpu-model")}
    for r in rows:
        assert r["modeled_s"] > 0 and r["wall_s"] >= 0
    # host and gpu-model rows share numerics -> identical scores
    by_key = {(r["workload"], r["system"]): r for r in rows}
    for w in ("linreg", "logreg", "dtree", "kmeans", "emb"):
        assert by_key[(w, "host")]["score"] == \
            by_key[(w, "gpu-model")]["score"]


# ---------------------------------------------------------------------------
# Legacy PimSystem-only call paths: one DeprecationWarning, identical
# results (pattern from tests/test_deprecation.py).
# ---------------------------------------------------------------------------

def _deprecations(fn):
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        result = fn()
    return result, [w for w in rec
                    if issubclass(w.category, DeprecationWarning)]


def test_make_estimator_pim_kwarg_warns_once_and_matches(lin_data):
    X, y = lin_data
    pim = PimSystem(PimConfig(n_cores=CORES))
    est, deps = _deprecations(
        lambda: make_estimator("linreg", version="int32", n_iters=10,
                               pim=pim))
    assert len(deps) == 1 and "system=" in str(deps[0].message)
    _, deps_fit = _deprecations(lambda: est.fit(X, y))
    assert len(deps_fit) == 0
    modern = make_estimator("linreg", version="int32", n_iters=10,
                            system=PimSystem(PimConfig(n_cores=CORES))
                            ).fit(X, y)
    assert np.array_equal(est.coef_, modern.coef_)
    assert est.intercept_ == modern.intercept_
    # the deprecated alias attribute still reads (and is the system)
    assert est.pim is est.system


def test_set_params_pim_kwarg_warns_once(lin_data):
    est = make_estimator("linreg", version="int32", n_iters=5)
    other = PimSystem(PimConfig(n_cores=4))
    _, deps = _deprecations(lambda: est.set_params(pim=other))
    assert len(deps) == 1
    assert est.system is other and est.n_cores == 4


def test_train_wrappers_accept_any_system(lin_data):
    """The deprecated train(...) shims are System-generic now: a
    HostSystem flows through with the same single warning."""
    X, y = lin_data
    host = make_system("host")
    r_legacy, deps = _deprecations(
        lambda: linreg.train(X, y, host,
                             linreg.GdConfig(version="fp32", n_iters=8)))
    assert len(deps) == 1
    r_new = linreg.fit(make_system("host").put(X, y),
                       linreg.GdConfig(version="fp32", n_iters=8))
    assert np.array_equal(r_legacy.w, r_new.w) and r_legacy.b == r_new.b


# ---------------------------------------------------------------------------
# Estimator + registry integration.
# ---------------------------------------------------------------------------

def test_estimator_system_kwarg_and_adoption(lin_data):
    X, y = lin_data
    host = make_system("host")
    est = make_estimator("linreg", version="fp32", n_iters=10,
                         system=host).fit(X, y)
    assert est.system is host
    # fitting a dataset adopts ITS system (here: a different target)
    pim = PimSystem(PimConfig(n_cores=CORES))
    est.fit(pim.put(X, y))
    assert est.system is pim


def test_estimator_rejects_y_with_dataset(lin_data):
    X, y = lin_data
    host = make_system("host")
    ds = host.put(X, y)
    with pytest.raises(ValueError, match="System.put"):
        make_estimator("linreg", system=host).fit(ds, y)


def test_kmeans_fp32_version_via_registry():
    X, _, _ = make_blobs(300, 4, centers=3, seed=5)
    est = make_estimator("kmeans", version="fp32", n_clusters=3,
                         max_iter=10,
                         system=make_system("host")).fit(X)
    assert est.cluster_centers_.shape == (3, 4)
    assert get_workload("kmeans").versions == ("int16", "fp32")


@pytest.mark.slow
def test_compare_rerun_other_cores_and_shape_table(tmp_path):
    """The compare driver re-run at a different core count/seed stays
    complete, and the non-tiny shape table is well-formed (the full
    shapes themselves run via `make bench` — fig13_17_compare)."""
    from repro.launch.compare import _shapes, run_compare
    record = run_compare(tiny=True, cores=8, seed=1)
    assert len(record["rows"]) == 15
    full = _shapes(tiny=False)
    assert set(full) == {"linreg", "logreg", "dtree", "kmeans", "emb"}
    for n, f, params in full.values():
        assert n > 0 and f > 0 and params
