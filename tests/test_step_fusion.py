"""Step-fusion engine (core/pim.py StepProgram; DESIGN.md §9).

Covers fused-vs-serial bit identity for every integer trainer version
(LIN and LOG run one driver, so the chunking cases take both), float
closeness for fp32/K-Means, chunk-boundary ``record_every``
equivalence, the analytic TransferStats chunk accounting (k=32 chunk ==
ONE kernel launch — the CI assertion; a one-step chunk == one
host-orchestrated step), HostReduce degradation, and
scheduler integration with mixed fused/unfused jobs; the large-k and
fused-gang cases are marked ``slow``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PimConfig, PimSystem, make_estimator
from repro.core import kmeans, linreg, logreg
from repro.core.pim import HierarchicalReduce, ReduceVia
from repro.data.synthetic import make_blobs, make_linear_dataset
from repro.sched import JobState, PimScheduler

N, F, CORES = 256, 6, 8


@pytest.fixture(scope="module")
def lin_data():
    X, y, _ = make_linear_dataset(N, F, seed=0)
    return X, y


@pytest.fixture(scope="module")
def log_data(lin_data):
    X, y = lin_data
    return X, (y > np.median(y)).astype(np.float32)


@pytest.fixture(params=("linreg", "logreg"))
def gd(request, lin_data, log_data):
    """One GD workload of the shared driver: (registry name, trainer
    module, config class, an integer version, its data)."""
    if request.param == "linreg":
        return "linreg", linreg, linreg.GdConfig, "int32", lin_data
    return ("logreg", logreg, logreg.LogRegConfig, "int32_lut_wram",
            log_data)


def _gd_fit(gd, fuse, n_iters=40, eval_fn=None, **kw):
    _, mod, cfg_cls, ver, (X, y) = gd
    ds = PimSystem(PimConfig(n_cores=CORES)).put(X, y)
    return mod.fit(ds, cfg_cls(version=ver, n_iters=n_iters,
                               fuse_steps=fuse, **kw), eval_fn=eval_fn)


def _lin_pair(X, y, ver, fuse, n_iters=40, **kw):
    pim = PimSystem(PimConfig(n_cores=CORES, **kw.pop("pim_kw", {})))
    ds = pim.put(X, y)
    cfg = linreg.GdConfig(version=ver, n_iters=n_iters, fuse_steps=fuse,
                          **kw)
    return linreg.fit(ds, cfg), pim


# ---------------------------------------------------------------------------
# Acceptance: fused == serial, bit for bit, for every integer version.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ver", ("int32", "hyb", "bui"))
def test_lin_fused_bit_identical(lin_data, ver):
    X, y = lin_data
    r1, _ = _lin_pair(X, y, ver, fuse=1)
    rk, _ = _lin_pair(X, y, ver, fuse=8)
    assert np.array_equal(r1.w, rk.w)
    assert r1.b == rk.b


def test_lin_fp32_fused_close(lin_data):
    X, y = lin_data
    r1, _ = _lin_pair(X, y, "fp32", fuse=1)
    rk, _ = _lin_pair(X, y, "fp32", fuse=8)
    np.testing.assert_allclose(r1.w, rk.w, rtol=1e-5, atol=1e-6)
    assert r1.b == pytest.approx(rk.b, rel=1e-5, abs=1e-6)


@pytest.mark.parametrize("ver", ("int32", "int32_lut_wram", "hyb_lut",
                                 "bui_lut"))
def test_log_fused_bit_identical(log_data, ver):
    X, y = log_data
    results = []
    for fuse in (1, 8):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(X, y)
        results.append(logreg.fit(ds, logreg.LogRegConfig(
            version=ver, n_iters=30, fuse_steps=fuse)))
    assert np.array_equal(results[0].w, results[1].w)
    assert results[0].b == results[1].b


def test_kmeans_fused_inertia_close():
    Xb, _, _ = make_blobs(300, 4, centers=5, seed=1)
    results = []
    for fuse in (1, 8):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(Xb)
        results.append(kmeans.fit(ds, kmeans.KMeansConfig(
            k=5, max_iters=40, seed=3, fuse_steps=fuse)))
    r1, rk = results
    assert rk.inertia == pytest.approx(r1.inertia, rel=1e-4)
    assert rk.n_iters == r1.n_iters       # on-device done flag matches
    np.testing.assert_allclose(r1.centroids, rk.centroids,
                               rtol=1e-4, atol=1e-3)


def test_fused_partial_tail_chunk(gd):
    """n_iters not divisible by fuse_steps: the tail chunk is clipped,
    total iterations exact."""
    r1 = _gd_fit(gd, fuse=1, n_iters=21)
    rk = _gd_fit(gd, fuse=8, n_iters=21)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b


# ---------------------------------------------------------------------------
# record_every lands on chunk boundaries with identical history.
# ---------------------------------------------------------------------------

def test_record_every_chunk_boundary_equivalence(gd):
    def run(fuse):
        return _gd_fit(gd, fuse, n_iters=25, record_every=10,
                       eval_fn=lambda w, b: (w.copy(), float(b)))

    r1, rk = run(1), run(8)
    assert [it for it, _ in r1.history] == [it for it, _ in rk.history] \
        == [10, 20, 25]
    for (_, (w1, b1)), (_, (wk, bk)) in zip(r1.history, rk.history):
        assert np.array_equal(w1, wk) and b1 == bk


# ---------------------------------------------------------------------------
# TransferStats chunk accounting.
# ---------------------------------------------------------------------------

def test_k32_chunk_is_one_launch_one_sync(lin_data):
    """THE fusion assertion (scripts/ci.sh): a k=32 chunk is ONE
    host-issued kernel launch and ONE host sync."""
    X, y = lin_data
    pim = PimSystem(PimConfig(n_cores=CORES))
    ds = pim.put(X, y)
    linreg.fit(ds, linreg.GdConfig(version="int32", n_iters=32,
                                   fuse_steps=32))  # warm the view cache
    snap = pim.stats.snapshot()
    linreg.fit(ds, linreg.GdConfig(version="int32", n_iters=32,
                                   fuse_steps=32))
    d = pim.stats.delta(snap)
    assert d.kernel_launches == 1
    assert d.host_syncs == 1


def test_one_step_chunk_counts_as_per_step(lin_data):
    """Under FabricReduce, a one-step chunk — run directly, as a fused
    fit's tail, or as every chunk of a ``fuse_steps=1`` fit — has the
    launch, sync and byte deltas of ``StepProgram._run_per_step``."""
    X, y = lin_data
    pim = PimSystem(PimConfig(n_cores=CORES, reduce=ReduceVia.FABRIC))
    ds = pim.put(X, y)
    cfg = linreg.GdConfig(version="int32", n_iters=1)
    view = ds.gd_view(cfg.version, cfg.frac_bits, cfg.x8_frac)
    prepare, update = linreg.make_gd_step_fns(cfg)
    program = pim.step_program(pim.named_kernel(*linreg.LIN.kernel(pim, cfg)),
                               prepare, update, name="test.one_step")
    carry = (jnp.zeros(F, jnp.float32), jnp.float32(0.0),
             jnp.float32(0.1))

    def delta(run):
        run()                           # warm the view and jit caches
        snap = pim.stats.snapshot()
        run()
        d = pim.stats.delta(snap)
        return (d.kernel_launches, d.host_syncs, d.cpu_to_pim,
                d.pim_to_cpu)

    per_step = delta(lambda: program._run_per_step(carry, view, 1))
    assert per_step[:2] == (1, 1)
    assert delta(lambda: program.run(carry, view, 1)) == per_step
    assert delta(lambda: linreg.fit(ds, cfg)) == per_step
    # 9 iterations at fuse_steps=8: the tail chunk of one step costs
    # what a fuse_steps=1 step does
    fused = dataclasses.replace(cfg, n_iters=9, fuse_steps=8)
    head = dataclasses.replace(fused, n_iters=8)
    d9, d8 = delta(lambda: linreg.fit(ds, fused)), delta(
        lambda: linreg.fit(ds, head))
    assert tuple(a - b for a, b in zip(d9, d8)) == per_step


def test_unfused_counts_one_launch_per_step(lin_data):
    X, y = lin_data
    pim = PimSystem(PimConfig(n_cores=CORES))
    ds = pim.put(X, y)
    n_iters = 12
    linreg.fit(ds, linreg.GdConfig(version="int32", n_iters=n_iters))
    snap = pim.stats.snapshot()
    linreg.fit(ds, linreg.GdConfig(version="int32", n_iters=n_iters))
    d = pim.stats.delta(snap)
    assert d.kernel_launches == n_iters
    assert d.host_syncs == n_iters


def test_chunk_reduce_bytes_scale_k_times(lin_data):
    """The fabric reduce still moves k x the single-step bytes per
    chunk; only the sync count and broadcast bytes collapse."""
    X, y = lin_data
    k = 8

    def deltas(fuse):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(X, y)
        cfg = linreg.GdConfig(version="int32", n_iters=k, fuse_steps=fuse)
        linreg.fit(ds, cfg)
        snap = pim.stats.snapshot()
        linreg.fit(ds, cfg)
        return pim.stats.delta(snap)

    du, df = deltas(1), deltas(k)
    # per-step reduce legs: identical byte totals (k x single-step)...
    assert df.pim_to_cpu >= du.pim_to_cpu
    # ...up to the single chunk-boundary sync of carry + emits
    assert df.pim_to_cpu - du.pim_to_cpu <= (F + 2) * 4
    # broadcasts collapse: one carry broadcast per chunk vs k per-step
    assert df.cpu_to_pim < du.cpu_to_pim
    assert df.host_syncs == 1 and du.host_syncs == k


def test_chunk_accounting_not_cached_across_widths(lin_data):
    """Two same-n datasets of different width on ONE system produce
    same-named programs; the reduce-leg byte accounting must follow
    each dataset's true shapes, not a stale cached eval_shape."""
    k = 8
    pim = PimSystem(PimConfig(n_cores=CORES))
    for feat in (4, 12):
        X, y, _ = make_linear_dataset(N, feat, seed=1)
        ds = pim.put(X, y)
        cfg = linreg.GdConfig(version="int32", n_iters=k, fuse_steps=k)
        linreg.fit(ds, cfg)
        snap = pim.stats.snapshot()
        linreg.fit(ds, cfg)
        d = pim.stats.delta(snap)
        # fabric reduce legs: k x (gw:(F, 2), gb:(2,)) int32 fx_sum
        # pairs x n_cores, plus the chunk-boundary sync of the (w, b, s)
        # carry
        assert d.pim_to_cpu == (k * (feat + 1) * 2 * 4 * CORES
                                + (feat + 2) * 4)


def test_hierarchical_chunk_accounting(lin_data):
    """HierarchicalReduce fuses fully on device; the modeled rank->host
    leg still accrues k x per-step bytes (inter_core_via_host)."""
    X, y = lin_data
    k = 6
    pim = PimSystem(PimConfig(n_cores=CORES,
                              reduce=ReduceVia.HIERARCHICAL))
    ds = pim.put(X, y)
    cfg = linreg.GdConfig(version="int32", n_iters=k, fuse_steps=k)
    linreg.fit(ds, cfg)
    snap = pim.stats.snapshot()
    r = linreg.fit(ds, cfg)
    d = pim.stats.delta(snap)
    assert d.kernel_launches == 1
    # HierarchicalReduce(8) on 8 cores -> 1 group; per-step rank
    # partials: (1, F, 2) int32 gw + (1, 2) int32 gb fx_sum pairs
    per_step = (F + 1) * 2 * 4
    assert d.inter_core_via_host == k * per_step
    # matches the unfused hierarchical trajectory bit for bit
    pim2 = PimSystem(PimConfig(n_cores=CORES,
                               reduce=ReduceVia.HIERARCHICAL))
    r2 = linreg.fit(pim2.put(X, y),
                    linreg.GdConfig(version="int32", n_iters=k))
    assert np.array_equal(r.w, r2.w) and r.b == r2.b


def test_host_reduce_degrades_to_per_step(lin_data):
    """HostReduce cannot fuse (the reduce IS a host round trip): the
    chunk runs as k single steps with unfused accounting — and stays
    bit-identical."""
    X, y = lin_data
    k = 6

    def run(fuse):
        pim = PimSystem(PimConfig(n_cores=CORES, reduce=ReduceVia.HOST))
        ds = pim.put(X, y)
        cfg = linreg.GdConfig(version="int32", n_iters=k, fuse_steps=fuse)
        r = linreg.fit(ds, cfg)
        snap = pim.stats.snapshot()
        r = linreg.fit(ds, cfg)
        return r, pim.stats.delta(snap)

    r1, d1 = run(1)
    rk, dk = run(k)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b
    assert dk.kernel_launches == d1.kernel_launches == k
    assert dk.host_syncs == d1.host_syncs == k


def test_minibatch_fuses_with_offset_scan_xs(lin_data):
    """Minibatch SGD no longer falls back (DESIGN.md §9.5): each chunk's
    batch offsets are pre-drawn from the serial loop's rng stream and
    fed through the scan as xs — bit-identical trajectory, and the
    launch count collapses to one per chunk."""
    X, y = lin_data
    r1, p1 = _lin_pair(X, y, "int32", fuse=1, n_iters=10, minibatch=8,
                       seed=7)
    rk, pk = _lin_pair(X, y, "int32", fuse=8, n_iters=10, minibatch=8,
                       seed=7)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b
    # 10 iterations at fuse_steps=8 -> chunks of 8 + 2: TWO launches
    # (and syncs) where the serial SGD loop pays ten of each
    assert p1.stats.kernel_launches == 10 and p1.stats.host_syncs == 10
    assert pk.stats.kernel_launches == 2 and pk.stats.host_syncs == 2


def test_log_minibatch_trains_on_batches(log_data):
    """LOG honours ``minibatch`` as its registry entry promises: fused
    equals unfused bit for bit, and both differ from full-batch GD."""
    X, y = log_data

    def fit(fuse, minibatch):
        ds = PimSystem(PimConfig(n_cores=CORES)).put(X, y)
        return logreg.fit(ds, logreg.LogRegConfig(
            version="int32_lut_wram", n_iters=21, fuse_steps=fuse,
            minibatch=minibatch, seed=3, record_every=10))

    r1, rk, full = fit(1, 8), fit(8, 8), fit(1, 0)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b
    assert not np.array_equal(r1.w, full.w)


@pytest.mark.parametrize("ver", ("int32", "hyb"))
def test_minibatch_fused_bit_identical_versions(lin_data, ver):
    """Fused minibatch SGD == serial minibatch SGD, bit for bit, with a
    non-dividing tail chunk and record_every landing mid-stream."""
    X, y = lin_data
    kw = dict(n_iters=21, minibatch=8, seed=3, record_every=10)
    r1, _ = _lin_pair(X, y, ver, fuse=1, **kw)
    rk, _ = _lin_pair(X, y, ver, fuse=8, **kw)
    assert np.array_equal(r1.w, rk.w) and r1.b == rk.b


# ---------------------------------------------------------------------------
# API + scheduler integration.
# ---------------------------------------------------------------------------

def test_estimator_exposes_fuse_steps(lin_data):
    X, y = lin_data
    e1 = make_estimator("linreg", version="int32", n_iters=30,
                        n_cores=CORES).fit(X, y)
    ek = make_estimator("linreg", version="int32", n_iters=30,
                        fuse_steps=8, n_cores=CORES).fit(X, y)
    assert ek.get_params()["fuse_steps"] == 8
    assert np.array_equal(e1.coef_, ek.coef_)


def test_scheduler_mixed_fused_unfused_jobs(lin_data):
    """A fused-chunk job and a per-step job interleave in one queue;
    both finish, chunk accounting is attributable, results match solo
    fits bit for bit."""
    X, y = lin_data
    system = PimSystem(PimConfig(n_cores=CORES))
    sched = PimScheduler(system, rank_size=CORES // 2)
    n_iters = 24
    hf = sched.submit("linreg", (X, y), version="int32", n_iters=n_iters,
                      fuse_steps=8)
    hu = sched.submit("linreg", (X, y), version="int32", n_iters=n_iters)
    sched.drain()
    assert hf.state is JobState.DONE and hu.state is JobState.DONE
    assert np.array_equal(hf.result.attributes["coef_"],
                          hu.result.attributes["coef_"])
    # the fused job took 3 chunk turns covering 24 iterations
    assert hf.steps == 3 and hf.iters == n_iters
    assert hu.steps == n_iters and hu.iters == n_iters
    assert hf.transfer.kernel_launches == 3
    assert hu.transfer.kernel_launches == n_iters
    # per-iteration cost-model accounting matches across the two modes
    assert hf.modeled_seconds == pytest.approx(hu.modeled_seconds)


@pytest.mark.slow
def test_fused_gang_with_step_chunks_matches_serial(lin_data):
    """Lane fusion x step fusion: a fused lr-sweep gang whose specs
    carry fuse_steps advances K lanes x k steps per launch and stays
    bit-identical to serial unfused fits."""
    X, y = lin_data
    lrs = [0.05, 0.1, 0.2]
    n_iters = 40

    def sweep(fuse_steps):
        system = PimSystem(PimConfig(n_cores=CORES))
        sched = PimScheduler(system, rank_size=CORES)
        snap = system.stats.snapshot()
        hs = sched.sweep("linreg", (X, y), {"lr": lrs}, version="int32",
                         n_iters=n_iters, fuse_steps=fuse_steps,
                         n_cores=CORES, fused=True)
        sched.drain()
        assert all(h.state is JobState.DONE and h.fused for h in hs)
        return hs, system.stats.delta(snap)

    serial, _ = sweep(1)
    chunked, d = sweep(8)
    # K lanes x 8 steps per launch: 5 launches for the 40-iter sweep
    assert d.kernel_launches == n_iters // 8
    for hs, hc in zip(serial, chunked):
        assert np.array_equal(hs.result.attributes["coef_"],
                              hc.result.attributes["coef_"])
        assert hs.result.attributes["intercept_"] \
            == hc.result.attributes["intercept_"]


def test_chunked_gang_lane_cancel(lin_data):
    """Cancelling a lane between chunks rebuilds the device carry with
    the new active mask: the cancelled lane freezes, survivors finish
    bit-identical to their solo fused fits."""
    from repro.api import get_workload
    from repro.sched.gang import FusedGdSweep
    X, y = lin_data
    wl = get_workload("linreg")
    system = PimSystem(PimConfig(n_cores=CORES))
    ds = system.put(X, y)
    lrs = [0.05, 0.1, 0.2]
    specs = [wl.spec("int32", lr=lr, n_iters=24, fuse_steps=8)
             for lr in lrs]
    gang = FusedGdSweep(wl, specs, ds)
    gang.step()                          # chunk 1 (iters 1-8)
    gang.deactivate(1)
    frozen = gang.w[1].copy()
    while not gang.step():
        pass
    assert gang.result(1) is None
    assert np.array_equal(gang.w[1], frozen)     # froze at cancellation
    for lane in (0, 2):
        solo = linreg.fit(ds, linreg.GdConfig(
            version="int32", n_iters=24, lr=lrs[lane], fuse_steps=8))
        r = gang.result(lane)
        assert np.array_equal(r.model.w, solo.w)
        assert r.model.b == solo.b


@pytest.mark.slow
def test_large_k_long_run_bit_identical(lin_data):
    """500 iterations at fuse_steps=64 (tail chunk included) stays bit-
    identical to the serial loop for every integer LIN version."""
    X, y = lin_data
    for ver in ("int32", "hyb"):
        r1, _ = _lin_pair(X, y, ver, fuse=1, n_iters=500)
        rk, _ = _lin_pair(X, y, ver, fuse=64, n_iters=500)
        assert np.array_equal(r1.w, rk.w) and r1.b == rk.b


# ---------------------------------------------------------------------------
# Chunk pipelining (DESIGN.md §14.1): depth only reorders host work.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", (1, 2, 3))
@pytest.mark.parametrize("ver", ("int32", "hyb", "bui"))
def test_lin_pipeline_depth_bit_identical(lin_data, ver, depth):
    """Any in-flight depth must equal the serial dispatch-drain cadence
    bit for bit — weights, bias, AND the recorded history (the drain
    side is where pipelining reorders work)."""
    X, y = lin_data
    ref, _ = _lin_pair(X, y, ver, fuse=8, record_every=8,
                       pipeline_depth=1)
    r, _ = _lin_pair(X, y, ver, fuse=8, record_every=8,
                     pipeline_depth=depth)
    assert np.array_equal(ref.w, r.w)
    assert ref.b == r.b
    assert ref.history == r.history


def test_lin_pipeline_eval_fn_order(lin_data):
    """eval_fn fires once per boundary, in chunk order, with the
    boundary's own dequantized coefficients — regardless of depth."""
    X, y = lin_data
    traces = {}
    for depth in (1, 2):
        trace = []
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(X, y)
        cfg = linreg.GdConfig(version="int32", n_iters=32, fuse_steps=8,
                              record_every=8, pipeline_depth=depth)
        linreg.fit(ds, cfg, eval_fn=lambda w, b, t=trace: (
            t.append((w.tobytes(), b)), 0.0)[1])
        traces[depth] = trace
    assert len(traces[1]) == 4
    assert traces[1] == traces[2]


@pytest.mark.parametrize("ver", ("int32", "int32_lut_mram",
                                 "int32_lut_wram", "hyb_lut", "bui_lut"))
def test_log_pipeline_bit_identical(log_data, ver):
    """Every in-flight depth equals the unfused fit bit for bit —
    weights, bias and recorded history."""
    X, y = log_data
    results = {}
    for fuse, depth in ((1, 1), (8, 1), (8, 2), (8, 3)):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(X, y)
        cfg = logreg.LogRegConfig(version=ver, n_iters=32,
                                  fuse_steps=fuse, record_every=8,
                                  pipeline_depth=depth)
        results[fuse, depth] = logreg.fit(ds, cfg)
    ref = results[1, 1]
    for r in results.values():
        assert np.array_equal(ref.w, r.w)
        assert ref.b == r.b
        assert ref.history == r.history


def test_kmeans_pipeline_bit_identical():
    Xb, _, _ = make_blobs(N, F, centers=4, seed=1)
    results = {}
    for depth in (1, 2):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(Xb)
        # tol=0 runs Lloyd's to max_iters so every chunk executes
        cfg = kmeans.KMeansConfig(k=4, max_iters=12, tol=0.0, seed=3,
                                  fuse_steps=4, pipeline_depth=depth)
        results[depth] = kmeans.fit(ds, cfg, return_labels=False)
    assert np.array_equal(results[1].centroids, results[2].centroids)
    assert results[1].inertia == results[2].inertia
    assert results[1].n_iters == results[2].n_iters


def test_kmeans_pipeline_early_convergence():
    """The done-latch must discard speculative in-flight chunks: a run
    that converges mid-pipeline stops at the same iteration as the
    serial cadence."""
    Xb, _, _ = make_blobs(N, F, centers=4, seed=1)
    results = {}
    for depth in (1, 3):
        pim = PimSystem(PimConfig(n_cores=CORES))
        ds = pim.put(Xb)
        cfg = kmeans.KMeansConfig(k=4, max_iters=40, tol=1e-4, seed=3,
                                  fuse_steps=2, pipeline_depth=depth)
        results[depth] = kmeans.fit(ds, cfg, return_labels=False)
    assert results[1].n_iters == results[3].n_iters < 40
    assert np.array_equal(results[1].centroids, results[3].centroids)


def test_minibatch_pipeline_bit_identical(lin_data):
    """Pipelined dispatch pre-draws each chunk's batch offsets eagerly;
    the rng stream consumption must still match the serial cadence."""
    X, y = lin_data
    ref, _ = _lin_pair(X, y, "int32", fuse=4, n_iters=32, minibatch=32,
                       record_every=4, pipeline_depth=1)
    r, _ = _lin_pair(X, y, "int32", fuse=4, n_iters=32, minibatch=32,
                     record_every=4, pipeline_depth=2)
    assert np.array_equal(ref.w, r.w)
    assert ref.b == r.b
    assert ref.history == r.history


def test_scheduler_gang_pipeline_bit_identical(lin_data):
    """Two fused jobs gang-stepped by the scheduler with depth-2
    pipelines match their solo depth-1 fits."""
    X, y = lin_data
    sched = PimScheduler(PimSystem(PimConfig(n_cores=CORES)), rank_size=4)
    handles = [sched.submit("linreg", (X, y), version="int32",
                            n_cores=4, lr=lr, n_iters=24, fuse_steps=8,
                            pipeline_depth=2)
               for lr in (0.05, 0.2)]
    sched.drain()
    for h, lr in zip(handles, (0.05, 0.2)):
        assert h.state is JobState.DONE
        pim = PimSystem(PimConfig(n_cores=4))
        solo = linreg.fit(pim.put(X, y), linreg.GdConfig(
            version="int32", lr=lr, n_iters=24, fuse_steps=8,
            pipeline_depth=1))
        assert np.array_equal(np.asarray(h.result.model.w), solo.w)
        assert float(h.result.model.b) == solo.b


def test_preempt_resume_mid_pipeline_bit_identical(gd):
    """Preemption at a chunk boundary while chunks are in flight:
    the snapshot is drain-authoritative, and resuming on a fresh
    scheduler completes bit-identically to an uninterrupted fit."""
    name, mod, cfg_cls, ver, (X, y) = gd
    params = dict(version=ver, n_iters=32, fuse_steps=4,
                  pipeline_depth=2)
    pim = PimSystem(PimConfig(n_cores=4))
    ref = mod.fit(pim.put(X, y), cfg_cls(**params))

    sched = PimScheduler(PimSystem(PimConfig(n_cores=CORES)), rank_size=4)
    h = sched.submit(name, (X, y), n_cores=4, **params)
    sched.step(); sched.step()
    h.preempt()
    sched.step()
    assert h.state is JobState.PREEMPTED
    assert 0 < h.iters < 32
    assert h.iters % 4 == 0            # snapshot on a chunk boundary

    s2 = PimScheduler(PimSystem(PimConfig(n_cores=CORES)), rank_size=4)
    s2.resume(h, data=(X, y))
    s2.drain()
    assert h.state is JobState.DONE and h.iters == 32
    assert np.array_equal(np.asarray(h.result.model.w), ref.w)
    assert float(h.result.model.b) == ref.b
