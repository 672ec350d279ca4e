"""Multi-tenant PIM job scheduler (repro/sched; DESIGN.md §7).

Covers the allocator invariants, PimSlice scoping, the gang-stepped
queue (lifecycle, priority, failure isolation, per-job transfer
deltas), and — in the ``slow``-marked cases — fused sweeps and large-K
queues.
"""
import json

import numpy as np
import pytest

from repro.api import PimConfig, PimSystem, Workload, make_estimator
from repro.data.synthetic import make_blobs, make_linear_dataset
from repro.sched import (BankAllocator, JobState, PimScheduler, PimSlice,
                         fuse_key, job_report, plan_fusion, run_manifest)
from repro.sched.allocator import BankLease


# ---------------------------------------------------------------------------
# BankAllocator invariants.
# ---------------------------------------------------------------------------

def test_allocator_first_fit_rank_alignment():
    alloc = BankAllocator(64, rank_size=16)
    a = alloc.allocate(10)           # rounds up to one 16-core rank
    b = alloc.allocate(17)           # rounds up to two ranks
    assert (a.start, a.n_cores) == (0, 16)
    assert (b.start, b.n_cores) == (16, 32)
    assert a.start % 16 == 0 and b.start % 16 == 0
    assert alloc.allocate(32) is None     # only 16 cores left
    c = alloc.allocate(None)              # default: one rank
    assert (c.start, c.n_cores) == (48, 16)
    assert alloc.free_cores == 0


def test_allocator_release_coalesces_free_extents():
    alloc = BankAllocator(32, rank_size=8)
    leases = [alloc.allocate(8) for _ in range(4)]
    # free the middle two in reverse order: must coalesce with each other
    alloc.release(leases[2])
    alloc.release(leases[1])
    frag = alloc.fragmentation()
    assert frag.free_cores == 16
    assert frag.n_free_extents == 1
    assert frag.largest_free_extent == 16
    assert frag.external_fragmentation == 0.0
    alloc.release(leases[0])
    alloc.release(leases[3])
    assert alloc.fragmentation().n_free_extents == 1
    assert alloc.free_cores == 32


def test_allocator_fragmentation_visible():
    alloc = BankAllocator(32, rank_size=8)
    leases = [alloc.allocate(8) for _ in range(4)]
    alloc.release(leases[0])
    alloc.release(leases[2])          # two disjoint 8-core holes
    frag = alloc.fragmentation()
    assert frag.free_cores == 16 and frag.n_free_extents == 2
    assert frag.external_fragmentation == pytest.approx(0.5)
    # 16 free cores but no 16-core hole
    assert alloc.allocate(16) is None


def test_allocator_auto_rank_on_awkward_machine_sizes():
    """The default rank clamps to the largest divisor of the machine
    <= UPMEM's 64 — a 96-core scheduler must construct out of the box."""
    from repro.sched import default_rank_size
    assert default_rank_size(96) == 48
    assert default_rank_size(100) == 50
    assert default_rank_size(128) == 64
    assert default_rank_size(7) == 7
    assert BankAllocator(96).rank_size == 48
    sched = PimScheduler(PimSystem(PimConfig(n_cores=96)))
    assert sched.allocator.rank_size == 48


def test_allocator_rejects_bad_requests():
    alloc = BankAllocator(16, rank_size=4)
    with pytest.raises(ValueError):
        alloc.allocate(17)            # larger than the machine
    with pytest.raises(ValueError):
        alloc.allocate(0)
    with pytest.raises(ValueError):
        alloc.release(BankLease(0, 4))  # never granted
    with pytest.raises(ValueError):
        BankAllocator(16, rank_size=5)  # rank must divide cores


# ---------------------------------------------------------------------------
# PimSlice scoping.
# ---------------------------------------------------------------------------

def test_slice_scopes_shards_and_mirrors_stats():
    parent = PimSystem(PimConfig(n_cores=16))
    sl = PimSlice(parent, BankLease(4, 4))
    assert sl.config.n_cores == 4
    xs = sl.shard_rows(np.arange(12, dtype=np.float32))
    assert xs.shape == (4, 3)                      # sliced, not parent, width
    assert sl.stats.cpu_to_pim == xs.nbytes
    assert parent.stats.cpu_to_pim == xs.nbytes    # mirrored increment
    sl.stats.reset()                               # slice-local only
    assert sl.stats.cpu_to_pim == 0
    assert parent.stats.cpu_to_pim == xs.nbytes    # parent keeps cumulative


def test_slice_lease_must_fit_parent():
    parent = PimSystem(PimConfig(n_cores=8))
    with pytest.raises(ValueError):
        PimSlice(parent, BankLease(4, 8))


# ---------------------------------------------------------------------------
# Acceptance (a): disjoint slices == whole-mesh serial, bit for bit.
# ---------------------------------------------------------------------------

def test_disjoint_slices_bit_identical_to_whole_mesh():
    X, y, _ = make_linear_dataset(512, 8, seed=0)
    Xb, _, _ = make_blobs(512, 4, centers=4, seed=1)

    system = PimSystem(PimConfig(n_cores=16))
    sched = PimScheduler(system, rank_size=4)
    h_lin = sched.submit("linreg", (X, y), version="int32", n_iters=15,
                         n_cores=4)
    h_kme = sched.submit("kmeans", Xb, n_clusters=4, max_iter=8,
                         n_cores=8)
    sched.drain()
    assert h_lin.state is JobState.DONE and h_kme.state is JobState.DONE
    # the two jobs really ran concurrently on disjoint extents
    assert h_lin.lease.stop <= h_kme.lease.start \
        or h_kme.lease.stop <= h_lin.lease.start

    ref = PimSystem(PimConfig(n_cores=16))
    ref_lin = make_estimator("linreg", version="int32", n_iters=15,
                             system=ref).fit(ref.put(X, y))
    ref_kme = make_estimator("kmeans", n_clusters=4, max_iter=8,
                             system=ref).fit(Xb)
    # integer GD / integer Lloyd's are partition-invariant: the sliced
    # fits must equal the whole-mesh fits bit for bit
    assert np.array_equal(h_lin.result.attributes["coef_"], ref_lin.coef_)
    assert h_lin.result.attributes["intercept_"] == ref_lin.intercept_
    assert np.array_equal(h_kme.result.attributes["cluster_centers_"],
                          ref_kme.cluster_centers_)
    assert np.array_equal(h_kme.result.attributes["labels_"],
                          ref_kme.labels_)
    # inertia is accumulated in float32 per core (int32 would overflow,
    # see kmeans._inertia_kernel_factory) so it is partition-dependent
    # rounding noise, not part of the bit-exact fit
    assert h_kme.result.attributes["inertia_"] \
        == pytest.approx(ref_kme.inertia_, rel=1e-6)


# ---------------------------------------------------------------------------
# Acceptance (b): mixed queue, per-job deltas, failure isolation.
# ---------------------------------------------------------------------------

def test_mixed_queue_drains_with_per_job_deltas_and_isolation():
    """K=8 mixed LIN/LOG/KME queue; one job forced to raise mid-queue
    leaves the other seven DONE with attributable transfer deltas."""
    X, y, _ = make_linear_dataset(256, 8, seed=0)
    Xb, _, _ = make_blobs(256, 4, centers=4, seed=1)
    n_iters = 12

    system = PimSystem(PimConfig(n_cores=16))
    sched = PimScheduler(system, rank_size=4)
    handles = [
        sched.submit("linreg", (X, y), version="int32", n_iters=n_iters),
        sched.submit("linreg", (X, y), version="hyb", n_iters=n_iters),
        sched.submit("logreg", (X, y), version="int32", n_iters=n_iters),
        sched.submit("kmeans", Xb, n_clusters=4, max_iter=10),
        # forced failure: more clusters than points raises inside fit
        sched.submit("kmeans", Xb[:3], n_clusters=8, name="poison"),
        sched.submit("logreg", (X, y), version="int32_lut_wram",
                     n_iters=n_iters),
        sched.submit("linreg", (X, y), version="fp32", n_iters=n_iters),
        sched.submit("kmeans", Xb, n_clusters=4, max_iter=10, seed=7),
    ]
    assert len(handles) == 8
    sched.drain()

    poison = handles[4]
    assert poison.state is JobState.FAILED
    assert isinstance(poison.error, ValueError)
    others = [h for h in handles if h is not poison]
    assert all(h.state is JobState.DONE for h in others)

    # per-job transfer deltas are attributable and correct even though
    # the jobs interleaved on one system:
    for h in handles[:3] + [handles[5], handles[6]]:     # LIN/LOG jobs
        assert h.transfer.kernel_launches == n_iters     # 1 per GD step
        assert h.transfer.shard_transfers == 2           # X and y views
    for h in (handles[3], handles[7]):                   # KME jobs
        # one launch per Lloyd step + inertia + labels passes
        assert h.transfer.kernel_launches == h.steps + 2
        assert h.transfer.shard_transfers == 1           # X view only
    # slice deltas partition the parent's mirrored global counters
    assert sum(h.transfer.cpu_to_pim for h in handles) \
        == system.stats.cpu_to_pim
    assert sum(h.transfer.kernel_launches for h in handles) \
        == system.stats.kernel_launches
    # DPU cycle accounting accumulated per gang step
    assert all(h.modeled_seconds > 0 for h in others)
    # every lease was reclaimed
    frag = sched.fragmentation()
    assert frag.free_cores == 16 and frag.n_free_extents == 1


def test_gang_round_robin_interleaves_concurrent_jobs():
    X, y, _ = make_linear_dataset(256, 4, seed=0)
    system = PimSystem(PimConfig(n_cores=8))
    sched = PimScheduler(system, rank_size=4)
    a = sched.submit("linreg", (X, y), version="int32", n_iters=6)
    b = sched.submit("linreg", (X, y), version="int32", n_iters=6)
    sched.step()
    # both fit on the machine, so one turn admits AND advances both
    assert a.state is JobState.RUNNING and b.state is JobState.RUNNING
    assert a.steps == 1 and b.steps == 1
    sched.drain()
    assert a.state is JobState.DONE and b.state is JobState.DONE
    assert np.array_equal(a.result.attributes["coef_"],
                          b.result.attributes["coef_"])


def test_priority_admission_order():
    X, y, _ = make_linear_dataset(128, 4, seed=0)
    system = PimSystem(PimConfig(n_cores=4))     # room for ONE job
    sched = PimScheduler(system, rank_size=4)
    low = sched.submit("linreg", (X, y), version="int32", n_iters=4,
                       priority=0)
    high = sched.submit("linreg", (X, y), version="int32", n_iters=4,
                        priority=5)
    sched.step()
    assert high.state is JobState.RUNNING        # jumped the FIFO head
    assert low.state is JobState.QUEUED
    sched.drain()
    assert low.state is JobState.DONE and high.state is JobState.DONE


def test_cancel_queued_and_running():
    X, y, _ = make_linear_dataset(128, 4, seed=0)
    system = PimSystem(PimConfig(n_cores=4))
    sched = PimScheduler(system, rank_size=4)
    running = sched.submit("linreg", (X, y), version="int32", n_iters=50)
    queued = sched.submit("linreg", (X, y), version="int32", n_iters=50)
    sched.step()
    queued.cancel()
    assert queued.state is JobState.CANCELLED
    running.cancel()
    sched.drain()
    assert running.state is JobState.CANCELLED
    assert running.steps < 50                    # stopped at a boundary
    assert sched.fragmentation().free_cores == 4


def test_unschedulable_job_rejected_at_submit():
    X, y, _ = make_linear_dataset(64, 4, seed=0)
    sched = PimScheduler(PimSystem(PimConfig(n_cores=8)), rank_size=4)
    with pytest.raises(ValueError):
        sched.submit("linreg", (X, y), version="int32", n_cores=12)


def test_custom_workload_default_macro_step():
    """Any registered-protocol workload schedules via the base
    fit_steps default (one macro step)."""

    class OneShot(Workload):
        name = "oneshot"
        versions = ("v0",)
        defaults = {}

        def fit(self, dataset, spec):
            from repro.api import FitResult
            return FitResult(spec, {"n": dataset.n}, {})

    sched = PimScheduler(PimSystem(PimConfig(n_cores=8)), rank_size=4)
    h = sched.submit(OneShot(), np.zeros((16, 2), np.float32))
    sched.drain()
    assert h.state is JobState.DONE
    assert h.steps == 1
    assert h.result.model == {"n": 16}


# ---------------------------------------------------------------------------
# Fusion planning (cheap, fast tier) and fused execution (slow tier).
# ---------------------------------------------------------------------------

def test_fuse_key_eligibility():
    from repro.api import get_workload
    lin = get_workload("linreg")
    kme = get_workload("kmeans")
    s1 = lin.spec("int32", lr=0.1, n_iters=50)
    s2 = lin.spec("int32", lr=0.5, n_iters=50)
    s3 = lin.spec("hyb", lr=0.1, n_iters=50)
    s4 = lin.spec("int32", lr=0.1, n_iters=50, minibatch=8)
    assert fuse_key(lin, s1) == fuse_key(lin, s2)       # lr is lane-local
    assert fuse_key(lin, s1) != fuse_key(lin, s3)       # version differs
    assert fuse_key(lin, s4) is None                    # SGD can't fuse
    assert fuse_key(kme, kme.spec()) is None            # not a GD family
    groups = plan_fusion(lin, [s1, s2, s3, s4])
    assert groups == [[0, 1], [2], [3]]


@pytest.mark.slow
def test_fused_sweep_one_launch_per_step_matches_unfused():
    """Acceptance (c): an 8-point fused GD sweep performs exactly one
    batched kernel launch per step and matches unfused results bit for
    bit."""
    X, y, _ = make_linear_dataset(512, 8, seed=0)
    lrs = [0.02, 0.04, 0.06, 0.08, 0.1, 0.15, 0.2, 0.3]
    n_iters = 25

    system = PimSystem(PimConfig(n_cores=8))
    sched = PimScheduler(system, rank_size=8)
    snap = system.stats.snapshot()
    fused = sched.sweep("linreg", (X, y), {"lr": lrs}, version="int32",
                        n_iters=n_iters, fused=True)
    sched.drain()
    assert all(h.state is JobState.DONE and h.fused for h in fused)
    delta = system.stats.delta(snap)
    # ONE batched launch per step for the whole gang of 8
    assert delta.kernel_launches == n_iters
    assert fused[0].transfer.kernel_launches == n_iters
    # the gang shares one slice and ONE bank-resident dataset
    assert delta.shard_transfers == 2                    # X and y, once

    unfused = sched.sweep("linreg", (X, y), {"lr": lrs}, version="int32",
                          n_iters=n_iters, fused=False)
    sched.drain()
    # 8 independent jobs: 8 launches per step-equivalent, 8 datasets
    assert sum(h.transfer.kernel_launches for h in unfused) \
        == n_iters * len(lrs)
    for hf, hu in zip(fused, unfused):
        assert np.array_equal(hf.result.attributes["coef_"],
                              hu.result.attributes["coef_"])
        assert hf.result.attributes["intercept_"] \
            == hu.result.attributes["intercept_"]


@pytest.mark.slow
def test_fused_sweep_logreg_and_lane_cancel():
    X, y, _ = make_linear_dataset(512, 8, seed=1)
    lrs = [1.0, 2.0, 4.0]
    system = PimSystem(PimConfig(n_cores=8))
    sched = PimScheduler(system, rank_size=8)
    fused = sched.sweep("logreg", (X, y), {"lr": lrs},
                        version="int32_lut_wram", n_iters=20, fused=True)
    sched.step()                        # admit + first gang step
    fused[1].cancel()
    sched.drain()
    assert fused[0].state is JobState.DONE
    assert fused[1].state is JobState.CANCELLED
    assert fused[2].state is JobState.DONE
    ref = sched.sweep("logreg", (X, y), {"lr": [lrs[0]]},
                      version="int32_lut_wram", n_iters=20, fused=False)
    sched.drain()
    assert np.array_equal(fused[0].result.attributes["coef_"],
                          ref[0].result.attributes["coef_"])


def test_fused_logreg_fp32_on_host_uses_exact_sigmoid():
    """A fused LOG fp32 sweep on a HostSystem trains each lane with the
    exact sigmoid its solo fit uses there: the gang's kernel carries the
    exact flavour, and each lane matches its solo fit."""
    from repro.api import get_workload, make_system
    from repro.core import logreg
    from repro.sched.gang import FusedGdSweep
    X, y, _ = make_linear_dataset(256, 6, seed=2)
    ds = make_system("host").put(X, (y > np.median(y)).astype(np.float32))
    wl = get_workload("logreg")
    lrs = [5.0, 20.0]
    gang = FusedGdSweep(wl, [wl.spec("fp32", lr=lr, n_iters=60)
                             for lr in lrs], ds)
    while not gang.step():
        pass
    assert gang.kernel.startswith("sched.fused/K2/log.grad/fp32x/")
    for lane, lr in enumerate(lrs):
        solo = logreg.fit(ds, logreg.LogRegConfig(version="fp32", lr=lr,
                                                  n_iters=60))
        r = gang.result(lane).model
        np.testing.assert_allclose(r.w, solo.w, rtol=1e-5, atol=1e-6)
        assert r.b == pytest.approx(solo.b, rel=1e-5, abs=1e-6)


@pytest.mark.slow
def test_large_k_mixed_queue_with_backfill():
    """K=16 mixed queue on a fragmented machine drains fully; backfill
    keeps the cores busy when the FIFO head is too big."""
    X, y, _ = make_linear_dataset(256, 4, seed=0)
    Xb, _, _ = make_blobs(256, 4, centers=4, seed=2)
    system = PimSystem(PimConfig(n_cores=16))
    sched = PimScheduler(system, rank_size=4, backfill=True)
    handles = []
    for i in range(16):
        if i % 4 == 3:
            handles.append(sched.submit("kmeans", Xb, n_clusters=4,
                                        max_iter=8, n_cores=8))
        else:
            handles.append(sched.submit(
                "linreg", (X, y), version="int32", n_iters=8,
                n_cores=4, priority=i % 3))
    sched.drain()
    assert all(h.state is JobState.DONE for h in handles)
    frag = sched.fragmentation()
    assert frag.free_cores == 16 and frag.n_free_extents == 1


# ---------------------------------------------------------------------------
# Manifest front end.
# ---------------------------------------------------------------------------

def test_manifest_runs_jobs_and_fused_sweep():
    doc = {
        "system": {"cores": 8, "rank_size": 4},
        "datasets": {
            "lin": {"kind": "linear", "samples": 256, "features": 8,
                    "seed": 0},
            "blobs": {"kind": "blobs", "samples": 256, "features": 4,
                      "centers": 4, "seed": 1},
        },
        "jobs": [
            {"workload": "kmeans", "dataset": "blobs", "cores": 4,
             "params": {"n_clusters": 4, "max_iter": 5}},
        ],
        "sweeps": [
            {"workload": "linreg", "dataset": "lin", "version": "int32",
             "cores": 4, "grid": {"lr": [0.05, 0.1]}, "fused": True,
             "params": {"n_iters": 6}},
        ],
    }
    scheduler, handles = run_manifest(doc)
    assert len(handles) == 3
    assert all(h.state is JobState.DONE for h in handles)
    rows = job_report(handles)
    json.dumps(rows)                       # must be serializable
    assert rows[1]["fused"] and rows[2]["fused"]
    assert scheduler.stats()["jobs"]["done"] == 3


def test_manifest_rejects_unknown_dataset():
    doc = {"system": {"cores": 4},
           "jobs": [{"workload": "linreg", "dataset": "nope"}]}
    with pytest.raises(ValueError, match="unknown dataset"):
        run_manifest(doc)


def test_manifest_file_must_be_a_mapping(tmp_path):
    from repro.sched import load_manifest
    p = tmp_path / "bad.json"
    p.write_text('[{"workload": "linreg"}]')   # valid JSON, wrong shape
    with pytest.raises(ValueError, match="must be a mapping"):
        load_manifest(str(p))


def test_fused_zero_iteration_sweep_accounts_nothing():
    """A fused gang that never launches must not charge steps or DPU
    seconds (parity with the unfused path's accounting)."""
    X, y, _ = make_linear_dataset(128, 4, seed=0)
    sched = PimScheduler(PimSystem(PimConfig(n_cores=8)), rank_size=8)
    hs = sched.sweep("linreg", (X, y), {"lr": [0.1, 0.2]},
                     version="int32", n_iters=0, fused=True)
    sched.drain()
    assert all(h.state is JobState.DONE for h in hs)
    assert all(h.steps == 0 and h.modeled_seconds == 0.0 for h in hs)
    assert hs[0].transfer.kernel_launches == 0
