"""Async training service (DESIGN.md §14): background serve loop,
SLO-aware admission, deadline scheduling, and the manifest spool.

Covers the serve/shutdown lifecycle (no lost jobs), queue/completion
latency accounting, submit- and manifest-level ``max_modeled_seconds``
admission (FAILED handle / SloViolation — never a crash), deadline
(EDF) queue ordering and eviction, and mid-flight manifest admission
through ``serve_manifests``; the Poisson soak and the end-to-end
``pim_jobs --serve`` CLI run are marked ``slow``.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.api import PimConfig, PimSystem
from repro.data.synthetic import make_linear_dataset
from repro.sched import (JobState, PimScheduler, SloViolation,
                         serve_manifests, submit_manifest)

N, F = 192, 6


@pytest.fixture(scope="module")
def lin_data():
    X, y, _ = make_linear_dataset(N, F, seed=0)
    return X, y


def _sched(cores=8, rank=4, **kw):
    return PimScheduler(PimSystem(PimConfig(n_cores=cores)),
                        rank_size=rank, **kw)


def _spool_write(path, doc):
    """Write a manifest whole under a name the spool watcher skips, then
    rename it: a scan never reads a half-written manifest."""
    part = path.with_name(path.name + ".part")
    part.write_text(json.dumps(doc))
    os.replace(part, path)


def _manifest_doc(n_iters=20, name="job", cores=4):
    return {
        "system": {"cores": 8, "rank_size": 4},
        "datasets": {"lin": {"kind": "linear", "samples": N,
                             "features": F, "seed": 0}},
        "jobs": [
            {"workload": "linreg", "dataset": "lin", "cores": cores,
             "version": "int32", "name": name,
             "params": {"n_iters": n_iters, "fuse_steps": 5}},
        ],
    }


# ---------------------------------------------------------------------------
# Serve lifecycle: background drain, wait, shutdown without job loss.
# ---------------------------------------------------------------------------

def test_serve_lifecycle_and_latency(lin_data):
    X, y = lin_data
    s = _sched()
    assert not s.serving and s.idle
    s.serve(poll_interval=0.005)
    assert s.serving
    with pytest.raises(RuntimeError):
        s.serve()                      # one drain loop per scheduler
    handles = [s.submit("linreg", (X, y), version="int32", n_cores=4,
                        n_iters=20, fuse_steps=5, name=f"j{i}")
               for i in range(3)]
    assert s.wait(handles, timeout=60.0)
    assert all(h.state is JobState.DONE for h in handles)
    for h in handles:
        assert h.queue_latency is not None and h.queue_latency >= 0.0
        assert h.completion_latency >= h.queue_latency
        m = h.metrics()
        assert m["queue_latency"] == h.queue_latency
        assert m["deadline_missed"] is False
    lat = s.latency_summary()
    assert lat["completion"]["count"] == 3
    assert lat["queue"]["p50"] <= lat["queue"]["p99"]
    stats = s.stats()
    assert stats["serving"] and stats["latency"]["completion"]["count"] == 3
    s.shutdown(wait=True)
    assert not s.serving and s.idle


def test_shutdown_drains_submitted_jobs(lin_data):
    """shutdown(wait=True) is a drain barrier: every job submitted
    before the call reaches a terminal state — none lost."""
    X, y = lin_data
    s = _sched()
    s.serve(poll_interval=0.005)
    handles = [s.submit("linreg", (X, y), version="int32", n_cores=4,
                        n_iters=15, fuse_steps=5) for _ in range(4)]
    s.shutdown(wait=True)
    assert all(h.state is JobState.DONE for h in handles)
    assert s.idle and not s.serving
    # shutdown is idempotent; serve can restart after a clean stop
    s.shutdown(wait=True)
    s.serve(poll_interval=0.005)
    h = s.submit("linreg", (X, y), version="int32", n_cores=4,
                 n_iters=10, fuse_steps=5)
    assert s.wait([h], timeout=60.0) and h.state is JobState.DONE
    s.shutdown(wait=True)


# ---------------------------------------------------------------------------
# SLO admission: the cost model answers before anything runs.
# ---------------------------------------------------------------------------

def test_submit_slo_rejection_is_failed_not_crash(lin_data):
    X, y = lin_data
    s = _sched()
    h = s.submit("linreg", (X, y), version="int32", n_cores=4,
                 n_iters=400, max_modeled_seconds=1e-12)
    assert h.state is JobState.FAILED
    assert isinstance(h.error, SloViolation)
    assert "max_modeled_seconds" in str(h.error)
    assert s.idle                       # never queued
    assert s.metrics.counter("sched.slo_rejections").value == 1
    # a permissive bound on the same scheduler still admits
    ok = s.submit("linreg", (X, y), version="int32", n_cores=4,
                  n_iters=10, fuse_steps=5, max_modeled_seconds=1e9)
    s.drain()
    assert ok.state is JobState.DONE


def test_scheduler_default_slo_bound(lin_data):
    X, y = lin_data
    s = _sched(max_modeled_seconds=1e-12)
    h = s.submit("linreg", (X, y), version="int32", n_cores=4, n_iters=50)
    assert h.state is JobState.FAILED and isinstance(h.error, SloViolation)
    # per-submit bound overrides the scheduler default
    ok = s.submit("linreg", (X, y), version="int32", n_cores=4,
                  n_iters=10, fuse_steps=5, max_modeled_seconds=1e9)
    s.drain()
    assert ok.state is JobState.DONE


def test_manifest_slo_rejected_whole(lin_data):
    s = _sched()
    doc = _manifest_doc(n_iters=200)
    doc["slo"] = {"max_modeled_seconds": 1e-12}
    with pytest.raises(SloViolation, match="makespan lower bound"):
        submit_manifest(s, doc)
    assert s.idle                       # nothing queued
    assert s.metrics.counter("sched.manifest_slo_rejections").value == 1
    # without the slo section the same manifest is admitted
    del doc["slo"]
    handles = submit_manifest(s, doc)
    s.drain()
    assert all(h.state is JobState.DONE for h in handles)


# ---------------------------------------------------------------------------
# Deadline (EDF) policy: ordering and eviction.
# ---------------------------------------------------------------------------

def test_deadline_policy_orders_queue(lin_data):
    X, y = lin_data
    s = _sched(cores=4, rank=4, policy="deadline")   # one job at a time
    kw = dict(version="int32", n_cores=4, n_iters=10, fuse_steps=5)
    a = s.submit("linreg", (X, y), name="no-deadline", **kw)
    b = s.submit("linreg", (X, y), name="late", deadline_seconds=100.0,
                 **kw)
    c = s.submit("linreg", (X, y), name="soon", deadline_seconds=10.0,
                 **kw)
    s.drain()
    assert all(h.state is JobState.DONE for h in (a, b, c))
    # earliest deadline first; deadline-less jobs run last
    assert c.started_at < b.started_at < a.started_at


def test_deadline_outranks_evicts_at_chunk_boundary(lin_data):
    X, y = lin_data
    s = _sched(cores=4, rank=4, policy="deadline", preemptive=True)
    kw = dict(version="int32", n_cores=4, n_iters=40, fuse_steps=4)
    victim = s.submit("linreg", (X, y), name="no-deadline", **kw)
    s.step()
    assert victim.state is JobState.RUNNING
    urgent = s.submit("linreg", (X, y), name="urgent",
                      deadline_seconds=5.0, **kw)
    s.step()
    # evicted at the chunk boundary, back in the queue behind the
    # deadline job, holding its boundary snapshot
    assert victim.preemptions == 1
    assert victim.state is JobState.QUEUED
    assert urgent.state is JobState.RUNNING
    s.drain()
    assert urgent.state is JobState.DONE
    assert urgent.deadline_missed is False
    assert victim.state is JobState.DONE and victim.iters == 40
    assert urgent.finished_at < victim.finished_at


def test_fifo_policy_ignores_deadline_ordering(lin_data):
    X, y = lin_data
    s = _sched(cores=4, rank=4)        # fifo default
    kw = dict(version="int32", n_cores=4, n_iters=10, fuse_steps=5)
    a = s.submit("linreg", (X, y), **kw)
    b = s.submit("linreg", (X, y), deadline_seconds=1e-3, **kw)
    s.drain()
    assert a.started_at < b.started_at
    assert b.deadline_missed or b.completion_latency >= 0.0


def test_bad_policy_rejected():
    with pytest.raises(ValueError, match="policy"):
        _sched(policy="lifo")


# ---------------------------------------------------------------------------
# Manifest spool: mid-flight admission with sidecar verdicts.
# ---------------------------------------------------------------------------

def test_serve_manifests_mid_flight(tmp_path, lin_data):
    s = _sched()
    handles = submit_manifest(s, _manifest_doc(n_iters=30, name="first"))
    spool = tmp_path / "spool"
    spool.mkdir()
    (spool / "m1.json").write_text(
        json.dumps(_manifest_doc(n_iters=20, name="second")))

    def drop_late():
        time.sleep(0.3)
        _spool_write(spool / "m2.json",
                     _manifest_doc(n_iters=10, name="third"))

    t = threading.Thread(target=drop_late)
    t.start()
    records = serve_manifests(s, str(spool), poll_interval=0.02,
                              idle_timeout=1.0, handles=handles)
    t.join()
    s.shutdown(wait=True)
    assert [r["state"] for r in records] == ["accepted", "accepted"]
    assert len(handles) == 3
    assert all(h.state is JobState.DONE for h in handles)
    # sidecar verdicts: durable, and not re-scanned as manifests
    for name in ("m1.json", "m2.json"):
        sidecar = json.loads((spool / (name + ".status.json")).read_text())
        assert sidecar["state"] == "accepted" and sidecar["jobs"] == 1


def test_serve_manifests_rejects_bad_and_slo_manifests(tmp_path, lin_data):
    s = _sched()
    spool = tmp_path / "spool"
    spool.mkdir()
    ok = _manifest_doc(n_iters=10, name="ok")
    (spool / "a_ok.json").write_text(json.dumps(ok))
    bad = _manifest_doc(name="bad")
    bad["jobs"][0]["dataset"] = "nope"
    (spool / "b_bad.json").write_text(json.dumps(bad))
    slo = _manifest_doc(n_iters=300, name="slo")
    slo["slo"] = {"max_modeled_seconds": 1e-12}
    (spool / "c_slo.json").write_text(json.dumps(slo))
    (spool / "notes.txt").write_text("not a manifest")

    handles = []
    records = serve_manifests(s, str(spool), poll_interval=0.02,
                              idle_timeout=0.8, handles=handles)
    s.shutdown(wait=True)
    by_name = {os.path.basename(r["path"]): r for r in records}
    assert by_name["a_ok.json"]["state"] == "accepted"
    assert by_name["b_bad.json"]["state"] == "rejected"
    assert "unknown dataset" in by_name["b_bad.json"]["reason"]
    assert by_name["c_slo.json"]["state"] == "rejected"
    assert "SloViolation" in by_name["c_slo.json"]["reason"]
    assert "notes.txt" not in by_name
    assert len(handles) == 1 and handles[0].state is JobState.DONE
    # a rejected manifest's sidecar stops it being re-tried next scan
    sidecar = json.loads(
        (spool / "b_bad.json.status.json").read_text())
    assert sidecar["state"] == "rejected"


# ---------------------------------------------------------------------------
# Sustained load + the CLI face (slow tier).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_poisson_soak_no_lost_jobs(lin_data):
    """Poisson arrivals onto a serving scheduler: every job terminal,
    latency accounting complete, no serve-loop errors."""
    X, y = lin_data
    rng = np.random.RandomState(7)
    s = _sched(cores=16, rank=4, policy="deadline")
    s.serve(poll_interval=0.005)
    handles = []
    for i in range(12):
        time.sleep(float(rng.exponential(0.02)))
        handles.append(s.submit(
            "linreg", (X, y), version="int32", n_cores=4,
            n_iters=15, fuse_steps=5, deadline_seconds=30.0,
            name=f"soak{i}"))
    assert s.wait(handles, timeout=120.0)
    s.shutdown(wait=True)
    assert all(h.state is JobState.DONE for h in handles)
    lat = s.latency_summary()
    assert lat["completion"]["count"] == 12
    assert s.metrics.counter("sched.serve_errors").value == 0


@pytest.mark.slow
def test_cli_serve_accepts_manifest_mid_flight(tmp_path, lin_data,
                                               monkeypatch):
    """pim_jobs --serve end to end: initial manifest drains on the
    background thread, a spooled manifest lands mid-flight, both reach
    terminal states, and the JSON report records the spool verdicts."""
    from repro.launch import pim_jobs
    manifest = tmp_path / "initial.json"
    manifest.write_text(json.dumps(_manifest_doc(n_iters=40,
                                                 name="initial")))
    spool = tmp_path / "spool"
    spool.mkdir()
    out = tmp_path / "report.json"

    # the late manifest drops once the initial jobs are admitted and
    # the spool watcher starts — an event, not a guess at the timing
    watching = threading.Event()
    real_serve = pim_jobs.serve_manifests

    def serve_and_signal(*args, **kw):
        watching.set()
        return real_serve(*args, **kw)

    monkeypatch.setattr(pim_jobs, "serve_manifests", serve_and_signal)

    def drop_late():
        if watching.wait(timeout=60):
            _spool_write(spool / "late.json",
                         _manifest_doc(n_iters=10, name="late"))

    dropper = threading.Thread(target=drop_late, daemon=True)
    dropper.start()
    outcome = {}

    def run():
        try:
            outcome["rc"] = pim_jobs.main(
                [str(manifest), "--serve", "--spool", str(spool),
                 "--poll-interval", "0.02", "--idle-timeout", "1.0",
                 "--json", str(out)])
        except BaseException as err:  # noqa: BLE001 — re-raised below
            outcome["error"] = err

    runner = threading.Thread(target=run, daemon=True)
    runner.start()
    runner.join(timeout=120)        # this test's own time limit
    assert not runner.is_alive(), "pim_jobs --serve outlived 120 s"
    dropper.join(timeout=5)
    if "error" in outcome:
        raise outcome["error"]
    assert outcome["rc"] == 0
    report = json.loads(out.read_text())
    assert {j["state"] for j in report["jobs"]} == {"done"}
    assert len(report["jobs"]) == 2
    assert [m["state"] for m in report["manifests"]] == ["accepted"]
    assert report["scheduler"]["latency"]["completion"]["count"] == 2
