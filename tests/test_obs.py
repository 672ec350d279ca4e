"""Unified telemetry layer (repro/obs; DESIGN.md §13).

Covers the span tracer (overhead contract included), the Chrome
trace-event exporter (schema validity, nesting, determinism under a
seeded manifest), the metrics registry (snapshot/delta + parent
mirroring, per-job attribution across PimSlice/HostSlice/GpuModelSlice),
drift accounting in ``PimScheduler.stats()``, the shared CLI table
formatter, and the run-metadata envelope.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.data.synthetic import make_linear_dataset
from repro.obs import (DRIFT_BUCKETS, TRACER, Column, Counter, Histogram,
                       MetricsRegistry, format_ratio, load_chrome_trace,
                       render_table, run_meta, to_chrome_trace,
                       track_names, validate_chrome_trace, write_json)
from repro.obs.trace import NULL_SPAN, Tracer
from repro.sched import JobState, PimScheduler, run_manifest
from repro.api import make_system


@pytest.fixture
def tracer():
    """The global tracer, enabled and clean, restored afterwards."""
    TRACER.clear()
    TRACER.enable()
    yield TRACER
    TRACER.disable()
    TRACER.clear()


def _small_manifest(n_iters=12):
    return {
        "system": {"cores": 8, "rank_size": 4},
        "datasets": {"lin": {"kind": "linear", "samples": 256,
                             "features": 8, "seed": 0}},
        "jobs": [
            {"workload": "linreg", "dataset": "lin", "cores": 4,
             "version": "int32", "params": {"n_iters": n_iters}},
            {"workload": "logreg", "dataset": "lin", "cores": 4,
             "version": "int32", "params": {"n_iters": n_iters}},
        ],
    }


# ---------------------------------------------------------------------------
# Span tracer.
# ---------------------------------------------------------------------------

def test_tracer_disabled_emits_nothing_and_shares_null_span():
    t = Tracer()
    assert not t.enabled
    span = t.span("x", track="a")
    assert span is NULL_SPAN          # one shared no-op, no allocation
    with span:
        pass
    t.instant("i")
    t.counter("c", 1.0)
    assert len(t) == 0


def test_tracer_records_spans_instants_counters():
    t = Tracer()
    t.enable()
    with t.span("outer", track="target:pim", cat="chunk", job="j0"):
        with t.span("inner", track="target:pim"):
            pass
    t.instant("preempt", track="job:j0", cat="elastic")
    t.counter("channel0.occupancy", 0.5, track="channels:pim")
    events = t.events()
    assert [e["ph"] for e in events] == ["X", "X", "i", "C"]
    # spans append on exit: inner closes before outer
    assert events[0]["name"] == "inner"
    assert events[1]["name"] == "outer"
    assert events[1]["args"] == {"job": "j0"}
    outer, inner = events[1], events[0]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert events[3]["args"] == {"value": 0.5}


def test_span_mirrors_into_the_profiler_only_while_it_records(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    t = Tracer()
    assert not TraceAnnotation.is_enabled()
    assert t.span("repro.step", track="fit") is NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        span = t.span("repro.step", track="fit", k=1)
        assert isinstance(span, TraceAnnotation)
        with span:
            pass
    finally:
        jax.profiler.stop_trace()
    assert len(t) == 0                 # the ring buffer stayed off
    assert t.span("repro.step", track="fit") is NULL_SPAN


def test_tracer_ring_buffer_drops_oldest():
    t = Tracer(capacity=4)
    t.enable()
    for i in range(10):
        t.instant(f"e{i}")
    names = [e["name"] for e in t.events()]
    assert names == ["e6", "e7", "e8", "e9"]


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_snapshot_delta():
    reg = MetricsRegistry()
    c = reg.counter("launches")
    c.inc(3)
    snap = reg.snapshot()
    c.inc(2)
    reg.gauge("occupancy").set(0.75)
    h = reg.histogram("ratio", bounds=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    delta = reg.delta(snap)
    assert delta["launches"] == 2
    assert h.buckets == [1, 1, 1]
    assert h.count == 3 and h.min == 0.5 and h.max == 50.0
    assert h.mean == pytest.approx(55.5 / 3)
    # registry-level dict stays JSON-serializable
    json.dumps(reg.to_dict())


def test_histogram_delta_is_bucketwise():
    h = Histogram(bounds=(1.0, 2.0))
    h.observe(0.5)
    snap = h.snapshot()
    h.observe(1.5)
    h.observe(5.0)
    d = h.delta(snap)
    assert d["count"] == 2 and d["buckets"] == [0, 1, 1]


def test_registry_parent_mirroring():
    parent = MetricsRegistry()
    a, b = MetricsRegistry(parent=parent), MetricsRegistry(parent=parent)
    a.counter("x").inc(3)
    b.counter("x").inc(4)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(2.0)
    assert parent.counter("x").value == 7
    assert parent.histogram("h").count == 2
    # children stay attributable
    assert a.counter("x").value == 3 and b.counter("x").value == 4


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_counter_standalone_parent():
    parent = Counter()
    child = Counter(parent=parent)
    child.inc(5)
    snap = child.snapshot()
    child.inc(2)
    assert child.delta(snap) == 2 and parent.value == 7


# ---------------------------------------------------------------------------
# Thread safety: the serve-mode drain thread increments metrics
# concurrently with caller-thread reads (DESIGN.md §14.2) — mirrored
# increments must never be lost or double-propagated.
# ---------------------------------------------------------------------------

def test_concurrent_mirrored_counter_increments_are_exact():
    import threading

    parent = MetricsRegistry()
    n_threads, n_incs = 8, 2000
    children = [MetricsRegistry(parent=parent) for _ in range(n_threads)]
    # pre-create so every thread races on the SAME counter objects
    for child in children:
        child.counter("sched.steps")

    def work(child):
        c = child.counter("sched.steps")
        h = child.histogram("sched.step_seconds", bounds=(1.0, 10.0))
        for i in range(n_incs):
            c.inc()
            h.observe(float(i % 3))

    threads = [threading.Thread(target=work, args=(c,))
               for c in children]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert parent.counter("sched.steps").value == n_threads * n_incs
    hist = parent.histogram("sched.step_seconds", bounds=(1.0, 10.0))
    assert hist.count == n_threads * n_incs
    assert sum(hist.buckets) == hist.count
    for child in children:
        assert child.counter("sched.steps").value == n_incs


def test_concurrent_registry_lazy_creation_single_instance():
    import threading

    reg = MetricsRegistry()
    out = [None] * 16
    barrier = threading.Barrier(len(out))

    def grab(i):
        barrier.wait()
        out[i] = reg.counter("lazy.race")

    threads = [threading.Thread(target=grab, args=(i,))
               for i in range(len(out))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(c is out[0] for c in out)


def test_concurrent_mirror_stats_increments_are_exact():
    import threading

    from repro.systems.base import TransferStats, _MirrorStats

    parent = TransferStats()
    n_threads, n_incs = 8, 2000
    mirrors = [_MirrorStats(parent) for _ in range(n_threads)]
    stop = threading.Event()

    def bump(m):
        for _ in range(n_incs):
            m.cpu_to_pim += 3
            m.host_syncs += 1

    def read():
        # caller-thread stats() reads must never crash or tear while
        # the drain thread mirrors increments
        while not stop.is_set():
            snap = parent.snapshot()
            assert snap.cpu_to_pim >= 0

    threads = [threading.Thread(target=bump, args=(m,)) for m in mirrors]
    reader = threading.Thread(target=read)
    reader.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    reader.join()
    assert parent.cpu_to_pim == n_threads * n_incs * 3
    assert parent.host_syncs == n_threads * n_incs
    for m in mirrors:
        assert m.cpu_to_pim == n_incs * 3


# ---------------------------------------------------------------------------
# Per-slice attribution: parent totals == sum of per-job deltas in a
# mixed-target queue (PimSlice / HostSlice / GpuModelSlice).
# ---------------------------------------------------------------------------

def test_mixed_target_parent_totals_equal_job_delta_sums():
    X, y, _ = make_linear_dataset(192, 6, seed=0)
    systems = {"pim": make_system("pim", n_cores=8),
               "host": make_system("host", n_cores=4),
               "gpu": make_system("gpu-model", n_cores=4)}
    sched = PimScheduler(systems, rank_size=4)
    handles = []
    for target, version in (("pim", "int32"), ("host", "fp32"),
                            ("gpu", "fp32")):
        handles.append(sched.submit(
            "linreg", (X, y), version=version, n_cores=4,
            target=target, n_iters=10))
        handles.append(sched.submit(
            "logreg", (X, y), version=version, n_cores=4,
            target=target, n_iters=10))
    sched.drain()
    assert all(h.state is JobState.DONE for h in handles)
    for target, system in systems.items():
        jobs = [h for h in handles if h.target == target]
        assert all(h.transfer is not None for h in jobs)
        for field in ("kernel_launches", "cpu_to_pim", "pim_to_cpu",
                      "shard_transfers", "shard_bytes", "dram_bytes"):
            total = getattr(system.stats, field)
            attributed = sum(getattr(h.transfer, field) for h in jobs)
            assert attributed == total, (target, field)
    # the modeled-GPU roofline mirrors per slice the same way
    gpu_jobs = [h for h in handles if h.target == "gpu"]
    assert all(h.gpu is not None for h in gpu_jobs)
    assert sum(h.gpu.launches for h in gpu_jobs) \
        == systems["gpu"].gpu.launches
    assert sum(h.gpu.modeled_seconds for h in gpu_jobs) \
        == pytest.approx(systems["gpu"].gpu.modeled_seconds)


# ---------------------------------------------------------------------------
# Chrome trace export.
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_tracks():
    t = Tracer()
    t.enable()
    with t.span("chunk", track="target:pim"):
        pass
    t.instant("preempt", track="job:j0")
    t.counter("channel0.occupancy", 1.0, track="channels:pim")
    doc = to_chrome_trace(t.events())
    validate_chrome_trace(doc)
    assert track_names(doc) == {"target:pim", "job:j0", "channels:pim"}
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert {e["ph"] for e in body} == {"X", "i", "C"}
    for ev in body:
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
    # groups map to distinct pids, tracks to distinct (pid, tid) rows
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    groups = {e["args"]["name"] for e in meta
              if e["name"] == "process_name"}
    assert groups == {"target", "job", "channels"}


def test_validate_rejects_malformed_docs():
    with pytest.raises(ValueError):
        validate_chrome_trace({})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "a",
                                               "pid": 1, "tid": 1,
                                               "ts": 0.0}]})  # no dur
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "a",
                                               "pid": 1, "tid": "x",
                                               "ts": 0.0, "dur": 1.0}]})
    # overlapping (non-nesting) spans on one row
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0,
         "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5.0,
         "dur": 10.0},
    ]}
    with pytest.raises(ValueError, match="overlaps"):
        validate_chrome_trace(bad)


def test_chrome_trace_roundtrip_and_write(tmp_path, tracer):
    with tracer.span("s", track="a"):
        pass
    path = os.path.join(str(tmp_path), "trace.json")
    from repro.obs import write_chrome_trace
    doc = write_chrome_trace(tracer.events(), path)
    assert load_chrome_trace(path) == doc
    validate_chrome_trace(doc)


def _traced_manifest_signature():
    TRACER.clear()
    TRACER.enable()
    try:
        run_manifest(_small_manifest())
        return [(e["ph"], e["name"], e["track"]) for e in TRACER.events()]
    finally:
        TRACER.disable()
        TRACER.clear()


def test_trace_deterministic_under_seeded_manifest():
    first = _traced_manifest_signature()
    second = _traced_manifest_signature()
    assert first == second
    assert first        # actually traced something
    tracks = {t for _, _, t in first}
    assert "sched" in tracks
    assert any(t.startswith("job:") for t in tracks)
    assert any(t.startswith("channels:") for t in tracks)


def test_scheduler_trace_has_expected_tracks_and_spans(tracer):
    scheduler, handles = run_manifest(_small_manifest())
    assert all(h.state is JobState.DONE for h in handles)
    doc = to_chrome_trace(tracer.events())
    validate_chrome_trace(doc)
    tracks = track_names(doc)
    assert "sched" in tracks and "target:pim" in tracks
    assert "channels:pim" in tracks
    assert any(t.startswith("job:") for t in tracks)
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    names = {e["name"] for e in body}
    assert "chunk" in names and "admit" in names
    assert any(n.startswith("channel") for n in names)   # occupancy
    assert "repro.launch" in names or "repro.chunk" in names  # launches


def test_preempt_resume_instants_in_trace(tracer):
    X, y, _ = make_linear_dataset(256, 8, seed=1)
    sched = PimScheduler(make_system("pim", n_cores=8), rank_size=4)
    h = sched.submit("linreg", (X, y), version="int32", n_cores=4,
                     n_iters=30)
    sched.step()
    sched.step()
    h.preempt()
    sched.step()
    assert h.state is JobState.PREEMPTED
    sched.resume(h)
    sched.drain()
    assert h.state is JobState.DONE
    instants = [e["name"] for e in tracer.events() if e["ph"] == "i"
                and e["track"] == f"job:{h.name}"]
    assert "preempt" in instants and "resume" in instants
    doc = to_chrome_trace(tracer.events())
    validate_chrome_trace(doc)
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert {"preempt", "resume"} <= {e["name"] for e in inst}


# ---------------------------------------------------------------------------
# Drift accounting.
# ---------------------------------------------------------------------------

def test_stats_reports_per_job_drift_ratios():
    scheduler, handles = run_manifest(_small_manifest())
    stats = scheduler.stats()
    json.dumps(stats)                      # whole surface serializes
    drift = stats["drift"]
    assert set(drift) == {h.name for h in handles}
    for h in handles:
        entry = drift[h.name]
        assert entry["ratio"] is not None and entry["ratio"] > 0
        assert entry["chunks"] == h.drift.count > 0
        assert entry["measured_seconds"] == h.measured_seconds > 0
        assert h.drift_ratio == pytest.approx(
            h.measured_seconds / h.modeled_seconds)
    # the scheduler-wide per-chunk histogram saw every priced chunk
    hist = stats["metrics"]["sched.drift_ratio"]
    assert hist["count"] == sum(h.drift.count for h in handles)
    assert list(hist["bounds"]) == list(DRIFT_BUCKETS)
    # JobHandle.metrics() carries the same accounting per job
    m = handles[0].metrics()
    assert m["drift_ratio"] == handles[0].drift_ratio
    assert m["transfer"]["kernel_launches"] > 0


def test_drift_ratio_none_when_model_cannot_price():
    X, y, _ = make_linear_dataset(128, 4, seed=0)
    sched = PimScheduler(make_system("host", n_cores=4), rank_size=4)
    h = sched.submit("linreg", (X, y), version="fp32", n_cores=4,
                     n_iters=5)
    sched.drain()
    assert h.state is JobState.DONE
    assert h.modeled_seconds == 0.0
    assert h.drift_ratio is None           # absence, not a guess
    assert h.measured_seconds > 0.0


# ---------------------------------------------------------------------------
# Overhead contract: tracing disabled must cost <2% of a small
# scheduler sweep makespan.
# ---------------------------------------------------------------------------

def test_disabled_tracer_overhead_under_two_percent():
    assert not TRACER.enabled
    # the untraced baseline: a small scheduled sweep
    t0 = time.perf_counter()
    scheduler, handles = run_manifest(_small_manifest())
    makespan = time.perf_counter() - t0
    assert all(h.state is JobState.DONE for h in handles)
    # how many telemetry call sites would that drain hit when enabled?
    TRACER.clear()
    TRACER.enable()
    try:
        run_manifest(_small_manifest())
        n_sites = len(TRACER)
    finally:
        TRACER.disable()
        TRACER.clear()
    # per-call cost of the disabled fast path (one attribute check)
    n_calls = 50_000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        TRACER.span("x", track="t")
        TRACER.instant("x")
        TRACER.counter("x", 1.0)
    per_site = (time.perf_counter() - t0) / (3 * n_calls)
    # deterministic guard: the disabled overhead the instrumented run
    # pays is (sites hit) x (disabled per-call cost) — far under 2%
    assert n_sites * per_site < 0.02 * makespan, (
        f"{n_sites} sites x {per_site * 1e9:.0f} ns "
        f"vs makespan {makespan:.3f}s")


# ---------------------------------------------------------------------------
# Shared CLI formatter.
# ---------------------------------------------------------------------------

def test_render_table_formats_and_defaults():
    cols = (Column("name", width=6, align="<"),
            Column("x", width=8, spec=".2f"),
            Column("n", width=4, spec="d", default="0"))
    out = render_table([{"name": "alpha", "x": 1.5, "n": 3},
                        {"name": "toolongname", "x": None}],
                       cols, extra=lambda r: r.get("err", ""))
    lines = out.splitlines()
    assert lines[0].split() == ["name", "x", "n"]
    assert lines[1].split() == ["alpha", "1.50", "3"]
    assert lines[2].split() == ["toolon", "-", "0"]   # clipped + defaults
    assert format_ratio(None) == "-"
    assert format_ratio(2.5) == "2.50x"
    assert format_ratio(1234.0) == "1234x"


def test_launch_cli_column_specs_cover_report_rows():
    from repro.launch.compare import COMPARE_COLUMNS
    from repro.launch.pim_jobs import JOB_COLUMNS
    assert {"name", "state", "drift_ratio"} <= {c.key for c in JOB_COLUMNS}
    assert {"workload", "drift_ratio"} <= {c.key for c in COMPARE_COLUMNS}


# ---------------------------------------------------------------------------
# Run-metadata envelope.
# ---------------------------------------------------------------------------

def test_run_meta_fields():
    meta = run_meta()
    assert set(meta) == {"git_sha", "git_dirty", "timestamp",
                         "jax_version", "python", "platform"}
    assert meta["timestamp"].endswith("+00:00")        # UTC ISO-8601
    assert meta["git_sha"] is None or len(meta["git_sha"]) == 40


def test_write_json_stamps_envelope(tmp_path):
    path = os.path.join(str(tmp_path), "out", "bench.json")
    stamped = write_json(path, {"metric": 1.0})
    on_disk = json.load(open(path))
    assert on_disk == stamped
    assert on_disk["metric"] == 1.0
    assert "timestamp" in on_disk["run_meta"]


def test_benchmarks_common_reexports_writer():
    from benchmarks.common import write_json as bench_writer
    assert bench_writer is write_json


# ---------------------------------------------------------------------------
# End-to-end CLI acceptance (slow tier).
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pim_jobs_trace_flag_on_example_manifest(tmp_path):
    from repro.launch.pim_jobs import main
    trace_path = os.path.join(str(tmp_path), "trace.json")
    manifest = os.path.join(os.path.dirname(__file__), os.pardir,
                            "examples", "jobs.yaml")
    try:
        rc = main([manifest, "--trace", trace_path])
    finally:
        TRACER.disable()
        TRACER.clear()
    assert rc == 0
    doc = load_chrome_trace(trace_path)
    validate_chrome_trace(doc)
    tracks = track_names(doc)
    assert "channels:pim" in tracks            # per-channel rows
    assert any(t.startswith("job:") for t in tracks)   # per-job rows
    assert "target:pim" in tracks


@pytest.mark.slow
def test_repro_trace_env_var_exports_on_exit(tmp_path):
    trace_path = os.path.join(str(tmp_path), "env_trace.json")
    env = dict(os.environ,
               REPRO_TRACE=trace_path,
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    code = ("from repro.obs import TRACER\n"
            "assert TRACER.enabled\n"
            "with TRACER.span('s', track='t'):\n"
            "    pass\n")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.join(os.path.dirname(__file__), os.pardir))
    doc = load_chrome_trace(trace_path)
    validate_chrome_trace(doc)
    assert track_names(doc) == {"t"}


# ---------------------------------------------------------------------------
# The fit path's spans in the profiler's trace, and the read counter.
# ---------------------------------------------------------------------------

def _log_dataset():
    X, y, _ = make_linear_dataset(512, 6, seed=0)
    y = (y > np.median(y)).astype(np.float32)
    return make_system("pim", n_cores=8).put(X, y)


def _log_fit(ds, n_iters, fuse_steps=1):
    from repro.api import make_estimator
    return make_estimator("logreg", version="int32_lut_wram",
                          system=ds.system, n_iters=n_iters,
                          fuse_steps=fuse_steps).fit(ds)


def _inside(outer, inner):
    return [[(s, e) for s, e in inner if s0 <= s and e <= e0]
            for s0, e0 in outer]


def test_profiler_trace_holds_the_fit_path_spans(tmp_path):
    import jax
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import trace as xtrace
    n_iters = 4
    ds = _log_dataset()
    _log_fit(ds, n_iters)                  # compiles outside the trace
    assert not TRACER.enabled
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            _log_fit(ds, n_iters)
    finally:
        jax.profiler.stop_trace()
    tr = xtrace.load(str(tmp_path), "bench.window")
    hs, he, hn = tr.host

    def spans(name):
        return sorted(zip(hs[hn == name].tolist(), he[hn == name].tolist()))
    fits, steps = spans("repro.fit"), spans("repro.step")
    launches, reads = spans("repro.launch"), spans("repro.read")
    assert len(fits) == 1 and len(steps) == n_iters + 1
    assert _inside(fits, steps) == [steps]
    # one launch a step, each step but the last reading the broadcast
    # state back; the last step reads the result
    assert [len(x) for x in _inside(steps, launches)] == [1] * n_iters + [0]
    assert [len(x) for x in _inside(steps, reads)] == [1] * (n_iters + 1)
    assert len(launches) == n_iters and len(reads) == n_iters + 1
    assert not len(TRACER)                 # the ring buffer stayed off


@pytest.mark.parametrize("n_iters", [4, 8])
def test_device_reads_count_the_reads_that_block(n_iters):
    ds = _log_dataset()
    before = ds.system.stats.snapshot()
    _log_fit(ds, n_iters)
    serial = ds.system.stats.delta(before)
    # the broadcast's byte count each iteration, and the result
    assert serial.device_reads == n_iters + 1
    assert serial.host_syncs == n_iters
    before = ds.system.stats.snapshot()
    _log_fit(ds, n_iters, fuse_steps=n_iters)
    fused = ds.system.stats.delta(before)
    assert fused.device_reads == 1 and fused.host_syncs == 1


@pytest.mark.parametrize("n_init,fuse_steps", [(1, 1), (3, 1), (2, 3)])
def test_kmeans_trace_has_one_init_and_one_finish_per_restart(
        tmp_path, n_init, fuse_steps):
    import jax
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import trace as xtrace
    from repro.api import make_estimator
    X = np.random.default_rng(0).normal(size=(512, 4)).astype(np.float32)
    ds = make_system("pim", n_cores=8).put(X)

    def fit():
        return make_estimator("kmeans", version="int16", system=ds.system,
                              n_clusters=3, max_iter=4, tol=0.0,
                              n_init=n_init, fuse_steps=fuse_steps).fit(ds)
    fit()                                  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            fit()
    finally:
        jax.profiler.stop_trace()
    tr = xtrace.load(str(tmp_path), "bench.window")
    hs, he, hn = tr.host

    def spans(name):
        return sorted(zip(hs[hn == name].tolist(), he[hn == name].tolist()))
    inits, finishes = spans("repro.init"), spans("repro.finish")
    steps = spans("repro.step")
    assert len(inits) == n_init and len(finishes) == n_init
    # each inside one trainer step, the draw before its restart's passes
    assert sum(len(x) for x in _inside(steps, inits + finishes)) \
        == 2 * n_init
    assert all(i[1] <= f[0] for i, f in zip(inits, finishes))
    # the inertia read, then the labels' read where the restart is the
    # best so far (always the first)
    reads = [len(x) for x in _inside(finishes, spans("repro.read"))]
    assert reads[0] == 2 and set(reads) <= {1, 2}
