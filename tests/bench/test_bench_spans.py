"""bench/spans.py and the readers of the program's spans and read
counter: ``host_step_s``, ``step_idle_s``, ``device_reads_per_fit``.
CPU only, on a synthetic trace."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, spans, trace  # noqa: E402

# times in us (x 1e6 ps): the window 0-100; two steps, 10-40 and 50-90,
# with a read nested in each (30-38, 60-85) and a launch in the second;
# the device busy 0-12, 35-52 and 56-88, so idle 12-35, 52-56, 88-100
HOST = [("bench.window", 0, 100), ("repro.step", 10, 40),
        ("repro.read", 30, 38), ("repro.step", 50, 90),
        ("repro.launch", 52, 55), ("repro.read", 60, 85)]
BUSY = [(0, 12), (35, 52), (56, 88)]


def _proto(host, busy):
    names = sorted({n for n, _, _ in host})
    meta = {n: i + 1 for i, n in enumerate(names)}
    dev = "".join(f"events {{ metadata_id: 1 offset_ps: {s * 10**6} "
                  f"duration_ps: {(e - s) * 10**6} }}\n" for s, e in busy)
    hst = "".join(f"events {{ metadata_id: {meta[n]} offset_ps: {s * 10**6} "
                  f"duration_ps: {(e - s) * 10**6} }}\n" for n, s, e in host)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in meta.items())
    device = (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
              f'name: "XLA Ops" timestamp_ns: 0 {dev} }} event_metadata {{ '
              f'key: 1 value {{ id: 1 name: "fusion.1" }} }} }}\n'
              if busy else "")
    return (device + f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 '
            f'name: "python3" timestamp_ns: 0 {hst} }} {hmeta} }}')


def _run(host=HOST, busy=BUSY, traced_fits=1, stats=None):
    data = jax.profiler.ProfileData.from_text_proto(_proto(host, busy))
    tr = trace.from_profile(data, harness.WINDOW_SPAN)
    return harness.Run(harness.resolve_cell("log_lut_susy.serial"), seed=1,
                       trace=tr, traced_fits=traced_fits, fits=2,
                       stats=stats or {})


def _read(metric, run):
    return harness.load_plugin("metrics", metric).read(run)


def test_merge_clips_and_unites():
    got = spans.merge([5, 0, 30, 95], [20, 10, 40, 120], (2, 100))
    assert got.tolist() == [[2, 20], [30, 40], [95, 100]]
    assert spans.merge([], [], (0, 1)).shape == (0, 2)


def test_overlap_of_interval_sets():
    a = np.array([[0, 10], [20, 30]])
    b = np.array([[5, 25], [28, 40]])
    assert spans.overlap_ns(a, b) == 5 + 5 + 2
    assert spans.overlap_ns(a, b) == spans.overlap_ns(b, a)
    assert spans.overlap_ns(a, np.zeros((0, 2), np.int64)) == 0
    assert spans.total_ns(a) == 20


def test_spans_and_idle_on_the_synthetic_trace():
    tr = _run().trace
    assert spans.spans(tr, "repro.step").tolist() == [[10_000, 40_000],
                                                      [50_000, 90_000]]
    assert spans.idle(tr, 0).tolist() == [[12_000, 35_000],
                                          [52_000, 56_000],
                                          [88_000, 100_000]]


@pytest.mark.parametrize("traced_fits", [1, 2])
def test_host_step_s_is_step_time_less_nested_reads(traced_fits):
    # steps 30 + 40 us, reads inside them 8 + 25 us
    got = _read("host_step_s", _run(traced_fits=traced_fits))
    assert got == pytest.approx((70 - 33) * 1e-6 / traced_fits)


@pytest.mark.parametrize("traced_fits", [1, 2])
def test_step_idle_s_is_idle_clipped_to_steps(traced_fits):
    # 12-35 in 10-40: 23; 52-56 in 50-90: 4; 88-100 in 50-90: 2;
    # the window's idle outside the steps (0 and 8 us more) is left out
    got = _read("step_idle_s", _run(traced_fits=traced_fits))
    assert got == pytest.approx((23 + 4 + 2) * 1e-6 / traced_fits)


@pytest.mark.parametrize("metric", ["host_step_s", "step_idle_s"])
def test_span_readers_give_none_without_step_spans(metric):
    no_steps = [h for h in HOST if h[0] != "repro.step"]
    assert _read(metric, _run(host=no_steps)) is None
    assert _read(metric, _run(traced_fits=0)) is None
    untraced = _run()
    untraced.trace = None
    assert _read(metric, untraced) is None


def test_step_idle_s_none_without_a_device():
    assert _read("step_idle_s", _run(busy=[])) is None
    assert _read("host_step_s", _run(busy=[])) == pytest.approx(37e-6)


def test_device_reads_per_fit_reads_the_counter():
    assert _read("device_reads_per_fit",
                 _run(stats={"device_reads": 202})) == 101
    # a program without the counter: the metric is left out
    assert _read("device_reads_per_fit",
                 _run(stats={"host_syncs": 200})) is None
