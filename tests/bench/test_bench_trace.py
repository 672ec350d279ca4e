"""bench/trace.py: the reduction from a profiler trace to busy time,
idle gaps, kernel time and the breakdown.  CPU only."""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

# a device with three ops (two overlapping) and a host whose window span
# holds one nested activity; times in ps, offsets from the line's start
SYNTHETIC = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fx_matvec.3" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "host_update" } } }
'''


@pytest.fixture(scope="module")
def synthetic():
    data = jax.profiler.ProfileData.from_text_proto(SYNTHETIC)
    return trace.from_profile(data, harness.WINDOW_SPAN)


def test_synthetic_busy_union_and_window(synthetic):
    assert synthetic.window == (0, 20000)
    assert synthetic.window_s == pytest.approx(20e-6)
    # [1000, 3000] and [2000, 5000] merge; [11000, 12000] stands alone
    assert synthetic.busy_s() == pytest.approx(5e-6)


def test_synthetic_idle_gaps_are_named_by_the_host(synthetic):
    assert synthetic.idle_gaps() == [(0, 1000, "bench.window"),
                                     (5000, 11000, "host_update"),
                                     (12000, 20000, "bench.window")]


def test_synthetic_kernel_time_and_breakdown(synthetic):
    assert synthetic.kernel("fx_matvec") == (2, pytest.approx(3e-6))
    b = synthetic.breakdown()
    assert sorted(b["device_ops"]) == [["fusion.1", pytest.approx(3e-6)],
                                       ["fx_matvec.3", pytest.approx(3e-6)]]
    assert b["idle_gaps"] == [["bench.window", pytest.approx(9e-6)],
                              ["host_update", pytest.approx(6e-6)]]


# -- a real trace: one fused LOG fit on a TPU v5 lite (bench/testdata) ------

REAL = harness.BENCH / "testdata" / "log_lut_susy.fused.xplane.pb.gz"


@pytest.fixture(scope="module")
def real():
    return trace.load(str(REAL), harness.WINDOW_SPAN)


def _plain_union_ns(intervals, w0, w1):
    """Busy nanoseconds by a plain sweep over sorted, clipped intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, w0), min(e, w1)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            total += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (0 if cur_e is None else cur_e - cur_s)


def test_real_trace_window_busy_and_idle_share(real):
    assert real.window_s == pytest.approx(1.491680654, abs=1e-9)
    assert real.busy_s() == pytest.approx(1.485793931, abs=1e-9)
    starts, ends, _ = real.device_ops[0]
    assert len(starts) == 4604
    plain = _plain_union_ns(zip(starts.tolist(), ends.tolist()), *real.window)
    assert real.busy_s() == pytest.approx(plain / 1e9, abs=1e-9)
    run = harness.Run(harness.resolve_cell("log_lut_susy.fused"), seed=1,
                      trace=real)
    idle = harness.load_plugin("metrics", "device_idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - 1.485793931 / 1.491680654))


def test_real_trace_idle_gaps_add_up_and_are_named(real):
    gaps = real.idle_gaps()
    assert len(gaps) == 4
    assert sum(g1 - g0 for g0, g1, _ in gaps) == pytest.approx(
        (real.window_s - real.busy_s()) * 1e9, abs=2)
    top = real.breakdown()["idle_gaps"]
    assert top[0] == ["$array.py:631 _value", pytest.approx(0.003755257)]


def test_real_trace_kernel_time_and_roofline_share(real):
    # events are matched by operation name, not by operands that name it
    assert real.kernel("fx_matvec") == (100, pytest.approx(0.160173617))
    assert real.kernel("lut_sigmoid") == (100, pytest.approx(1.107167874))
    assert real.kernel("kmeans_assign") == (0, 0.0)
    run = harness.Run(harness.resolve_cell("log_lut_susy.fused"), seed=1,
                      peaks=harness.peaks_for("TPU v5 lite"), trace=real,
                      traced_fits=1)
    n = 5_000_000
    assert run.kernel_roofline("fx_matvec") == pytest.approx(
        100 * 100 * (n * 18 * 4 + n * 4) / 819e9 / 0.160173617)
    share = harness.load_plugin("metrics", "fit_mfu").read(run)
    assert share == pytest.approx(
        100 * 100 * (n * 18 * 4 + n * 4) / 819e9 / 1.491680654)
    assert 0 < share < 100


def test_real_trace_breakdown(real):
    ops = real.breakdown()["device_ops"]
    assert len(ops) == 10
    assert [name for name, _ in ops[:3]] == [
        "while.7", "vmap_jit_lut_sigmoid_vmem__.7", "vmap_jit_fx_matvec__.7"]
    assert ops[1][1] == pytest.approx(1.107167874)
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
