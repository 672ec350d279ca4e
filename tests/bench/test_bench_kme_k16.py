"""The cell ``kme_int16_higgs_k16.serial`` at a test size on the CPU:
the program agrees bit for bit with the exact quantized reference
(Pallas kernels in interpret mode), each planted fault and the int8
control read not correct, the reference itself is exact where one int32
sum would wrap, and the readers of the cell's two spans,
``init_draw_s`` and ``finish_pass_s``, work on synthetic traces.

The test size is that of ``test_bench_correct.py``'s KME cells, whose
helpers are reused here.  Its limits are the test size's own, set from
CPU readings at 65,536 rows (seeds 1 to 4) as ``bench/calibrate.py``
sets the cell's: the program reads 0 on both numbers, serial and fused;
the int8 control 0.0053 to 0.22 (``centroid_gap``) and 1.0 % to 9.0 %
of rows (``label_mismatch``), the reference fitted on half the rows
0.0078 to 0.012 and 1.8 % to 3.5 %.  Each limit is a quarter of the
smallest of those.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "tests", Path(__file__).resolve().parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import calibrate, harness  # noqa: E402
from test_bench_correct import FAULTS, SHAPES  # noqa: E402
from test_bench_spans import HOST, _read  # noqa: E402
from test_bench_spans import _run as _traced  # noqa: E402
from test_kmeans_pair import ITERS, K, N_BIG, _exact_lloyd  # noqa: E402

CELL, CONFIG = "kme_int16_higgs_k16.serial", "kme_int16_higgs_k16"
SHAPE = SHAPES["kme_int16_higgs"]
LIMIT = {"centroid_gap": 0.0013, "label_mismatch": 0.0026}


def _run(seed=1):
    result, _ = harness.execute(CELL, seed, 0.01, False, require_tpu=False,
                                shape=SHAPE, limits=LIMIT,
                                log=lambda msg: None)
    assert result["attempted"] >= 1
    return result


def test_config_is_the_higgs_shape_with_limits():
    spec = harness.load_json(harness.BENCH / "cells" / f"{CELL}.json")
    assert spec["chips"] == 1 and spec["params"] == {"fuse_steps": 1}
    cell = harness.resolve_cell(CELL)
    assert (cell.n, cell.n_features) == (11_000_000, 28)
    assert cell.config["name"] == CONFIG
    assert cell.config["reference"] == "kmeans_quantized"
    assert cell.fit_params(7) == {"n_clusters": 16, "max_iter": 10,
                                  "tol": 0.0, "n_init": 1, "seed": 7,
                                  "fuse_steps": 1}
    assert set(cell.config["limits"]) == {"centroid_gap", "label_mismatch"}
    assert set(cell.config["limits_why"]) == set(cell.config["limits"])


@pytest.mark.parametrize("seed", [1, 2])
def test_program_agrees_with_reference_pallas_interpret(seed, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas_interpret")
    result = _run(seed)
    assert result["correct"], result["checks"]
    assert {k: v["value"] for k, v in result["checks"].items()} == {
        "fits_differ": 0, "centroid_gap": 0.0, "label_mismatch": 0.0}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_underneath_the_timed_path_is_not_correct(fault, monkeypatch):
    assert _run()["correct"]
    FAULTS[fault](monkeypatch)
    result = _run()
    assert not result["correct"], result["checks"]


def test_int8_control_is_not_correct():
    rows = calibrate.calibrate(CONFIG, [1, 2, 3], shape=SHAPE,
                               log=lambda s: None)
    for row in rows:
        assert list(row["program"]) == [CELL]
        for numbers in row["program"].values():
            assert all(v <= LIMIT[k] for k, v in numbers.items())
        int8 = row["control"]["int8"]
        assert any(v > LIMIT[k] for k, v in int8.items()), row
        for fault in ("half_batch", "one_core", "answer_altered"):
            numbers = row["faults"][fault]
            assert any(v > LIMIT[k] for k, v in numbers.items()), row


def test_reference_is_exact_past_int32():
    """One cluster's coordinate sum passes 2^31: the reference gives the
    exact means, as float64 Lloyd's on the quantized data does."""
    ref = harness.load_plugin("ref", "kmeans_quantized")
    rng = np.random.default_rng(0)
    X = np.concatenate([np.ones((N_BIG, 2), np.float32),
                        rng.uniform(-1.0, -0.2, (20_000, 2))
                        .astype(np.float32)])
    X = X[rng.permutation(len(X))]
    got = ref.fit(X, seed=5, n_clusters=K, n_iters=ITERS)
    np.testing.assert_array_equal(got["cluster_centers_"],
                                  _exact_lloyd(X, 5))


# -- the readers of repro.init and repro.finish -----------------------------

# times in us: the window 0-100 and the two steps of the synthetic
# trace; a draw at the head of the first (12-15) and a second draw and
# a finish pass in the second (50-51, 70-88)
KME_HOST = HOST + [("repro.init", 12, 15), ("repro.init", 50, 51),
                   ("repro.finish", 70, 88)]


@pytest.mark.parametrize("traced_fits", [1, 2])
@pytest.mark.parametrize("metric,us", [("init_draw_s", 3 + 1),
                                       ("finish_pass_s", 18)])
def test_span_seconds_per_traced_fit(metric, us, traced_fits):
    got = _read(metric, _traced(host=KME_HOST, traced_fits=traced_fits))
    assert got == pytest.approx(us * 1e-6 / traced_fits)


@pytest.mark.parametrize("metric", ["init_draw_s", "finish_pass_s"])
def test_span_readers_give_none_without_their_spans(metric):
    # a program without the spans (the parent of the pair) or no trace
    assert _read(metric, _traced(host=HOST)) is None
    assert _read(metric, _traced(host=KME_HOST, traced_fits=0)) is None
    untraced = _traced(host=KME_HOST)
    untraced.trace = None
    assert _read(metric, untraced) is None
