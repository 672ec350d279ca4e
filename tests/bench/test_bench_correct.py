"""``correct`` at a test size on the CPU: the program's fits agree with the
plain references (Pallas kernels in interpret mode), and the control and
each fault the cells can have come out as not correct.

The limits here are the test size's own, set from CPU readings at
65,536 rows (seeds 1 to 4): LOG's ``coef_gap`` reads 0 (the program
computes the reference's fixed-point algorithm bit for bit), its
controls 2.6e-4 to 5.7e-4 (``hyb_lut``) and 8e-4 to 2.7e-3
(bfloat16); KME's gaps lie below 0.0017 (``centroid_gap``) and 0.5 % of
rows (``label_mismatch``), its control's at 0.0053 to 0.22 and 1 % to
9 %.  At this size a cluster holds some 4,000 rows, so KME's gaps are
far larger than at the cells' own size.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import calibrate, harness  # noqa: E402

N = 65_536
SHAPES = {"log_lut_susy": (N, 18), "kme_int16_higgs": (N, 28)}
LIMITS = {"log_lut_susy": {"coef_gap": 6e-5},
          "kme_int16_higgs": {"centroid_gap": 0.004,
                              "label_mismatch": 0.008}}
CELLS = ["log_lut_susy.serial", "log_lut_susy.fused",
         "kme_int16_higgs.serial", "kme_int16_higgs.fused"]


def _config(cell):
    return cell.split(".")[0]


def _run(cell, seed=1):
    result, _ = harness.execute(
        cell, seed, 0.01, False, require_tpu=False,
        shape=SHAPES[_config(cell)], limits=LIMITS[_config(cell)],
        log=lambda msg: None)
    assert result["attempted"] >= 1
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_program_agrees_with_reference_pallas_interpret(cell, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas_interpret")
    result = _run(cell)
    assert result["correct"], result["checks"]
    assert result["checks"]["fits_differ"]["value"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("config", sorted(SHAPES))
def test_control_is_not_correct(config):
    """Each control (the program's ``hyb_lut`` path and bfloat16
    products for LOG, int8 data for KME) fails at least one number on
    every seed, and the program passes every number."""
    rows = calibrate.calibrate(config, [1, 2, 3], shape=SHAPES[config],
                               log=lambda s: None)
    for row in rows:
        for cell, numbers in row["program"].items():
            assert all(v <= LIMITS[config][k] for k, v in numbers.items())
        for control in row["control"].values():
            assert any(v > LIMITS[config][k] for k, v in control.items()), \
                row


# -- faults planted underneath the timed path -------------------------------

def _state_unchanged(monkeypatch):
    """Every step returns its state unchanged: the reduce gives nothing."""
    from repro.systems.base import FabricReduce
    monkeypatch.setattr(FabricReduce, "device_reduce", lambda self, p: (
        __import__("jax").tree_util.tree_map(
            lambda v: jnp.zeros_like(v[0]), p)))


def _exchange_left_out(monkeypatch):
    """The exchange between cores left out: core 0's partial alone."""
    from repro.systems.base import FabricReduce
    monkeypatch.setattr(FabricReduce, "device_reduce", lambda self, p: (
        __import__("jax").tree_util.tree_map(lambda v: v[0], p)))


def _half_batch(monkeypatch):
    """Half of each core's rows left out, the mean taken over the rest."""
    from repro.api.dataset import KMeansView, PimDataset
    gd_view, km_view = PimDataset.gd_view, PimDataset.kmeans_view

    def half_gd(self, *a, **kw):
        Xs, ys, mask = gd_view(self, *a, **kw)
        h = Xs.shape[1] // 2
        self.n = int(np.asarray(mask[:, :h]).astype(bool).sum())
        return Xs[:, :h], ys[:, :h], mask[:, :h]

    def half_km(self, *a, **kw):
        v = km_view(self, *a, **kw)
        h = v.shards.shape[1] // 2
        return KMeansView(v.shards[:, :h], v.mask[:, :h], v.host_q, v.scale)
    monkeypatch.setattr(PimDataset, "gd_view", half_gd)
    monkeypatch.setattr(PimDataset, "kmeans_view", half_km)


def _answer_altered(monkeypatch):
    """The fitted model altered where it is produced."""
    from repro.api import workloads
    for cls, key in ((workloads.LogRegWorkload, "coef_"),
                     (workloads.KMeansWorkload, "cluster_centers_")):
        def fit(self, ds, spec, _orig=cls.fit, _key=key):
            r = _orig(self, ds, spec)
            a = np.array(r.attributes[_key], copy=True)
            a[0] = a[1]
            r.attributes[_key] = a
            return r
        monkeypatch.setattr(cls, "fit", fit)


FAULTS = {"state_unchanged": _state_unchanged,
          "exchange_left_out": _exchange_left_out,
          "half_batch": _half_batch, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_underneath_the_timed_path_is_not_correct(cell, fault,
                                                        monkeypatch):
    assert _run(cell)["correct"]
    FAULTS[fault](monkeypatch)
    result = _run(cell)
    assert not result["correct"], result["checks"]
