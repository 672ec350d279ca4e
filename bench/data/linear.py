"""Seeded LIN/LOG data: uniform samples with 4 decimals, binary labels.

The distribution of ``repro.data.synthetic.make_linear_dataset``
(classification task): X uniform in [0, 1) rounded to ``decimals``, a
ground-truth linear response ``X @ w + b`` with w uniform in (-1, 1) and
b uniform in (-0.5, 0.5), and labels 1 where the response is above its
median.  Drawn in float32 with ``numpy.random.default_rng``, which is
several times faster than the float64 ``RandomState`` draw at 5M x 18.
"""
from __future__ import annotations

import numpy as np


def generate(seed: int, n: int, n_features: int, decimals: int = 4):
    """Return ``{"X": float32 [n, F], "y": float32 [n]}``."""
    rng = np.random.default_rng(seed)
    X = rng.random((n, n_features), dtype=np.float32)
    np.round(X, decimals, out=X)
    w = rng.uniform(-1.0, 1.0, n_features).astype(np.float32)
    b = np.float32(rng.uniform(-0.5, 0.5))
    resp = X @ w + b
    y = (resp > np.median(resp)).astype(np.float32)
    return {"X": X, "y": y}
