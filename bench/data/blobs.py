"""Seeded KME data: isotropic Gaussian blobs.

The distribution of ``repro.data.synthetic.make_blobs``: ``centers``
centres uniform in ``center_box`` per coordinate, each row assigned to a
centre uniformly at random, plus unit-variance Gaussian noise.  Drawn in
float32 with ``numpy.random.default_rng``, in blocks of rows so that no
float64 or full-size temporary is made.
"""
from __future__ import annotations

import numpy as np

_BLOCK = 1 << 20


def generate(seed: int, n: int, n_features: int, centers: int = 16,
             cluster_std: float = 1.0, center_box=(-10.0, 10.0)):
    """Return ``{"X": float32 [n, F]}``."""
    rng = np.random.default_rng(seed)
    C = rng.uniform(center_box[0], center_box[1],
                    (centers, n_features)).astype(np.float32)
    X = np.empty((n, n_features), np.float32)
    for lo in range(0, n, _BLOCK):
        hi = min(n, lo + _BLOCK)
        block = X[lo:hi]
        rng.standard_normal(block.shape, dtype=np.float32, out=block)
        if cluster_std != 1.0:
            block *= np.float32(cluster_std)
        block += C[rng.integers(0, centers, hi - lo)]
    return {"X": X}
