"""K-Means cluster sums past int32: one cluster's coordinate sum over all
cores (and, in the kernel, within one core) passes 2^31, and the fit
still gives the exact cluster means.

A cluster of more than 2^31 / 2047 rows at the quantization limit wraps
a single int32 sum; the sums therefore travel as ``fx_sum`` pairs
(DESIGN.md §2).  The data here is about 1.1M rows at that limit, on two
features, so one cluster's sum is about 2.25e9.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import make_estimator, make_system
from repro.core.kmeans import QUANT_RANGE
from repro.kernels.kmeans_assign.kernel import kmeans_assign
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref

N_BIG, N_SMALL, K, ITERS = 1_100_000, 20_000, 2, 3


@pytest.fixture(scope="module")
def data():
    """The big group sits at the top of the range on both features, the
    small one below zero; 1.1M x 2047 = 2.25e9 > 2^31."""
    rng = np.random.default_rng(0)
    big = np.ones((N_BIG, 2), np.float32)
    small = rng.uniform(-1.0, -0.2, (N_SMALL, 2)).astype(np.float32)
    X = np.concatenate([big, small])
    return X[rng.permutation(len(X))]


def _exact_lloyd(X, seed):
    """Lloyd's on the quantized data in float64, which holds every sum
    exactly: the program's init draw, first-minimum ties, and means."""
    scale = np.float32(float(np.abs(X).max()) / QUANT_RANGE)
    Xq = np.clip(np.round(X / scale), -QUANT_RANGE,
                 QUANT_RANGE).astype(np.int64)
    idx = np.random.RandomState(seed).choice(len(Xq), size=K,
                                             replace=False)
    C = Xq[idx].astype(np.float32)
    for _ in range(ITERS):
        c = np.round(C).astype(np.int64)
        lab = np.argmin((c * c).sum(1)[None, :] - 2 * Xq @ c.T, axis=1)
        counts = np.bincount(lab, minlength=K).astype(np.float64)
        sums = np.stack([Xq[lab == j].sum(0) for j in range(K)])
        assert np.abs(sums).max() > 2 ** 31    # a single int32 would wrap
        C = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts[:, None], 1),
                     C).astype(np.float32)
    return C * scale


@pytest.mark.parametrize("n_cores,fuse_steps", [(64, 1), (64, ITERS),
                                                (4, 1), (4, ITERS)])
def test_fit_gives_exact_means_past_int32(data, n_cores, fuse_steps):
    seed = 5
    ds = make_system("pim", n_cores=n_cores).put(data)
    est = make_estimator("kmeans", version="int16", system=ds.system,
                         n_clusters=K, max_iter=ITERS, tol=0.0, seed=seed,
                         kernel_backend="jnp_ref",
                         fuse_steps=fuse_steps).fit(ds)
    want = _exact_lloyd(data, seed)
    if fuse_steps == 1:     # float64 host update: bit for bit
        np.testing.assert_array_equal(est.cluster_centers_, want)
    else:                   # float32 update on the device
        np.testing.assert_allclose(est.cluster_centers_, want, rtol=1e-6)


def test_kernel_pair_past_int32_within_one_core_matches_oracle():
    """One core's sum passes 2^31: the Pallas kernel (interpret mode)
    and the ``ref.py`` oracle give the same normalised pair, worth the
    exact sum."""
    bn = 65_536
    n = 18 * bn
    rng = np.random.default_rng(1)
    x = np.full((n, 2), QUANT_RANGE, np.int16)
    some = rng.random(n) < 0.05
    x[some] = rng.integers(-QUANT_RANGE, QUANT_RANGE + 1, (some.sum(), 2))
    c = np.array([[QUANT_RANGE, QUANT_RANGE], [-QUANT_RANGE, 0], [0, 0]],
                 np.int16)
    labels, sums, counts = kmeans_assign(jnp.asarray(x), jnp.asarray(c),
                                         block_n=bn, interpret=True)
    l2, s2, n2 = kmeans_assign_ref(jnp.asarray(x), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(sums), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(n2))
    lab = np.asarray(labels)
    exact = np.stack([x[lab == j].astype(np.int64).sum(0)
                      for j in range(len(c))])
    assert np.abs(exact).max() > 2 ** 31
    s = np.asarray(sums, np.int64)
    assert sums.shape == (3, 2, 2)
    assert ((0 <= s[..., 1]) & (s[..., 1] < 256)).all()
    np.testing.assert_array_equal(s[..., 0] * 256 + s[..., 1], exact)
