"""Exact reference of quantized KME: Lloyd's method on the data rounded
to the integer grid, in integer arithmetic.

The paper's KME (arXiv:2207.07886 §3.4) quantizes the data to a
symmetric integer grid, ``quant`` steps either side of zero (2047 for
the int16 version: 12 bits stored in int16), and runs Lloyd's on it:
initial centroids are ``k`` distinct rows drawn with
``numpy.random.RandomState(seed).choice(n, k, replace=False)``; each of
``n_iters`` iterations rounds the centroids to the grid, assigns every
row to its nearest one by squared distance (the first on a tie) and
moves each centroid to the float64 mean of its rows, kept in float32 (a
centroid with no rows stays); the labels are those of the final rounded
centroids.

Every number before the means is an integer and is computed exactly:
the distances in int32 (at most ``F * (2 * quant)^2``), each block's
cluster sums in int32 (at most ``BLOCK * quant``), their total over the
blocks in int64 on the host.  So an implementation of the same
algorithm that is exact reads 0 against this one, and one whose sums
wrap or lose rows does not.  Rows go through in blocks, feature-major.
Nothing here imports the program.

``quant`` 127 gives the control: the same algorithm on the int8 grid.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

QUANT = 2047
BLOCK = 1 << 16


def init_indices(seed: int, n: int, k: int) -> np.ndarray:
    return np.random.RandomState(seed % 2 ** 32).choice(n, size=k,
                                                        replace=False)


def quantize(X: np.ndarray, quant: int):
    """``(Xq int32 on the grid, scale float32)``, ``X ~ Xq * scale``."""
    X = np.asarray(X, np.float32)
    scale = np.float32(max(float(np.abs(X).max()), 1e-12) / quant)
    Xq = np.clip(np.round(X / scale), -quant, quant)
    return Xq.astype(np.int32), scale


def _labels(x, c):
    """x int32 [F, B], c int32 [K, F] -> nearest centroid of each row."""
    dist = jnp.stack([jnp.sum((x - c[j][:, None]) ** 2, axis=0)
                      for j in range(c.shape[0])])
    return jnp.argmin(dist, axis=0)


@jax.jit
def _sums(xb, used, c):
    """Per block: each cluster's int32 coordinate sums and row count
    over the rows in use."""
    def block(args):
        x, u = args
        lab = _labels(x, c)
        hit = [(lab == j) & u for j in range(c.shape[0])]
        return (jnp.stack([jnp.sum(jnp.where(h[None, :], x, 0), axis=1)
                           for h in hit]),
                jnp.stack([jnp.sum(h.astype(jnp.int32)) for h in hit]))
    return jax.lax.map(block, (xb, used))


@jax.jit
def _all_labels(xb, c):
    return jax.lax.map(lambda x: _labels(x, c), xb)


def fit(X: np.ndarray, *, seed: int, n_clusters: int, n_iters: int,
        quant: int = QUANT, rows: Optional[int] = None,
        block: int = BLOCK):
    """Exact quantized Lloyd's: ``{"cluster_centers_", "labels_"}``.

    ``rows`` moves the centroids by the first rows only (a fault: the
    rest of the batch left out, the mean taken over what is left); the
    initial draw and the final labels still cover every row."""
    Xq, scale = quantize(X, quant)
    n, f = Xq.shape
    k = int(n_clusters)
    C = Xq[init_indices(seed, n, k)].astype(np.float32)
    block = min(block, n)
    nb = -(-n // block)
    xb = jnp.pad(jnp.asarray(Xq), [(0, nb * block - n), (0, 0)])
    xb = xb.reshape(nb, block, f).transpose(0, 2, 1)
    used = (jnp.arange(nb * block) < (n if rows is None else int(rows)))
    used = used.reshape(nb, block)
    del Xq
    for _ in range(int(n_iters)):
        c = jnp.asarray(np.round(C).astype(np.int32))
        sums, counts = jax.device_get(_sums(xb, used, c))
        sums = sums.astype(np.int64).sum(axis=0).astype(np.float64)
        counts = counts.astype(np.int64).sum(axis=0).astype(np.float64)
        C = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts[:, None], 1),
                     C).astype(np.float32)
    labels = _all_labels(xb, jnp.asarray(np.round(C).astype(np.int32)))
    return {"cluster_centers_": C * scale,
            "labels_": np.asarray(labels, np.int32).reshape(-1)[:n]}


def compare(program: dict, reference: dict, X=None) -> dict:
    """Numbers that decide ``correct``.

    ``centroid_gap``: Frobenius distance between the program's centroids
    and the reference's, over the norm of the reference's.
    ``label_mismatch``: share of rows whose label differs."""
    p = np.asarray(program["cluster_centers_"], np.float64)
    r = np.asarray(reference["cluster_centers_"], np.float64)
    lp = np.asarray(program["labels_"])
    lr = np.asarray(reference["labels_"])
    return {"centroid_gap": float(np.linalg.norm(p - r)
                                  / max(np.linalg.norm(r), 1e-30)),
            "label_mismatch": float(np.mean(lp != lr))
            if lp.shape == lr.shape else 1.0}
