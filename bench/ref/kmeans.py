"""Plain float32 reference of KME: Lloyd's method in ``jax.numpy`` at
``Precision.HIGHEST``.

It follows the paper's KME algorithm (arXiv:2207.07886 §3.4): initial
centroids are ``k`` distinct rows drawn at random with
``numpy.random.RandomState(seed).choice(n, k, replace=False)``; each of
``n_iters`` iterations assigns every row to its nearest centroid (the
first on a tie) and moves each centroid to the mean of its rows (a
centroid with no rows stays); the labels and the inertia are those of
the final centroids.  Rows go through in blocks.  Nothing here imports
the program.

``quant`` gives the control: the same algorithm on data rounded to a
symmetric integer grid of ``quant`` steps either side of zero, with the
centroids rounded to that grid wherever they meet the data, as a
quantized implementation would run it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 1 << 20


def init_indices(seed: int, n: int, k: int) -> np.ndarray:
    return np.random.RandomState(seed % 2 ** 32).choice(n, size=k,
                                                        replace=False)


def _blocks(X, block):
    n = X.shape[0]
    nb = -(-n // block)
    valid = (jnp.arange(nb * block) < n).reshape(nb, block)
    Xb = jnp.pad(X, [(0, nb * block - n), (0, 0)])
    return Xb.reshape(nb, block, X.shape[1]), valid


def _assign(x, c):
    dist = (jnp.sum(c * c, axis=1)[None, :]
            - 2.0 * jnp.dot(x, c.T, precision=HIGHEST))
    return jnp.argmin(dist, axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_iters", "block",
                                             "quantized", "rows"))
def _lloyd(X, C0, n_iters: int, block: int, quantized: bool, rows: int):
    Xb, valid = _blocks(X, block)
    used = valid & (jnp.arange(valid.size) < rows).reshape(valid.shape)
    k = C0.shape[0]
    cast = jnp.round if quantized else (lambda c: c)

    def step(_, C):
        c = cast(C)

        def body(acc, blk):
            x, v = blk
            oh = ((_assign(x, c)[:, None] == jnp.arange(k)[None, :])
                  & v[:, None]).astype(jnp.float32)
            return (acc[0] + jnp.dot(oh.T, x, precision=HIGHEST),
                    acc[1] + jnp.sum(oh, axis=0)), None
        (sums, counts), _ = jax.lax.scan(
            body, (jnp.zeros_like(C), jnp.zeros(k, jnp.float32)),
            (Xb, used))
        return jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts[:, None], 1.0), C)

    C = jax.lax.fori_loop(0, n_iters, step, C0)
    c = cast(C)

    def final(_, blk):
        x, v = blk
        lab = _assign(x, c)
        d = jnp.sum((x - c[lab]) ** 2, axis=1)
        return None, (lab, jnp.sum(jnp.where(v, d, 0.0)))
    _, (labels, inertia) = jax.lax.scan(final, None, (Xb, valid))
    return C, labels.reshape(-1)[: X.shape[0]], jnp.sum(inertia)


def fit(X: np.ndarray, *, seed: int, n_clusters: int, n_iters: int,
        quant: Optional[int] = None, rows: Optional[int] = None,
        block: int = BLOCK):
    """Float32 Lloyd's: ``{"cluster_centers_", "labels_", "inertia_"}``.

    ``rows`` moves the centroids by the first rows only (a fault: the
    rest of the batch left out, the mean taken over what is left); the
    initial draw and the final labels still cover every row."""
    X = np.asarray(X, np.float32)
    scale = np.float32(1.0)
    if quant is not None:
        scale = np.float32(max(float(np.abs(X).max()), 1e-12) / quant)
        X = np.clip(np.round(X / scale), -quant, quant).astype(np.float32)
    C0 = X[init_indices(seed, X.shape[0], n_clusters)]
    C, labels, inertia = _lloyd(jnp.asarray(X), jnp.asarray(C0),
                                n_iters=int(n_iters),
                                block=int(min(block, X.shape[0])),
                                quantized=quant is not None,
                                rows=int(X.shape[0] if rows is None
                                         else rows))
    return {"cluster_centers_": np.asarray(C, np.float32) * scale,
            "labels_": np.asarray(labels, np.int32),
            "inertia_": float(inertia) * float(scale) ** 2}


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
