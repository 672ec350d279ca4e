"""Plain reference of LOG: full-batch gradient descent on the logistic
loss, as the paper's LOG-INT32-LUT version states it (arXiv:2207.07886
§3.2, Fig. 4), in ``jax.numpy`` float32 and numpy.

    z = X w + b,  e = sigmoid(z) - y,  w -= (lr / n) X^T e,  b -= (lr / n) sum(e)

from w = 0, b = 0, for ``n_iters`` steps, with the version's rounding:

* the data, and at every step the broadcast weights and bias, lie on
  the grid of ``2**-frac_bits`` (Q format, rounded to nearest even);
* each product of the forward dot and of the gradient is rounded to
  that grid (ties up, as a fixed-point shift rounds) before it is
  summed, and the sums are exact integers in units of the grid;
* the sigmoid is a table of ``round(sigmoid(i / 2**lut_frac) *
  2**value_frac)`` for ``i < boundary * 2**lut_frac``, read at
  ``|z| * 2**lut_frac`` (saturating at its last entry) and reflected for
  negative ``z``; its value is rounded to the grid, ties up.  The table
  is built here from that formula;
* the update is float32: the exact gradient rounded once to float32,
  its product with ``lr / n`` rounded once, then the difference.

Every rounded value is held exactly in float32, and the integer sums
are exact, so the only float rounding is the update's.  Rows go
through in blocks; the update runs on the host.  Nothing here imports
the program.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 18


def sigmoid_table(boundary: int, lut_frac: int, value_frac: int):
    """The table's values as fractions: round(sigmoid(i/2^lut_frac) *
    2^value_frac) / 2^value_frac, from float64."""
    xs = np.arange(boundary << lut_frac, dtype=np.float64) / (1 << lut_frac)
    vals = np.round((1.0 / (1.0 + np.exp(-xs))) * (1 << value_frac))
    vals = np.minimum(vals, (1 << value_frac) - 1)
    return (vals / (1 << value_frac)).astype(np.float32)


def _ties_up(v, q):
    """Integer nearest to v * q, ties up."""
    return jnp.floor(v * q + 0.5)


@functools.partial(jax.jit, static_argnames=("frac_bits", "lut_frac",
                                             "dtype"))
def _grad(Xb, yb, mb, table, wq, bq, *, frac_bits: int, lut_frac: int,
          dtype: str):
    """Per-block gradient sums at the weights (wq, bq).  Fixed point:
    exact int32 sums in units of 2**-frac_bits.  ``dtype="bfloat16"``
    (the control): the same algorithm with its products in one bfloat16
    pass, float32 sums."""
    q = np.float32(1 << frac_bits)

    def sigmoid(z):
        idx = jnp.minimum(jnp.round(jnp.abs(z) * np.float32(1 << lut_frac)),
                          table.shape[0] - 1).astype(jnp.int32)
        v = table[idx]
        return _ties_up(jnp.where(z < 0, 1.0 - v, v), q) / q

    def block(x, yv, m):
        if dtype != "float32":
            x = x.astype(dtype)
            z = jnp.dot(x, wq.astype(dtype),
                        preferred_element_type=jnp.float32) + bq
            e = (sigmoid(z) - yv) * m
            return (jnp.dot(e.astype(dtype), x,
                            preferred_element_type=jnp.float32),
                    jnp.sum(e))
        z = jnp.sum(_ties_up(x * wq[None, :], q), axis=1) / q + bq
        e = (sigmoid(z) - yv) * m
        gw = jnp.sum(_ties_up(e[:, None] * x, q).astype(jnp.int32), axis=0)
        return gw, jnp.sum((e * q).astype(jnp.int32))

    return jax.lax.map(lambda blk: block(*blk), (Xb, yb, mb))


def _blocks(a: np.ndarray, block: int):
    """(n, ...) -> (n_blocks, block, ...), zero-padded, plus a row mask."""
    n = a.shape[0]
    nb = -(-n // block)
    out = np.zeros((nb * block,) + a.shape[1:], np.float32)
    out[:n] = a
    mask = (np.arange(nb * block) < n).astype(np.float32)
    return out.reshape(nb, block, *a.shape[1:]), mask.reshape(nb, block)


def fit(X: np.ndarray, y: np.ndarray, *, n_iters: int, lr: float,
        frac_bits: int, lut: dict, rows: Optional[int] = None,
        dtype: str = "float32", block: int = BLOCK):
    """LOG fit: ``{"coef_": float32 [F], "intercept_": float32}``.

    ``lut`` holds the table's ``boundary``, ``frac_bits`` and
    ``value_frac``.  ``rows`` keeps only the first rows (a fault: the
    rest of the batch left out, the mean taken over what is left);
    ``dtype="bfloat16"`` runs the products in one bfloat16 pass (the
    control)."""
    if rows is not None:
        X, y = X[:rows], y[:rows]
    n, f = X.shape
    q = np.float32(1 << frac_bits)
    Xg = (np.round(np.asarray(X, np.float32) * q) / q).astype(np.float32)
    Xb, mb = _blocks(Xg, min(block, n))
    yb, _ = _blocks(np.asarray(y, np.float32), min(block, n))
    Xb, yb, mb = jax.device_put((Xb, yb, mb))
    table = jnp.asarray(sigmoid_table(lut["boundary"], lut["frac_bits"],
                                      lut["value_frac"]))
    scale = np.float32(lr / n)
    w, b = np.zeros(f, np.float32), np.float32(0)
    for _ in range(int(n_iters)):
        wq = (np.round(w * q) / q).astype(np.float32)
        bq = np.float32(np.round(b * q) / q)
        gw, gb = jax.device_get(_grad(
            Xb, yb, mb, table, wq, bq, frac_bits=frac_bits,
            lut_frac=lut["frac_bits"], dtype=dtype))
        if dtype == "float32":      # exact integer sums, one rounding
            gw = gw.astype(np.int64).sum(0).astype(np.float32) / q
            gb = np.float32(gb.astype(np.int64).sum()) / q
        else:
            gw, gb = gw.sum(0, dtype=np.float32), np.float32(gb.sum())
        w = (w - (scale * gw).astype(np.float32)).astype(np.float32)
        b = np.float32(b - np.float32(scale * gb))
    return {"coef_": w, "intercept_": b}


def compare(program: dict, reference: dict, **_data) -> dict:
    """The number that decides ``correct``: ``coef_gap``, the distance
    between the program's fitted parameters (w, b) and the reference's,
    over the norm of the reference's."""
    p = np.append(np.asarray(program["coef_"], np.float64),
                  float(program["intercept_"]))
    r = np.append(np.asarray(reference["coef_"], np.float64),
                  float(reference["intercept_"]))
    return {"coef_gap": float(np.linalg.norm(p - r)
                              / max(np.linalg.norm(r), 1e-30))}
