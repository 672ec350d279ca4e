"""Dispatchable wrapper for the K-Means assign kernel (op ``kmeans_assign``).

``assign_and_accumulate`` routes between the Pallas kernel and the pure
jnp oracle through the :mod:`repro.kernels.dispatch` backend layer; on
the kernel path it pads N to a block multiple and corrects the
padding's contribution afterwards.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import legacy_launch, register_op
from .kernel import kmeans_assign
from .ref import kmeans_assign_ref


def _assign_pallas(x_q: jnp.ndarray, c_q: jnp.ndarray, *,
                   interpret: bool = True, block_n: int = 1024):
    """Kernel path: pads N to a block multiple, runs the kernel, and
    corrects the padding's contribution (padding rows are zeros -> they
    all land in the one cluster minimizing -2*0.c + ||c||^2, contribute
    zero to both bytes of ``sums``, and are subtracted from that
    cluster's count)."""
    n = x_q.shape[0]
    bn = min(block_n, max(n, 8))
    n_pad = -(-n // bn) * bn
    if n_pad != n:
        xp = jnp.zeros((n_pad, x_q.shape[1]), x_q.dtype).at[:n].set(x_q)
    else:
        xp = x_q
    labels, sums, counts = kmeans_assign(xp, c_q, block_n=bn,
                                         interpret=interpret)
    if n_pad != n:
        c = c_q.astype(jnp.int32)
        pad_label = jnp.argmin(jnp.sum(c * c, axis=1)).astype(jnp.int32)
        n_fake = n_pad - n
        counts = counts.at[pad_label].add(-n_fake)
        labels = labels[:n]
    return labels, sums, counts


def assign_and_accumulate(x_q: jnp.ndarray, c_q: jnp.ndarray, *,
                          backend=None, use_pallas: bool = None,
                          interpret: bool = None, block_n: int = 1024):
    """x_q int16 [N, F]; c_q int16 [K, F] ->
    (labels int32 [N], sums int32 [K, F, 2], counts int32 [K]).

    ``sums`` is an ``fx_sum`` pair (``core/fixed_point.py``) worth
    ``sums[..., 0] * 256 + sums[..., 1]``.  ``backend`` picks the
    implementation (None = auto-select).  The legacy
    ``use_pallas``/``interpret`` flags keep their meaning when set
    explicitly; leaving everything unset now auto-selects (``jnp_ref``
    off-TPU — the old default was the interpret kernel).
    """
    return legacy_launch("kmeans_assign", x_q, c_q, backend=backend,
                         use_pallas=use_pallas, interpret=interpret,
                         block_n=block_n)


def _assign_ref(x_q, c_q, *, block_n: int = 1024):
    del block_n  # jnp oracle needs no tiling
    return kmeans_assign_ref(x_q, c_q)


register_op("kmeans_assign", family="kmeans_assign",
            pallas=_assign_pallas, ref=_assign_ref)
