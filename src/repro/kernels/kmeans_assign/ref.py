"""Pure-jnp oracle for the K-Means assign/accumulate kernel.

Semantics (paper §3.4): for each quantized point find the nearest centroid
(squared L2, integer arithmetic), then produce per-cluster coordinate sums
and counts — the per-PIM-core part of one Lloyd iteration.  The sums are
``fx_sum`` pairs (``core/fixed_point.py``): the high-byte and low-byte
sums, normalised to ``(hi, lo)`` worth ``hi * 256 + lo`` with
``0 <= lo < 256``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.fixed_point import fx_pair

_TN = (((0,), (0,)), ((), ()))     # contract the point axis: (N, K) x (N, F)


def kmeans_assign_ref(x_q: jnp.ndarray, c_q: jnp.ndarray):
    """x_q int16 [N, F]; c_q int16 [K, F]
    -> (labels int32 [N], sums int32 [K, F, 2], counts int32 [K]).

    Exact while a call holds fewer than 2^23 rows (the low-byte sum,
    at most 255 a row, stays in int32), as ``fx_sum`` is."""
    x = x_q.astype(jnp.int32)
    c = c_q.astype(jnp.int32)
    cross = jax.lax.dot_general(x, c.T, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
    cnorm = jnp.sum(c * c, axis=1)
    dist = cnorm[None, :] - 2 * cross          # ||x||^2 omitted (argmin-inv)
    labels = jnp.argmin(dist, axis=1).astype(jnp.int32)
    k = c_q.shape[0]
    onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(jnp.int32)
    hi = jax.lax.dot_general(onehot, x >> 8, _TN,
                             preferred_element_type=jnp.int32)
    lo = jax.lax.dot_general(onehot, x & 255, _TN,
                             preferred_element_type=jnp.int32)
    sums = fx_pair(hi, lo)
    counts = jnp.sum(onehot, axis=0)
    return labels, sums, counts
