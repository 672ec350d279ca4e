"""Pallas TPU kernel: K-Means assignment + accumulation (paper §3.4).

TPU adaptation: the DPU loops over points computing 16-bit multiplies.
Here the points arrive transposed, one point per lane, so every block
and the label output are lane-dense (also under ``vmap``, which adds a
squeezed cores axis in front):

  * distances ``||c_k||^2 - 2 x.c_k`` (||x||^2 omitted, argmin-invariant)
    are exact int32 VPU multiply-adds over the F features — the
    contraction is tiny, and the MXU takes no int32 operands;
  * the per-cluster coordinate sums are one-hot matmuls on the MXU.  A
    coordinate is split into its high byte and its low byte, each an
    integer in [-128, 255] that bfloat16 holds exactly; a block's sums
    stay below 2^24, so float32 accumulation is exact too.  The two
    byte sums stay apart in two int32 accumulators, and each block
    carries the low sum's excess over 255 into the high one, so the
    kernel returns the ``fx_sum`` pair (``core/fixed_point.py``) worth
    ``hi * 256 + lo`` with ``0 <= lo < 256``: exact where one int32
    would wrap (a cluster of more than 2^31 / 2047 rows near the
    quantization range), and pairs from many cores add element-wise.

Centroids (K x F) stay pinned in VMEM across the whole grid; point blocks
stream HBM->VMEM, the streaming-bank access pattern the paper engineers
for the DPU (Recommendation #6).  The sums and ``counts`` map every
grid step to block (0, 0) and accumulate in place across the sequential
grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl

# contract the lane (point) axis of both operands: (K, bn) x (F, bn)
_NT = (((1,), (1,)), ((), ()))
# the byte dots are exact in one bfloat16 pass; pinned so a caller's
# default_matmul_precision cannot ask Mosaic for more
_ONE_PASS = jax.lax.Precision.DEFAULT


def _kmeans_kernel(xt_ref, c_ref, labels_ref, hi_ref, lo_ref, counts_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hi_ref[...] = jnp.zeros_like(hi_ref)
        lo_ref[...] = jnp.zeros_like(lo_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    xt = xt_ref[...].astype(jnp.int32)          # (F, bn)
    c = c_ref[...].astype(jnp.int32)            # (K, F)
    k, f = c.shape
    cross = jnp.zeros((k, xt.shape[1]), jnp.int32)
    for j in range(f):                          # (K, 1) * (1, bn)
        cross = cross + c[:, j:j + 1] * xt[j:j + 1, :]
    cnorm = jnp.sum(c * c, axis=1, keepdims=True)            # (K, 1)
    dist = cnorm - 2 * cross                                 # (K, bn)
    kid = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 0)
    best = jnp.min(dist, axis=0, keepdims=True)
    # first minimum, as jnp.argmin breaks ties
    labels = jnp.min(jnp.where(dist == best, kid, k), axis=0,
                     keepdims=True)                          # (1, bn)
    labels_ref[...] = labels

    onehot = kid == labels                                   # (K, bn)
    oh = onehot.astype(jnp.float32).astype(jnp.bfloat16)
    hi = (xt >> 8).astype(jnp.float32).astype(jnp.bfloat16)
    lo = (xt & 255).astype(jnp.float32).astype(jnp.bfloat16)
    s_hi = jax.lax.dot_general(oh, hi, _NT, precision=_ONE_PASS,
                               preferred_element_type=jnp.float32)
    s_lo = jax.lax.dot_general(oh, lo, _NT, precision=_ONE_PASS,
                               preferred_element_type=jnp.float32)
    lo_acc = lo_ref[...] + s_lo.astype(jnp.int32)            # (K, F)
    hi_ref[...] += s_hi.astype(jnp.int32) + (lo_acc >> 8)
    lo_ref[...] = lo_acc & 255
    counts_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=1,
                               keepdims=True)                # (K, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(x_q: jnp.ndarray, c_q: jnp.ndarray, *,
                  block_n: int = 1024, interpret: bool = False):
    """x_q int16 [N, F]; c_q int16 [K, F] ->
    (labels int32 [N], sums int32 [K, F, 2], counts int32 [K]).

    ``sums`` is the pair ``(hi, lo)`` worth ``hi * 256 + lo`` with
    ``0 <= lo < 256``.  Exact for points in the int16 range as long as a
    block of ``block_n`` rows keeps every per-cluster coordinate sum of
    either byte below 2^24 (``block_n <= 65,793``) and ``hi``, at most
    128 a row, stays in int32 (fewer than 2^24 rows a call)."""
    n, f = x_q.shape
    k, f2 = c_q.shape
    assert f == f2
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    labels, hi, lo, counts = pallas_call(
        _kmeans_kernel,
        name="kmeans_assign",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((f, bn), lambda i: (0, i)),
            pl.BlockSpec((k, f), lambda i: (0, 0)),   # centroids pinned
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((k, f), lambda i: (0, 0)),   # accumulated in place
            pl.BlockSpec((k, f), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((k, f), jnp.int32),
            jax.ShapeDtypeStruct((k, f), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
        ],
        dimension_semantics=("arbitrary",),
        interpret=interpret,
    )(x_q.T, c_q)
    return labels[0], jnp.stack([hi, lo], axis=-1), counts[:, 0]
