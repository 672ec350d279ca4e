"""Pallas TPU kernel: decision-tree split-evaluate (paper §3.3, Fig. 5).

TPU adaptation of the paper's streaming layout: the DPU version reorders
feature values so each leaf is contiguous and streams MRAM->WRAM.  On TPU
the same property — "every byte fetched from HBM is used by exactly one
streaming pass" — is achieved by tiling points into (F x block_n) VMEM
blocks, one point per lane (lane-dense also under ``vmap``), and turning
both per-leaf threshold selection and per-(leaf, class) count scatter
into **one-hot matmuls** (MXU work, no data-dependent scatter, which
Mosaic does not support):

  t[f, i]      = thresholds[:, f] . onehot_leaf[:, i]
  counts[s, f] = onehot_seg[s, :] . below[f, :]          s = leaf*C + class

Both stay exact in bfloat16: the thresholds arrive as three bfloat16
pieces whose float32 sum is the threshold itself (each one-hot column
selects exactly one leaf), and the 0/1 counts of a block sum exactly in
float32.  Thresholds and the count accumulators stay pinned in VMEM
across the grid; point blocks stream — the direct analogue of the DPU's
DMA streaming.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
# the one-hot dots are exact in one bfloat16 pass; pinned so a caller's
# default_matmul_precision cannot ask Mosaic for more
_ONE_PASS = jax.lax.Precision.DEFAULT


def _trunc_bf16(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> bfloat16 rounding toward zero (never overflows)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(
        jnp.bfloat16)


def split_bf16x3(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> (3, ...) bfloat16 pieces holding the top, middle and
    bottom 8 significand bits of ``x``: their float32 sum ``(hi + mid) +
    lo`` is ``x`` exactly for every finite ``x`` with ``|x| >= 2^-103``
    (below that the bottom piece is subnormal and may round)."""
    hi = _trunc_bf16(x)
    r1 = x - hi.astype(jnp.float32)
    mid = _trunc_bf16(r1)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.stack([hi, mid, lo])


def _gini_kernel(xt_ref, seg_ref, leaf_ref, th_ref, counts_ref, totals_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        totals_ref[...] = jnp.zeros_like(totals_ref)

    xt = xt_ref[...]                                 # (F, bn) f32
    seg = seg_ref[...]                               # (1, bn) leaf*C+y
    leaf = leaf_ref[...]                             # (1, bn)
    n_leaves = th_ref.shape[2]
    n_slots = counts_ref.shape[0]
    bn = xt.shape[1]

    oh_leaf = (jax.lax.broadcasted_iota(jnp.int32, (n_leaves, bn), 0)
               == leaf).astype(jnp.float32).astype(jnp.bfloat16)
    t = [jax.lax.dot_general(th_ref[p], oh_leaf, _NN, precision=_ONE_PASS,
                             preferred_element_type=jnp.float32)
         for p in range(3)]                          # (F, bn) each
    below = (xt <= (t[0] + t[1]) + t[2]).astype(jnp.float32)

    oh_seg = (jax.lax.broadcasted_iota(jnp.int32, (n_slots, bn), 0)
              == seg).astype(jnp.float32)            # (n_slots, bn)
    counts_ref[...] += jax.lax.dot_general(
        oh_seg.astype(jnp.bfloat16), below.astype(jnp.bfloat16), _NT,
        precision=_ONE_PASS,
        preferred_element_type=jnp.float32).astype(jnp.int32)
    totals_ref[...] += jnp.sum(oh_seg, axis=1,
                               keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_classes", "block_n",
                                             "interpret"))
def gini_counts(x: jnp.ndarray, y: jnp.ndarray, leaf: jnp.ndarray,
                thresholds: jnp.ndarray, *, n_classes: int,
                block_n: int = 1024, interpret: bool = False):
    """x f32 [N, F]; y/leaf int32 [N]; thresholds f32 [L, F].
    N must be a block multiple and leaf in [0, L) (ops.py pads/validates).
    -> (below int32 [L, C, F], total int32 [L, C])."""
    n, f = x.shape
    n_leaves = thresholds.shape[0]
    n_slots = n_leaves * n_classes
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    seg = (leaf * n_classes + y).reshape(1, n)
    counts, totals = pallas_call(
        _gini_kernel,
        name="gini_split",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((f, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((3, f, n_leaves), lambda i: (0, 0, 0)),  # pinned
        ],
        out_specs=[
            pl.BlockSpec((n_slots, f), lambda i: (0, 0)),   # accumulated
            pl.BlockSpec((n_slots, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, f), jnp.int32),
            jax.ShapeDtypeStruct((n_slots, 1), jnp.int32),
        ],
        dimension_semantics=("arbitrary",),
        interpret=interpret,
    )(x.T, seg, leaf.reshape(1, n), split_bf16x3(thresholds.T))
    return (counts.reshape(n_leaves, n_classes, f),
            totals.reshape(n_leaves, n_classes))
