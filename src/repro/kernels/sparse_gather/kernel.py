"""Pallas kernels: sparse embedding gather and scatter-add.

TPU adaptation of the DPU-side sparse row access the EMB workload needs
(DESIGN.md §15): the irregular MRAM row lookup becomes a one-hot matmul
against the shard's placement-map id vector, which the MXU executes as
dense math — the same trick the kmeans_assign family uses for argmin.

Exactness on the MXU, which takes no int32 operands:

* ``emb_gather`` moves bits, whatever the table dtype: each row's 32-bit
  pattern travels as four bytes, each exact in bfloat16, and a one-hot
  column selects at most one row, so every byte comes back unchanged.
* ``emb_scatter_add`` sums.  int32 updates travel as four bytes whose
  per-row sums (at most ``batch * 255``) are exact in float32, and
  recombine in wrapping int32 exactly as the reference's int32 matmul
  does.  float32 updates use a float32-precision matmul, the same
  contraction as the reference (bit-exactness with ``ref.py`` is
  asserted per dtype by tests/test_emb.py, including adversarial
  duplicate-index patterns).

Grid layout:

* ``emb_gather``: (batch blocks, table row blocks).  A batch block's
  output accumulates over the row blocks; only the owning block adds
  non-zero bits, so nothing larger than one (block_r, D) tile of the
  table is ever resident.
* ``emb_scatter_add``: table rows stream through the grid in
  ``block_r`` rows; the batch (idx + update rows) stays pinned and each
  row block absorbs its whole update mass in ONE dot over the full
  batch axis — no cross-grid accumulation, so duplicate indices are
  handled inside a single exact reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl, vmem_scratch

# contract the batch axis of both operands: (B, bR) x (B, D) -> (bR, D)
_TN = (((0,), (0,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))


def _bytes_bf16(bits, k):
    """Byte ``k`` of int32 ``bits`` as an exact bfloat16 in [0, 255]."""
    return ((bits >> (8 * k)) & 255).astype(jnp.float32).astype(
        jnp.bfloat16)


def _byte_dots(onehot, bits, dims):
    """sum_k (onehot . byte_k(bits)) << 8k in wrapping int32.  Exact in
    one bfloat16 pass, pinned against a caller's default precision."""
    out = None
    for k in range(4):
        part = jax.lax.dot_general(
            onehot, _bytes_bf16(bits, k), dims,
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32).astype(jnp.int32)
        out = part if out is None else out + (part << (8 * k))
    return out


def _as_bits(x):
    if x.dtype == jnp.int32:
        return x
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _gather_kernel(tab_ref, ids_ref, idx_ref, o_ref, acc_ref):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    onehot = (idx_ref[...] == ids_ref[...]).astype(     # (bB, 1)==(1, bR)
        jnp.float32).astype(jnp.bfloat16)
    acc_ref[...] += _byte_dots(onehot, _as_bits(tab_ref[...]), _NN)

    @pl.when(r == pl.num_programs(1) - 1)
    def _store():
        bits = acc_ref[...]
        o_ref[...] = (bits if o_ref.dtype == jnp.int32 else
                      jax.lax.bitcast_convert_type(bits, o_ref.dtype))


@functools.partial(jax.jit, static_argnames=("block_b", "block_r",
                                             "interpret"))
def emb_gather(table: jnp.ndarray, ids: jnp.ndarray, idx: jnp.ndarray,
               *, block_b: int = 512, block_r: int = 1024,
               interpret: bool = False) -> jnp.ndarray:
    """[R, D] table + int32 [R] ids, looked up by int32 [B] idx -> [B, D].
    32-bit tables only (int32 or float32)."""
    r, d = table.shape
    (b,) = idx.shape
    assert table.dtype.itemsize == 4, table.dtype
    bb, br = min(block_b, b), min(block_r, r)
    assert b % bb == 0 and r % br == 0, (b, bb, r, br)
    return pallas_call(
        _gather_kernel,
        name="emb_gather",
        grid=(b // bb, r // br),
        in_specs=[
            pl.BlockSpec((br, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, br), lambda i, j: (0, j)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        scratch_shapes=[vmem_scratch((bb, d), jnp.int32)],
        dimension_semantics=("parallel", "arbitrary"),
        interpret=interpret,
    )(table, ids.reshape(1, r), idx.reshape(b, 1))


def _scatter_kernel(tab_ref, ids_ref, idx_ref, upd_ref, o_ref):
    tab = tab_ref[...]                                # (bR, D)
    onehot = idx_ref[...] == ids_ref[...]             # (B, 1)==(1, bR)
    upd = upd_ref[...]                                # (B, D) pinned
    if tab.dtype == jnp.int32:
        delta = _byte_dots(onehot.astype(jnp.float32).astype(jnp.bfloat16),
                           upd.astype(jnp.int32), _TN)
    else:
        delta = jax.lax.dot_general(
            onehot.astype(tab.dtype), upd.astype(tab.dtype), _TN,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=tab.dtype)
    o_ref[...] = tab + delta


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def emb_scatter_add(table: jnp.ndarray, ids: jnp.ndarray,
                    idx: jnp.ndarray, upd: jnp.ndarray, *,
                    block_r: int = 1024,
                    interpret: bool = False) -> jnp.ndarray:
    """Segment-sum ``upd`` rows [B, D] into [R, D] table slots keyed by
    global id match; duplicate idx entries accumulate.  int32 tables are
    exact for B <= 65,793 (per-byte sums below 2^24)."""
    r, d = table.shape
    (b,) = idx.shape
    assert upd.shape == (b, d), (upd.shape, (b, d))
    br = min(block_r, r)
    assert r % br == 0, (r, br)
    return pallas_call(
        _scatter_kernel,
        name="emb_scatter_add",
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, br), lambda i: (0, i)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),   # batch ids pinned
            pl.BlockSpec((b, d), lambda i: (0, 0)),   # updates pinned
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), table.dtype),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(table, ids.reshape(1, r), idx.reshape(b, 1), upd)
