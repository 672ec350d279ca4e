"""Pallas TPU kernel: int8 x int8 -> int32 tiled matmul with dequant.

TPU adaptation of the paper's hybrid-precision multiply (LIN-HYB / LIN-BUI,
Listing 1): where the DPU replaces emulated 32-bit multiplies with native
8-bit built-ins, the TPU's native fast path is the MXU int8 systolic pass
with int32 accumulation.  Tiling: (bm x bk) x (bk x bn) blocks staged
HBM->VMEM by the BlockSpec machinery, int32 accumulator held in a VMEM
scratch across the K grid dimension.

Block shapes default to MXU-aligned (128, 128, 128); int8 operands allow
(32, 128)-packed tiles, so bk=256 is also profitable on real hardware.
Validated with interpret=True on CPU (see tests/test_kernels_quant_matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl, vmem_scratch


def _quant_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU; only the accumulator is int32
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def int_matmul(a_q: jnp.ndarray, b_q: jnp.ndarray, *, bm: int = 128,
               bn: int = 128, bk: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """int8[M,K] @ int8[K,N] -> int32[M,N] via pl.pallas_call."""
    m, k = a_q.shape
    k2, n = b_q.shape
    assert k == k2, (a_q.shape, b_q.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"shape ({m},{k})x({k},{n}) not divisible by blocks ({bm},{bn},{bk})"
    n_k = k // bk

    grid = (m // bm, n // bn, n_k)
    return pallas_call(
        functools.partial(_quant_matmul_kernel, n_k=n_k),
        name="int_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[vmem_scratch((bm, bn), jnp.int32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(a_q, b_q)


#: rows of one ``fx_matvec`` block: the lane axis of the blocked
#: feature-major layout the int32 GD view is stored in
BLOCK_N = 1024
LANES = 128
SUBLANES = 8


def block_rows(n: int) -> int:
    """Rows of a block for a shard of ``n`` rows: ``BLOCK_N``, or, for a
    shard of fewer than ``2 * BLOCK_N`` rows, half of them rounded up to
    whole lanes — two blocks where the shard has more than a lane's rows,
    so the grid pipelines one block's fetch behind the other's compute
    and half a shard's blocks are half its rows."""
    return min(BLOCK_N, -(-max(n, 1) // (2 * LANES)) * LANES)


def to_feature_blocks(a: jnp.ndarray, bn: int) -> jnp.ndarray:
    """Rows of each shard into ``fx_matvec``'s layout: ``(C, n)`` ->
    ``(C, nb, bn)`` and ``(C, n, F)`` -> ``(C, nb, F8, bn)``, ``nb =
    ceil(n / bn)``, the rows of each block on the last (lane) axis.
    Rows stay on axis 1.  Rows past ``n`` are zero, and so are the
    features past ``F`` up to ``F8``, ``F`` rounded up to whole sublanes:
    for an ``F`` that is no multiple of 8 the TPU's default layout of
    the array moves the cores axis into the sublanes to save ``F``'s
    padding, and every launch would copy the view back into the
    ``(F, bn)`` tiles the kernel reads."""
    c, n = a.shape[:2]
    nb = -(-n // bn)
    pad = [(0, 0), (0, nb * bn - n)]
    if a.ndim == 3:
        pad.append((0, -a.shape[2] % SUBLANES))
    a = jnp.pad(a, pad)
    return jnp.moveaxis(a.reshape(c, nb, bn, *a.shape[2:]), 2, -1)


def _fx_matvec_kernel(xt_ref, w_ref, o_ref, *, frac_bits: int):
    x = xt_ref[...].astype(jnp.int32)                # (F8, bn) rows on lanes
    w = w_ref[...].astype(jnp.int32)                 # (F8, 1)
    prod = x * w                                     # Q(2f)
    if frac_bits:
        prod = (prod + (1 << (frac_bits - 1))) >> frac_bits
    o_ref[...] = jnp.sum(prod, axis=0, keepdims=True)  # (1, bn) Q(f)


@functools.partial(jax.jit, static_argnames=("frac_bits", "interpret"))
def fx_matvec(x_blk: jnp.ndarray, w_q: jnp.ndarray, *, frac_bits: int,
              interpret: bool = False) -> jnp.ndarray:
    """Q-format row-dot over the blocked layout: int32[nb, F8, bn] x
    int32[F] -> int32[nb, bn] (``F <= F8``, the features past ``F``
    weighted zero), each product shifted back to Q(frac_bits) with
    round-to-nearest BEFORE accumulation (the paper's 32-bit DPU
    dot-product ordering; bit-identical to ``fixed_point.fx_dot`` on the
    rows).  VPU work: each grid step reads one contiguous ``(F8, bn)``
    block, rows on the lanes, and writes its ``bn`` dots lane-dense
    (also under ``vmap``, which adds a squeezed cores axis in front);
    the weight vector stays pinned.  The input is stored in this layout
    (:func:`to_feature_blocks`), so nothing is padded or transposed per
    call — the kernel-tier path of the LIN/LOG INT32 versions' matmul."""
    nb, f, bn = x_blk.shape
    assert w_q.ndim == 1 and w_q.shape[0] <= f, (x_blk.shape, w_q.shape)
    w = jnp.pad(w_q, (0, f - w_q.shape[0]))  # zero weights on pad features
    out = pallas_call(
        functools.partial(_fx_matvec_kernel, frac_bits=frac_bits),
        name="fx_matvec",
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, f, bn), lambda i: (i, 0, 0)),
            pl.BlockSpec((f, 1), lambda i: (0, 0)),  # weights pinned
        ],
        out_specs=pl.BlockSpec((None, 1, bn), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, bn), jnp.int32),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(x_blk, w.reshape(f, 1))
    return out.reshape(nb, bn)
