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


def _fx_matvec_kernel(xt_ref, w_ref, o_ref, *, frac_bits: int):
    x = xt_ref[...].astype(jnp.int32)                # (F, bn) rows on lanes
    w = w_ref[...].astype(jnp.int32)                 # (F, 1)
    prod = x * w                                     # Q(2f)
    if frac_bits:
        prod = (prod + (1 << (frac_bits - 1))) >> frac_bits
    o_ref[...] = jnp.sum(prod, axis=0, keepdims=True)  # (1, bn) Q(f)


@functools.partial(jax.jit, static_argnames=("frac_bits", "block_n",
                                             "interpret"))
def fx_matvec(x_q: jnp.ndarray, w_q: jnp.ndarray, *, frac_bits: int,
              block_n: int = 1024, interpret: bool = False) -> jnp.ndarray:
    """Q-format row-dot: int32[N, F] x int32[F] -> int32[N], each product
    shifted back to Q(frac_bits) with round-to-nearest BEFORE accumulation
    (the paper's 32-bit DPU dot-product ordering; bit-identical to
    ``fixed_point.fx_dot``).  VPU work: the rows are transposed onto the
    lane axis so every block and the output are lane-dense (also under
    ``vmap``, which adds a squeezed cores axis in front), the weight
    vector stays pinned — the kernel-tier path of the LIN/LOG INT32
    versions' matmul."""
    n, f = x_q.shape
    assert w_q.shape == (f,), (x_q.shape, w_q.shape)
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    out = pallas_call(
        functools.partial(_fx_matvec_kernel, frac_bits=frac_bits),
        name="fx_matvec",
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((f, bn), lambda i: (0, i)),
            pl.BlockSpec((f, 1), lambda i: (0, 0)),  # weights pinned
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(x_q.T, w_q.reshape(f, 1))
    return out[0]
