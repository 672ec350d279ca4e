"""Dispatchable wrappers around the quantized matmul kernel family.

Ops (registered with :mod:`repro.kernels.dispatch`):

``quant_matmul`` : dequantizing int8 matmul (kernel or XLA ref path)
``int_matmul``   : raw int8 x int8 -> int32 accumulator
``fx_matvec``    : Q-format row-dot with pre-accumulation rounding over
                   the blocked feature-major layout the int32 GD view
                   is stored in (``to_feature_blocks``) — the
                   kernel-tier path of the LIN/LOG INT32 versions'
                   matmul (bit-identical to ``fixed_point.fx_dot``)
``quant_dense``  : float-in/float-out PIM-style dense layer — quantizes
                   activations on the fly (per-tensor) against int8
                   weights (per-output-channel scales), the direct
                   TPU analogue of LIN-HYB feeding an LM linear layer.

The ``jnp_ref`` backend routes to the pure-jnp oracles; that path is
what the multi-pod dry-run lowers (Mosaic kernels only lower for real
TPU targets — DESIGN.md §6), and XLA fuses it into a single int8 MXU
matmul on TPU anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantization import symmetric_quantize
from ..dispatch import legacy_launch, register_op
from .kernel import fx_matvec as _fx_matvec_kernel
from .kernel import int_matmul
from .ref import fx_matvec_ref, int_matmul_ref, quant_matmul_ref


def quant_matmul(a_q, b_q, a_scale, b_scale, *, backend=None,
                 use_pallas: bool = None, interpret: bool = None,
                 out_dtype=jnp.float32):
    """Dequantizing int8 matmul.  ``backend`` None = auto-select
    (``jnp_ref`` off-TPU; the old default was the interpret kernel —
    pass ``use_pallas=True`` explicitly to force the kernel path)."""
    return legacy_launch("quant_matmul", a_q, b_q, a_scale, b_scale,
                         backend=backend, use_pallas=use_pallas,
                         interpret=interpret, out_dtype=out_dtype)


def fx_matvec(x_blk, w_q, frac_bits: int, *, backend=None,
              use_pallas: bool = None, interpret: bool = None):
    """Q(f)[nb, F8, bn] . Q(f)[F] -> Q(f)[nb, bn] with per-product
    rounding, over the blocked layout of ``kernel.to_feature_blocks``."""
    return legacy_launch("fx_matvec", x_blk, w_q, frac_bits,
                         backend=backend, use_pallas=use_pallas,
                         interpret=interpret)


def _fx_matvec_pallas(x_blk, w_q, frac_bits: int, *, interpret: bool = True):
    return _fx_matvec_kernel(x_blk, w_q, frac_bits=frac_bits,
                             interpret=interpret)


def _int_matmul_ref_op(a_q, b_q, *, bm=128, bn=128, bk=128):
    del bm, bn, bk  # jnp oracle needs no tiling
    return int_matmul_ref(a_q, b_q)


def _int_matmul_pallas(a_q, b_q, *, interpret: bool = True, bm=128,
                       bn=128, bk=128):
    return int_matmul(a_q, b_q, bm=bm, bn=bn, bk=bk, interpret=interpret)


def _quant_matmul_pallas(a_q, b_q, a_scale, b_scale, *,
                         interpret: bool = True, out_dtype=jnp.float32):
    acc = int_matmul(a_q, b_q, interpret=interpret)
    return (acc.astype(jnp.float32) * (a_scale * b_scale)).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("backend", "use_pallas",
                                             "interpret"))
def quant_dense(x: jnp.ndarray, w_q: jnp.ndarray, w_scale: jnp.ndarray,
                *, backend=None, use_pallas: bool = None,
                interpret: bool = None) -> jnp.ndarray:
    """x: float [..., K]; w_q: int8 [K, N]; w_scale: [1, N] per-channel.

    Activations are quantized per-tensor on the fly (symmetric), matmul'd
    in int8 -> int32, and dequantized — matching the paper's quantize-the-
    dataset-once + integer-kernel flow, applied per layer.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    x_q, xp = symmetric_quantize(x2, bits=8)
    out = quant_matmul(x_q, w_q, xp.scale, w_scale, backend=backend,
                       use_pallas=use_pallas, interpret=interpret)
    return out.reshape(*lead, -1).astype(x.dtype)


register_op("int_matmul", family="quant_matmul",
            pallas=_int_matmul_pallas, ref=_int_matmul_ref_op)
register_op("quant_matmul", family="quant_matmul",
            pallas=_quant_matmul_pallas, ref=quant_matmul_ref)
register_op("fx_matvec", family="quant_matmul",
            pallas=_fx_matvec_pallas, ref=fx_matvec_ref)
