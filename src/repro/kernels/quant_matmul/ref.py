"""Pure-jnp oracle for the quantized matmul kernel.

Semantics: C = (A_q @ B_q) * (a_scale * b_scale), accumulated in int32 —
the TPU-native analogue of the paper's hybrid-precision dot product
(LIN-HYB/LIN-BUI: 8-bit multiplies feeding wider accumulators).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.fixed_point import _shift_round


def quant_matmul_ref(a_q: jnp.ndarray, b_q: jnp.ndarray,
                     a_scale: jnp.ndarray, b_scale: jnp.ndarray,
                     out_dtype=jnp.float32) -> jnp.ndarray:
    """a_q: int8 [M, K]; b_q: int8 [K, N];
    a_scale: [] or [M, 1]; b_scale: [] or [1, N] (per-channel)."""
    acc = jnp.dot(a_q.astype(jnp.int32), b_q.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * (a_scale * b_scale)).astype(out_dtype)


def int_matmul_ref(a_q: jnp.ndarray, b_q: jnp.ndarray) -> jnp.ndarray:
    """Raw int32 accumulator (no dequant), for exactness tests."""
    return jnp.dot(a_q.astype(jnp.int32), b_q.astype(jnp.int32),
                   preferred_element_type=jnp.int32)


def fx_matvec_ref(x_blk: jnp.ndarray, w_q: jnp.ndarray,
                  frac_bits: int) -> jnp.ndarray:
    """``fixed_point.fx_dot`` over the blocked layout: int32[nb, F8, bn]
    x int32[F] -> int32[nb, bn], reduced over the feature axis in place
    (the features past ``F`` weighted zero)."""
    w = jnp.pad(w_q.astype(jnp.int32), (0, x_blk.shape[-2] - w_q.shape[0]))
    prod = x_blk.astype(jnp.int32) * w[:, None]
    return jnp.sum(_shift_round(prod, frac_bits), axis=-2)
