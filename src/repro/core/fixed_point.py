"""Fixed-point (Q-format) arithmetic, the numeric substrate of the paper.

The UPMEM DPUs evaluated in the paper have no FPU: the paper's LIN-INT32 /
LOG-INT32 versions represent real values as 32-bit fixed point Q(m.f)
integers (value = int / 2**f).  The hybrid-precision versions (LIN-HYB /
LOG-HYB-LUT) use 8-bit inputs x 16-bit weights with 16/32-bit accumulation.

TPU note: JAX defaults to 32-bit integers and TPUs have no fast int64, so —
unlike the UPMEM code, which leans on 64-bit accumulators — every helper
here is written so intermediate products *provably* fit in int32:
multiplications shift right by ``frac_bits`` immediately after each product
(the paper's DPU code does the same for its 32-bit dot products).  Where the
paper uses int64 accumulators (K-Means per-cluster sums), core/kmeans.py
instead narrows the quantization range so exact int32 accumulation holds;
see the module docstring there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def mul_round_f32(a, b):
    """Correctly-rounded float32 product with *pinned* two-rounding
    semantics for the consumer: ``x - mul_round_f32(s, g)`` computes
    round(x - round(s*g)) in EVERY execution context.

    A plain f32 ``s * g`` adjacent to a subtract gets FMA-contracted by
    XLA CPU inside jitted computations (observed: jit == single-rounding
    fma while eager/numpy == two roundings, diverging by 1 ULP per step
    and shape-dependently — neither ``optimization_barrier`` nor bitcast
    round-trips block the contraction).  The fused step engine
    (core/pim.py StepProgram) needs the compiled scan to be bit-identical
    to the eager per-step loop, so the product is computed exactly in
    float64 (24-bit mantissas -> the f64 product is exact) and rounded
    once by the down-convert; a convert cannot be contracted into the
    f32 subtract, so the two roundings survive any fusion decision.

    CAVEAT — inside a jit trace BOTH operands must be *traced* values
    (arguments or carry elements), not closed-over constants: every
    concrete float64 value — eagerly up-converted constants, weak python
    scalars, even literals — is canonicalized back to f32 when the jaxpr
    is lowered (the x64 context is long exited by then), leaving a
    mixed-dtype multiply that fails MLIR verification.  The fused
    trainers therefore thread the update scale through the scan carry.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    with jax.enable_x64(True):
        p = a.astype(jnp.float64) * b.astype(jnp.float64)
        return p.astype(jnp.float32)


def to_fixed(x, frac_bits: int, dtype=jnp.int32):
    """float -> Q(frac_bits) fixed point, saturating at the dtype range."""
    info = jnp.iinfo(dtype)
    scaled = jnp.round(jnp.asarray(x, jnp.float32) * np.float32(1 << frac_bits))
    return jnp.clip(scaled, info.min, info.max).astype(dtype)


def from_fixed(q, frac_bits: int):
    return q.astype(jnp.float32) / np.float32(1 << frac_bits)


def fx_sum(t, axis=None):
    """Exact sum of int32 Q-format terms ``t`` as an int32 ``(..., 2)``
    pair ``(hi, lo)`` worth ``hi * 256 + lo`` with ``0 <= lo < 256``.

    A gradient sums one term per training row, and at the paper's
    dataset sizes it outgrows int32 (SUSY's first LIN step has a bias
    gradient of -2.56e9).  The pair stays exact while a shard holds
    fewer than 2^23 rows and the summed ``|t|`` stays below 2^39, and
    pairs from many shards add element-wise without overflow — still
    integers, so every reduce order gives the same pair."""
    return fx_pair(jnp.sum(t >> 8, axis=axis), jnp.sum(t & 255, axis=axis))


def fx_pair(hi, lo):
    """The :func:`fx_sum` pair of a high-byte sum ``hi`` and a low-byte
    sum ``lo``: the low sum's excess over 255 carried into ``hi``."""
    return jnp.stack([hi + (lo >> 8), lo & 255], axis=-1)


def fx_pair_value(pair) -> np.ndarray:
    """Exact float64 value of an integer :func:`fx_sum` pair, on the
    host (float64 holds every sum below 2^53)."""
    p = np.asarray(pair, np.float64)
    return p[..., 0] * 256 + p[..., 1]


def from_fixed_sum(pair, frac_bits: int):
    """float32 value of a (summed) :func:`fx_sum` pair in Q(frac_bits)."""
    p = jnp.asarray(pair)
    return ((p[..., 0].astype(jnp.float32) * np.float32(256)
             + p[..., 1].astype(jnp.float32)) / np.float32(1 << frac_bits))


def saturate(x, dtype):
    info = jnp.iinfo(dtype)
    return jnp.clip(x, info.min, info.max).astype(dtype)


def fx_mul(a, b, frac_bits: int, out_dtype=jnp.int32):
    """Q(f) * Q(f) -> Q(f) with the post-product shift the DPU code uses.

    Inputs are widened to int32 for the product; callers must keep operand
    magnitudes below 2**(31 - frac_bits) (asserted in tests, guaranteed by
    the dataset quantizers which produce |x| < 2**frac_bits ranges).
    """
    prod = a.astype(jnp.int32) * b.astype(jnp.int32)
    return _shift_round(prod, frac_bits).astype(out_dtype)


def _shift_round(x, shift: int):
    """Arithmetic right-shift with round-to-nearest (ties toward +inf).

    Plain ``>>`` floors, which introduces a systematic negative bias that
    visibly degrades gradient-descent convergence; the DPU library rounds.
    """
    if shift == 0:
        return x
    return (x + (1 << (shift - 1))) >> shift


def fx_dot(x_q, w_q, frac_bits: int):
    """Fixed-point dot product along the last axis: Q(f) · Q(f) -> Q(f).

    Each product is shifted back to Q(f) *before* accumulation (as in the
    paper's 32-bit DPU kernels), so the int32 accumulator holds
    sum_i round(x_i * w_i / 2**f), exactly reproducible across backends.
    """
    prod = x_q.astype(jnp.int32) * w_q.astype(jnp.int32)
    return jnp.sum(_shift_round(prod, frac_bits), axis=-1)


def fx_dot_hybrid(x_q8, w_q16, x_frac: int, w_frac: int, out_frac: int,
                  acc_dtype=jnp.int16):
    """Hybrid-precision dot product (paper's LIN-HYB / LOG-HYB-LUT).

    8-bit inputs x 16-bit weights; products are rescaled to Q(out_frac) and
    accumulated in *16-bit* (``acc_dtype``) with saturation — the paper
    states "the dot product result is 16-bit width", which is exactly the
    precision loss that raises HYB training error (Fig. 6/7, §5.1).
    Returns Q(out_frac) in int32 (the widened final value).
    """
    prod = x_q8.astype(jnp.int32) * w_q16.astype(jnp.int32)  # Q(x_frac+w_frac)
    shift = x_frac + w_frac - out_frac
    prod = _shift_round(prod, shift) if shift > 0 else prod << (-shift)
    # saturating 16-bit accumulation, sequentially over the feature axis
    info = jnp.iinfo(acc_dtype)
    acc = jnp.zeros(prod.shape[:-1], jnp.int32)
    # feature counts are small (paper uses 16); unrolled cumulative clip
    # models the DPU's 16-bit register accumulation faithfully.
    n = prod.shape[-1]
    for i in range(n):
        acc = jnp.clip(acc + prod[..., i], info.min, info.max)
    return acc


def fx_recip(d_q, frac_bits: int, iters: int = 3):
    """Fixed-point reciprocal via Newton-Raphson (DPUs emulate division).

    Input Q(f) > 0; returns Q(f) approximation of 1/d.  Seed from a
    float-free shift-based estimate: 1/d ~= 2**(2f) / d via integer divide
    (DPU runtime also exposes integer division, just slowly).
    """
    one = jnp.int32(1 << frac_bits)
    d = d_q.astype(jnp.int32)
    x = (jnp.int32(1) << (2 * frac_bits)) // jnp.maximum(d, 1)
    for _ in range(iters):
        # x <- x * (2 - d*x)   in Q(f)
        dx = _shift_round(d * x, frac_bits)
        x = _shift_round(x * (2 * one - dx), frac_bits)
    return x
