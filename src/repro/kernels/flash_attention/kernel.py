"""Pallas TPU kernel: blocked causal attention with online softmax (fwd).

VMEM tiling: (bq x d) query blocks stay resident while (bk x d) key/value
blocks stream through the sequential kv grid axis; running max / sum /
accumulator live in VMEM scratch (the classic flash pattern re-tiled for
the MXU: all three matmuls are 128-aligned by default).

Causality is enforced two ways: (1) whole kv blocks strictly above the
diagonal are skipped via pl.when (no MXU work issued — same trick as the
paper's "skip what you can decide cheaply on the host"), and (2) the
diagonal block applies an element mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl, vmem_scratch

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, n_kv: int, bq: int, bk: int,
                  q_offset: int, window: int):
    """window: 0 = unbounded; >0 = sliding-window attention (hymba SWA):
    query at absolute position p attends kv in (p - window, p]."""
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = i * bq + q_offset          # absolute position of first q row
    block_needed = (not causal) or (j * bk <= q_first + bq - 1)
    if window:
        # kv block entirely below the EARLIEST query's window start -> skip
        in_window = (j + 1) * bk - 1 > q_first - window
        block_needed = jnp.logical_and(block_needed, in_window) \
            if causal else in_window

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window:
            qpos = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            mask = qpos >= kpos if causal else (qpos == qpos)
            if window:
                mask &= (qpos - kpos) < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _store():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "bq", "bk", "q_offset", "window", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    q_offset: int = 0, window: int = 0,
                    interpret: bool = False) -> jnp.ndarray:
    """q [BH, Sq, D]; k,v [BH, Skv, D] -> [BH, Sq, D] (heads pre-flattened).

    ``q_offset`` positions q rows at absolute offset within the kv sequence
    (decode: Skv - Sq).  ``window`` > 0 enables sliding-window attention
    with out-of-window kv blocks skipped entirely (no MXU work issued).
    """
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    n_kv = skv // bk
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // bq, n_kv)
    return pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          n_kv=n_kv, bq=bq, bk=bk, q_offset=q_offset,
                          window=window),
        name="mha",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            vmem_scratch((bq,), jnp.float32),
            vmem_scratch((bq,), jnp.float32),
            vmem_scratch((bq, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(q, k, v)
