"""Pallas TPU kernel: LUT-based sigmoid with the table pinned in VMEM.

TPU adaptation of the paper's WRAM-resident sigmoid LUT (§3.2, Fig. 4):
  DPU WRAM (64 KB)  ->  VMEM: the table (20 x 1024 int16 entries at the
  default geometry, the paper's 40 KB) rides along as a full-block input
  that the BlockSpec machinery keeps resident across the whole grid.
The "MRAM" variant of the paper corresponds to *not* using this kernel and
letting XLA issue an HBM gather (ops.lut_sigmoid with placement="hbm").

The lookup is an in-register lane gather: ``jnp.take_along_axis`` over
the lane axis of two same-shape ``(8, 128)`` blocks lowers to Mosaic's
``tpu.dynamic_gather``.  The gather moves 32-bit lanes, so the table is
laid out as int32 words, each holding two int16 entries: word ``[p, l]``
carries entry ``256p + l`` in its low half and ``256p + 128 + l`` in its
high half.  For each ``(8, 128)`` tile of logits and each table row
``p``, the kernel gathers row ``p`` by the index's low 7 bits and keeps
it where the index's pair of rows is ``p``; bit 7 of the index then
picks the half, sign-extended.  Every output is an exact selection.
Per tile: index clamp, the row selections, reflection for negative
inputs — the DPU kernel's three steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl

LANES = 128
SUBLANES = 8
#: most logit rows a block holds: 512 KiB of int32, so the
#: double-buffered input and output blocks take 2 MiB of VMEM
BLOCK_ROWS = 1024


def table_words(table: jnp.ndarray) -> jnp.ndarray:
    """int16 [n] -> int32 [ceil(n / 256), 128], two entries a word: row
    ``p`` holds entries ``256p .. 256p+127`` in its low halves and
    ``256p+128 .. 256p+255`` in its high halves; the tail is unreachable
    zeros."""
    n = table.shape[0]
    rows = pl.cdiv(n, 2 * LANES)
    t = jnp.pad(table.astype(jnp.int32),
                (0, rows * 2 * LANES - n)).reshape(rows, 2, LANES)
    return (t[:, 1] << 16) | (t[:, 0] & 0xFFFF)


def row_blocks(rows: int, block_rows: int = BLOCK_ROWS) -> tuple[int, int]:
    """``(blocks, rows a block)`` for ``rows`` rows of logits: as few
    blocks as ``block_rows`` (rounded down to a multiple of 8) allows,
    each a multiple of 8 rows, so the padding is under 8 rows a block.
    The SUSY per-core 611 rows take one block of 616."""
    cap = max(block_rows // SUBLANES, 1) * SUBLANES
    blocks = pl.cdiv(rows, cap)
    return blocks, pl.cdiv(pl.cdiv(rows, blocks), SUBLANES) * SUBLANES


def _lut_sigmoid_kernel(x_ref, tab_ref, o_ref, *, n_entries: int,
                        value_frac: int):
    one = jnp.int32(1 << value_frac)

    def tile(i, carry):
        r = pl.multiple_of(i * SUBLANES, SUBLANES)
        xq = x_ref[pl.ds(r, SUBLANES), :]           # (8, 128) int32
        idx = jnp.minimum(jnp.abs(xq), n_entries - 1)
        lane, pair = idx & (LANES - 1), idx >> 8
        w = None
        for p in range(tab_ref.shape[0]):
            g = jnp.take_along_axis(
                jnp.broadcast_to(tab_ref[p:p + 1, :], xq.shape), lane,
                axis=1, mode="promise_in_bounds")
            w = g if w is None else jnp.where(pair == p, g, w)
        v = jnp.where((idx & LANES) != 0, w >> 16, (w << 16) >> 16)
        o_ref[pl.ds(r, SUBLANES), :] = jnp.where(xq < 0, one - v, v)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // SUBLANES, tile, 0)


@functools.partial(jax.jit, static_argnames=("value_frac", "block_rows",
                                             "interpret"))
def lut_sigmoid_vmem(x_q: jnp.ndarray, table: jnp.ndarray, *,
                     value_frac: int = 15, block_rows: int = BLOCK_ROWS,
                     interpret: bool = False) -> jnp.ndarray:
    """x_q: int32 Q(f) [rows, 128]; table: int16 [n] -> int32 [rows, 128].

    The whole table is one VMEM block shared by every grid step; rows are
    tiled in blocks of ``min(block_rows, rows)``, a multiple of 8 that
    divides ``rows``, so arbitrarily large activations stream through.
    """
    rows, lanes = x_q.shape
    assert lanes == LANES, x_q.shape
    br = min(block_rows, rows)
    assert rows % br == 0 and br % SUBLANES == 0, (rows, br)
    assert table.dtype == jnp.int16, table.dtype
    tab = table_words(table)
    return pallas_call(
        functools.partial(_lut_sigmoid_kernel, n_entries=table.shape[0],
                          value_frac=value_frac),
        name="lut_sigmoid",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, lanes), lambda i: (i, 0)),
            pl.BlockSpec(tab.shape, lambda i: (0, 0)),  # pinned
        ],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(x_q.astype(jnp.int32), tab)
