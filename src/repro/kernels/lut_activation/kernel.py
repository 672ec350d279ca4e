"""Pallas TPU kernel: LUT-based sigmoid with the table pinned in VMEM.

TPU adaptation of the paper's WRAM-resident sigmoid LUT (§3.2, Fig. 4):
  DPU WRAM (64 KB)  ->  VMEM: the table (20 x 1024 int16 entries at the
  default geometry) rides along as a full-block input that the BlockSpec
  machinery keeps resident across the whole grid.
The "MRAM" variant of the paper corresponds to *not* using this kernel and
letting XLA issue an HBM gather (ops.lut_sigmoid with placement="hbm").

Mosaic has no general gather, so the lookup is two selections, one on
the MXU and one on the VPU.  The table is laid out as rows of 128
entries; for a row of 128 inputs, a one-hot matmul against the
transposed table brings each input's table row onto its lane, and a
compare-select over the 128 sublanes picks the entry.  Entries travel as
their high and low bytes (offset to [0, 255], exact in bfloat16), so
both selections are exact.  Per grid step: index clamp, the two
selections, reflection for negative inputs — the DPU kernel's three
steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..pallas_compat import pallas_call, pl

LANES = 128
_OFFSET = 1 << 15      # int16 entry + 2^15 -> [0, 2^16): two bytes


def table_planes(table: jnp.ndarray) -> jnp.ndarray:
    """int16 [n] -> bfloat16 [2 * 128, R]: column ``r`` holds entries
    ``r*128 .. r*128+127`` (high bytes in sublanes 0..127, low bytes in
    128..255); R is padded to a multiple of 128 with unreachable zeros."""
    n = table.shape[0]
    rows = -(-n // LANES)
    r_pad = -(-rows // LANES) * LANES
    u = jnp.zeros((r_pad * LANES,), jnp.int32).at[:n].set(
        table.astype(jnp.int32) + _OFFSET).reshape(r_pad, LANES).T
    return jnp.concatenate([u >> 8, u & 255]).astype(jnp.bfloat16)


def _lut_sigmoid_kernel(x_ref, tab_ref, o_ref, *, n_entries: int,
                        value_frac: int):
    planes = tab_ref[...]                        # (256, R) bf16
    r_pad = planes.shape[1]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (r_pad, LANES), 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    one = jnp.int32(1 << value_frac)

    def body(r, carry):
        xq = x_ref[pl.ds(r, 1), :]               # (1, 128) int32
        idx = jnp.minimum(jnp.abs(xq), n_entries - 1)
        oh = (row_id == idx // LANES).astype(jnp.float32).astype(
            jnp.bfloat16)                        # (R, 128)
        # exact in one bfloat16 pass: pinned against a caller's
        # default_matmul_precision
        g = jax.lax.dot_general(planes, oh, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        pick = lane_id == idx % LANES            # (128, 128)
        hi = jnp.sum(jnp.where(pick, g[:LANES], 0.0), axis=0,
                     keepdims=True)
        lo = jnp.sum(jnp.where(pick, g[LANES:], 0.0), axis=0,
                     keepdims=True)
        v = hi.astype(jnp.int32) * 256 + lo.astype(jnp.int32) - _OFFSET
        o_ref[pl.ds(r, 1), :] = jnp.where(xq < 0, one - v, v)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("value_frac", "block_rows",
                                             "interpret"))
def lut_sigmoid_vmem(x_q: jnp.ndarray, table: jnp.ndarray, *,
                     value_frac: int = 15, block_rows: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """x_q: int32 Q(f) [rows, 128]; table: int16 [n] -> int32 [rows, 128].

    The whole table is one VMEM block shared by every grid step; rows are
    tiled so arbitrarily large activations stream through.
    """
    rows, lanes = x_q.shape
    assert lanes == LANES, x_q.shape
    br = min(block_rows, rows)
    assert rows % br == 0, (rows, br)
    planes = table_planes(table)
    return pallas_call(
        functools.partial(_lut_sigmoid_kernel, n_entries=table.shape[0],
                          value_frac=value_frac),
        name="lut_sigmoid",
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, lanes), lambda i: (i, 0)),
            pl.BlockSpec(planes.shape, lambda i: (0, 0)),  # pinned
        ],
        out_specs=pl.BlockSpec((br, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        dimension_semantics=("parallel",),
        interpret=interpret,
    )(x_q.astype(jnp.int32), planes)
