"""Dispatchable wrappers around the sparse gather/scatter-add kernels.

Ops (registered with :mod:`repro.kernels.dispatch`):

``emb_gather``      : shard-local embedding row lookup, zeros for rows
                      the shard does not own — the per-core forward leg
                      of the EMB workload (summed by the fabric reduce).
``emb_scatter_add`` : duplicate-index-safe batched row update (segment
                      sum) — the eager apply and the deferred flush both
                      route through this single op.

The pallas wrappers pad ragged axes (batch and rows for gather, rows
for scatter) with the sentinel ids from :mod:`.ref`, which can never
match a real lookup — padded work contributes exact zeros and is sliced
off.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import register_op
from .kernel import emb_gather as _gather_kernel
from .kernel import emb_scatter_add as _scatter_kernel
from .ref import IDX_PAD, ROW_PAD_ID, emb_gather_ref, emb_scatter_add_ref


def _pad_to(x, n, fill):
    if x.shape[0] == n:
        return x
    pad = jnp.full((n - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad], axis=0)


def _emb_gather_ref(table, ids, idx, *, block_b: int = 512):
    del block_b  # jnp oracle needs no tiling
    return emb_gather_ref(table, ids, idx)


def _emb_gather_pallas(table, ids, idx, *, interpret: bool = True,
                       block_b: int = 512, block_r: int = 1024):
    b, r = idx.shape[0], table.shape[0]
    if b == 0:  # empty batch: nothing to look up
        return jnp.zeros((0, table.shape[1]), table.dtype)
    bb, br = min(block_b, b), min(block_r, r)
    b_pad = -(-b // bb) * bb
    r_pad = -(-r // br) * br
    out = _gather_kernel(_pad_to(table, r_pad, 0),
                         _pad_to(ids, r_pad, ROW_PAD_ID),
                         _pad_to(idx, b_pad, IDX_PAD),
                         block_b=bb, block_r=br, interpret=interpret)
    return out[:b]


def _emb_scatter_add_ref(table, ids, idx, upd, *, block_r: int = 1024):
    del block_r
    return emb_scatter_add_ref(table, ids, idx, upd)


def _emb_scatter_add_pallas(table, ids, idx, upd, *,
                            interpret: bool = True, block_r: int = 1024):
    if idx.shape[0] == 0:  # empty batch: table unchanged (ref adds 0)
        return table + jnp.zeros_like(table)
    r = table.shape[0]
    br = min(block_r, r)
    r_pad = -(-r // br) * br
    out = _scatter_kernel(
        _pad_to(table, r_pad, 0), _pad_to(ids, r_pad, ROW_PAD_ID),
        idx, upd, block_r=br, interpret=interpret)
    return out[:r]


def emb_gather(table, ids, idx, *, backend=None, block_b: int = 512):
    """Shard-local lookup: [R, D] x [B] global ids -> [B, D] partials."""
    from ..dispatch import launch
    return launch("emb_gather", table, ids, idx, backend=backend,
                  block_b=block_b)


def emb_scatter_add(table, ids, idx, upd, *, backend=None,
                    block_r: int = 1024):
    """Duplicate-safe batched row update: segment-sum [B, D] into [R, D]."""
    from ..dispatch import launch
    return launch("emb_scatter_add", table, ids, idx, upd,
                  backend=backend, block_r=block_r)


register_op("emb_gather", family="sparse_gather",
            pallas=_emb_gather_pallas, ref=_emb_gather_ref)
register_op("emb_scatter_add", family="sparse_gather",
            pallas=_emb_scatter_add_pallas, ref=_emb_scatter_add_ref)
