"""Pure-jnp oracles for the sparse gather / scatter-add family.

Both ops run against a *shard* of a row-sharded embedding table: the
shard holds rows ``table[r]`` whose global row ids are ``ids[r]``
(``ROW_PAD_ID`` marks padding slots past the vocabulary tail).  Lookups
arrive as global ids ``idx[b]``; a shard answers with zeros for rows it
does not own, so summing the per-shard partials across cores (the
fabric reduce) reconstructs the full gathered rows.

The one-hot matmul formulation is the load-bearing choice:

* ``gather``: each one-hot row has at most one 1 (ids are unique within
  a shard), so the "sum" is a pure selection — exact in every dtype.
* ``scatter_add``: duplicate batch indices hit the SAME table row's
  one-hot column and are summed by a single ``dot_general`` over the
  whole batch axis,
  i.e. a segment-sum — duplicate-safe with one fixed reduction order
  shared by the Pallas kernel, so ref and kernel stay bit-exact.

Matmuls run at float32 precision (``Precision.HIGHEST``) and
``preferred_element_type`` pins the accumulator to the table dtype:
int32 tables accumulate exactly in int32 (the Q-format fixed-point
path); float tables accumulate in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: global-id sentinel for padded table slots (vocab tail rounded up to
#: the shard grid); never matches a real lookup id (those are >= 0).
ROW_PAD_ID = -1
#: lookup-id sentinel for padded batch slots (ragged batch tails);
#: distinct from ROW_PAD_ID so padded lookups cannot hit padded rows.
IDX_PAD = -2


def _onehot_dot(onehot, rows, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(
        onehot, rows, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=rows.dtype)


def emb_gather_ref(table: jnp.ndarray, ids: jnp.ndarray,
                   idx: jnp.ndarray) -> jnp.ndarray:
    """table: [R, D]; ids: int32 [R]; idx: int32 [B] -> [B, D].

    ``out[b] = table[r]`` where ``ids[r] == idx[b]``, else zeros (the
    row lives on another shard, or ``idx[b]`` is an ``IDX_PAD``)."""
    onehot = (idx[:, None] == ids[None, :]).astype(table.dtype)  # (B, R)
    return _onehot_dot(onehot, table)


def emb_scatter_add_ref(table: jnp.ndarray, ids: jnp.ndarray,
                        idx: jnp.ndarray,
                        upd: jnp.ndarray) -> jnp.ndarray:
    """table: [R, D]; ids: int32 [R]; idx: int32 [B]; upd: [B, D]
    -> [R, D] with ``out[r] = table[r] + sum_b [ids[r]==idx[b]] upd[b]``
    (duplicate indices sum — segment-sum semantics)."""
    # (B, R) one-hot contracted over the batch axis: the Pallas kernel's
    # orientation, so both reduce each row's duplicates in one order
    onehot = (idx[:, None] == ids[None, :]).astype(table.dtype)  # (B, R)
    return table + _onehot_dot(onehot, upd.astype(table.dtype),
                               (((0,), (0,)), ((), ())))
