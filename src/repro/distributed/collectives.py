"""Hierarchical collectives for the multi-pod mesh (DESIGN.md §5).

Cross-pod links are slower than intra-pod ICI, so the flat
all-reduce over ("pod","data") is decomposed into:

  1. reduce-scatter within the pod  (fast links carry the bulk)
  2. all-reduce of the scattered shards across pods
     (slow links carry 1/pod_size of the bytes)
  3. all-gather within the pod

This is the standard two-level schedule (NCCL tree / TPU hierarchical);
with GSPMD the flat psum often lowers similarly, but the explicit form
pins the schedule and is what the explicit-DP trainer uses on multi-pod
meshes.  Equivalence with the flat psum is tested.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def hierarchical_psum(x: jnp.ndarray, *, intra_axis: str = "data",
                      inter_axis: str = "pod") -> jnp.ndarray:
    """Sum over (inter_axis x intra_axis) via RS -> inter-AR -> AG.

    Must run inside shard_map with both axes manual.  Requires the
    leading dim of ``x`` to be divisible by the intra-axis size (pad at
    call site otherwise; the trainer's grad vectors satisfy this).
    """
    n_intra = jax.lax.axis_size(intra_axis)
    lead = x.shape[0]
    if lead % n_intra != 0:
        # fall back to the flat reduction for awkward shapes
        return jax.lax.psum(x, (inter_axis, intra_axis))
    # 1. reduce-scatter within the pod over the leading dim
    shard = jax.lax.psum_scatter(x, intra_axis, scatter_dimension=0,
                                 tiled=True)
    # 2. all-reduce the shard across pods (1/n_intra of the bytes)
    shard = jax.lax.psum(shard, inter_axis)
    # 3. all-gather within the pod
    return jax.lax.all_gather(shard, intra_axis, axis=0, tiled=True)


def hierarchical_pmean(x: jnp.ndarray, *, intra_axis: str = "data",
                       inter_axis: str = "pod") -> jnp.ndarray:
    total = jax.lax.axis_size(intra_axis) * jax.lax.axis_size(inter_axis)
    return hierarchical_psum(x, intra_axis=intra_axis,
                             inter_axis=inter_axis) / total


def cross_pod_bytes(n_bytes: int, pod_size: int) -> tuple[int, int]:
    """(flat slow-link bytes, hierarchical slow-link bytes) per device —
    the napkin justification: hierarchical moves 1/pod_size as much over
    the slow links."""
    return n_bytes, n_bytes // pod_size
