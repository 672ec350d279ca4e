"""Pipeline parallelism (GPipe-style) over a mesh "stage" axis.

Completes the parallelism matrix (DP/TP/EP/FSDP are GSPMD-driven; PP needs
an explicit schedule): the layer stack is split into contiguous stages,
microbatches flow through a shard_map'd tick loop, and activations hop
stage-to-stage via ``jax.lax.ppermute``.  Because ppermute transposes to
the reverse permutation under AD, ``jax.grad`` *through* the pipelined
loop yields exactly the GPipe backward schedule — no hand-written
backward pass (validated bitwise against sequential execution in
tests/test_pipeline.py).

Scope: the embedding and LM head stay outside the pipelined region
(replicated or TP-sharded as usual); the pipeline carries the residual
stream [B_mb, S, d].  Bubble fraction is the standard
(n_stages - 1) / (n_micro + n_stages - 1); the tick loop issues compute
for invalid (bubble) slots and masks their writes — on real hardware the
latency-hiding scheduler overlaps the ppermute with the next tick's
compute.

On the production mesh the natural stage axis is "pod" (2 stages across
pods: intra-pod ICI stays TP/DP, the slower pod link carries only
boundary activations — the standard hierarchical deployment).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def split_stages(stacked_params, n_stages: int):
    """(reps, ...) leaves -> (n_stages, reps//n_stages, ...)."""
    def one(v):
        reps = v.shape[0]
        assert reps % n_stages == 0, (reps, n_stages)
        return v.reshape(n_stages, reps // n_stages, *v.shape[1:])
    return jax.tree_util.tree_map(one, stacked_params)


def pipeline_apply(mesh: Mesh, stage_axis: str, block_fn: Callable,
                   staged_params, x_micro: jnp.ndarray) -> jnp.ndarray:
    """Run ``block_fn(stage_params, x) -> x`` over all stages.

    staged_params: leaves (n_stages, layers_per_stage, ...) — sharded
                   P(stage_axis) on the leading axis inside shard_map.
    x_micro:       (n_micro, B_mb, S, d) replicated microbatches.
    Returns (n_micro, B_mb, S, d), replicated.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1

    pspecs = jax.tree_util.tree_map(lambda _: P(stage_axis), staged_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(pspecs, P()), out_specs=P())
    def run(params_stage, xs):
        # local view: leading stage axis is length-1 on each shard
        local = jax.tree_util.tree_map(lambda v: v[0], params_stage)
        stage_id = jax.lax.axis_index(stage_axis)
        last = n_stages - 1
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (clipped; bubbles masked below)
            mb_in = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(stage_id == 0, xs[mb_in], buf)
            h = block_fn(local, x_in)
            # last stage owns microbatch t - last at this tick
            mt = t - last
            write = jnp.logical_and(stage_id == last,
                                    jnp.logical_and(mt >= 0, mt < n_micro))
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, h, outs[jnp.clip(mt, 0, n_micro - 1)]),
                jnp.clip(mt, 0, n_micro - 1), 0)
            # hand activations to the next stage
            buf = jax.lax.ppermute(h, stage_axis, fwd_perm)
            return (buf, outs), None

        # mark the carries as varying over the stage axis (shard_map VMA
        # typing: they become stage-dependent after the first ppermute)
        buf0 = jax.lax.pcast(jnp.zeros_like(xs[0]), (stage_axis,),
                             to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), (stage_axis,),
                              to="varying")
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(n_ticks))
        # replicate the last stage's outputs to every shard
        outs = jax.lax.psum(
            jnp.where(stage_id == last, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    return run(staged_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
