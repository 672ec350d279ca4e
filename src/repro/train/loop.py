"""Training-step builders: pjit path (+microbatch grad accumulation) and
the explicit-DP shard_map path with the paper's PIM schedule
(+ int8 compressed all-reduce with error feedback).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


from repro.optim.adam import AdamW
from repro.optim.grad_compression import ef_compress_psum


def make_train_step(model, optimizer: AdamW, microbatches: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  batch leaves have leading dim B; with microbatches > 1 the
    step scans over k slices of B/k, accumulating f32 gradients — the
    activation-memory knob that makes the big train_4k cells fit
    (configs/shapes.py TRAIN_MICROBATCHES), and the natural place where
    per-microbatch reduce-scatter overlaps the next microbatch's compute
    on real hardware.
    """

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape(microbatches, b // microbatches,
                                 *x.shape[1:])

            mbs = jax.tree_util.tree_map(split, batch)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                acc_g, acc_l = carry
                loss, g = jax.value_and_grad(loss_fn)(params, mb)
                acc_g = jax.tree_util.tree_map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc_g, g)
                return (acc_g, acc_l + loss), None

            (grads, loss_sum), _ = jax.lax.scan(
                body, (g0, jnp.float32(0.0)), mbs)
            grads = jax.tree_util.tree_map(
                lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches

        params, opt_state, gnorm = optimizer.update(grads, opt_state,
                                                    params)
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm}
        return params, opt_state, metrics

    return train_step


def make_eval_step(model):
    def eval_step(params, batch):
        return model.loss(params, batch).astype(jnp.float32)
    return eval_step


# ---------------------------------------------------------------------------
# Explicit-DP trainer (the paper's PIM schedule applied to LM training):
# replicated params, batch sharded over a "data" axis via shard_map, ONE
# gradient reduction per step — optionally int8-compressed with error
# feedback (optim/grad_compression.py).
# ---------------------------------------------------------------------------

def make_dp_train_step(model, optimizer: AdamW, mesh, *,
                       compress: bool = False):
    axis = "data"
    world = mesh.shape[axis] * mesh.shape.get("pod", 1)

    def step(params, opt_state, err, batch):
        (loss, grads), new_err = _dp_call(mesh, axis, model, params, err,
                                          batch, compress, world)
        params, opt_state, gnorm = optimizer.update(grads, opt_state,
                                                    params)
        return params, opt_state, new_err, {
            "loss": loss.astype(jnp.float32), "grad_norm": gnorm}

    return step


def _dp_call(mesh, axis, model, params, err, batch, compress, world):
    """Build + call the shard_map'd gradient step (specs mirror args).

    On a multi-pod mesh the exact (uncompressed) reduction uses the
    two-level hierarchical schedule (distributed/collectives.py) so the
    slow cross-pod links carry 1/pod_size of the gradient bytes.
    """
    from jax.sharding import PartitionSpec as P
    hierarchical = "pod" in mesh.axis_names
    dp_axes = ("pod", axis) if hierarchical else (axis,)
    batch_specs = jax.tree_util.tree_map(
        lambda x: P(dp_axes) if getattr(x, "ndim", 0) > 0 else P(), batch)
    rep = jax.tree_util.tree_map(lambda _: P(), params)
    err_specs = jax.tree_util.tree_map(lambda _: P(), err)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(rep, err_specs, batch_specs),
        out_specs=((P(), rep), err_specs), check_vma=False)
    def run(params_, err_, batch_):
        loss, g = jax.value_and_grad(
            lambda p: model.loss(p, batch_))(params_)
        if compress:
            flat_g, td = jax.tree_util.tree_flatten(g)
            flat_e, _ = jax.tree_util.tree_flatten(err_)
            outs = [ef_compress_psum(gg, ee, dp_axes, world)
                    for gg, ee in zip(flat_g, flat_e)]
            g = jax.tree_util.tree_unflatten(td, [o[0] for o in outs])
            new_err = jax.tree_util.tree_unflatten(td,
                                                   [o[1] for o in outs])
        elif hierarchical:
            from repro.distributed.collectives import hierarchical_psum
            g = jax.tree_util.tree_map(
                lambda gg: hierarchical_psum(
                    gg, intra_axis=axis, inter_axis="pod") / world, g)
            new_err = err_
        else:
            g = jax.lax.pmean(g, axis)
            new_err = err_
        loss = jax.lax.pmean(loss, dp_axes)
        return (loss, g), new_err

    return run(params, err, batch)
