"""Hierarchical two-level all-reduce == flat psum (multi-pod schedule)."""
import subprocess

import pytest
import sys

from repro.distributed.collectives import cross_pod_bytes


def test_cross_pod_bytes_napkin():
    flat, hier = cross_pod_bytes(1 << 30, 16)
    assert hier * 16 == flat


@pytest.mark.slow
def test_hierarchical_psum_matches_flat_subprocess():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import hierarchical_psum

mesh = jax.make_mesh((2, 4), ("pod", "data"))

@functools.partial(jax.shard_map, mesh=mesh,
                   in_specs=P(("pod", "data")), out_specs=P())
def flat(x):
    return jax.lax.psum(x, ("pod", "data"))

# check_vma=False: the RS -> inter-AR -> AG composition is replicated in
# value, but shard_map's varying-axes type system cannot infer that.
@functools.partial(jax.shard_map, mesh=mesh,
                   in_specs=P(("pod", "data")), out_specs=P(),
                   check_vma=False)
def hier(x):
    return hierarchical_psum(x, intra_axis="data", inter_axis="pod")

x = jnp.arange(8 * 12, dtype=jnp.float32).reshape(8 * 4, 3) / 7.0
with mesh:
    a = flat(x)
    b = hier(x)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
# odd leading dim -> fallback path must also be exact
y = jnp.arange(8 * 5 * 3, dtype=jnp.float32).reshape(8 * 5, 3)
with mesh:
    a = flat(y)
    b = hier(y)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                       capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_multipod_dp_trainer_matches_flat_subprocess():
    """The hierarchical (pod,data) DP trainer must produce the same losses
    as the flat data-parallel reduction."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_config
from repro.models.api import Model
from repro.optim.adam import AdamW
from repro.optim.grad_compression import init_error_buffers
from repro.train.loop import make_dp_train_step
from repro.data.tokens import MarkovCorpus

cfg = get_config("granite-3-8b").reduced()
model = Model(cfg)
losses = {}
meshes = {"flat": jax.make_mesh((8,), ("data",)),
          "pod": jax.make_mesh((2, 4), ("pod", "data"))}
for name, mesh in meshes.items():
    corpus = MarkovCorpus(cfg.vocab_size, seed=0)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=3e-3)
    opt_state = opt.init(params)
    err = init_error_buffers(params)
    step = jax.jit(make_dp_train_step(model, opt, mesh))
    ls = []
    for i in range(3):
        batch = jax.tree_util.tree_map(jnp.asarray, corpus.batch(16, 16))
        with mesh:
            params, opt_state, err, m = step(params, opt_state, err, batch)
        ls.append(float(m["loss"]))
    losses[name] = ls
assert np.allclose(losses["flat"], losses["pod"], rtol=1e-4), losses
print("OK")
"""
    import subprocess
    import sys
    r = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                       capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr
