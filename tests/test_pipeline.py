"""Pipeline parallelism: PP execution == sequential execution (fwd + grad).

Runs in a subprocess with 4 forced host devices (stage axis of 4).
"""
import subprocess
import sys

import pytest

from repro.distributed.pipeline import bubble_fraction, split_stages


def test_split_stages_shapes():
    import jax.numpy as jnp
    p = {"w": jnp.zeros((8, 3, 5))}
    out = split_stages(p, 4)
    assert out["w"].shape == (4, 2, 3, 5)


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == 3 / 15
    assert bubble_fraction(1, 8) == 0.0


@pytest.mark.slow
def test_pipeline_matches_sequential_subprocess():
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.distributed.pipeline import pipeline_apply, split_stages

mesh = jax.make_mesh((4,), ("stage",))
L, D = 8, 32          # 8 layers -> 4 stages x 2 layers
n_micro, B, S = 6, 2, 4

key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (L, D, D)) * (1.0 / np.sqrt(D)),
          "b": jnp.zeros((L, D))}

def layer(w, b, x):
    return jnp.tanh(x @ w + b)

def block_fn(stage_params, x):
    def body(h, wb):
        w, b = wb
        return layer(w, b, h), None
    h, _ = jax.lax.scan(body, x, (stage_params["w"], stage_params["b"]))
    return h

def sequential(params, xs):
    def body(h, wb):
        w, b = wb
        return layer(w, b, h), None
    out = []
    for i in range(xs.shape[0]):
        h, _ = jax.lax.scan(body, xs[i], (params["w"], params["b"]))
        out.append(h)
    return jnp.stack(out)

xs = jax.random.normal(jax.random.PRNGKey(1), (n_micro, B, S, D))
staged = split_stages(params, 4)

with jax.set_mesh(mesh):
    out_pp = pipeline_apply(mesh, "stage", block_fn, staged, xs)
out_seq = sequential(params, xs)
print("fwd max diff", float(jnp.abs(out_pp - out_seq).max()))
assert float(jnp.abs(out_pp - out_seq).max()) < 1e-5

# gradients THROUGH the pipeline == sequential gradients
def loss_pp(staged):
    return jnp.sum(pipeline_apply(mesh, "stage", block_fn, staged,
                                  xs) ** 2)

def loss_seq(params):
    return jnp.sum(sequential(params, xs) ** 2)

with jax.set_mesh(mesh):
    g_pp = jax.grad(loss_pp)(staged)
g_seq = jax.grad(loss_seq)(params)
gw_pp = g_pp["w"].reshape(L, D, D)
diff = float(jnp.abs(gw_pp - g_seq["w"]).max())
rel = diff / float(jnp.abs(g_seq["w"]).max())
print("grad rel diff", rel)
assert rel < 1e-4
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                       capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr
