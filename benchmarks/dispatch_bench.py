"""Kernel-dispatch benchmark: backend wall times + trainer parity.

Two outputs per workload hot path:

  * per-op wall time of the ``jnp_ref`` vs ``pallas_interpret``
    backends (interpret mode on CPU is the correctness path, not a perf
    claim — real kernel perf comes from the TPU backend / cost model);
  * the accuracy/inertia of full ``KMeansTrainer``/``DTreeTrainer``
    fits under both backends, confirming the dispatch wiring causes
    **no accuracy regression vs the jnp path** (deltas must be 0: the
    kernels are deterministic integer ops).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import dtree, kmeans
from repro.kernels import dispatch
from repro.kernels.quant_matmul.kernel import BLOCK_N, to_feature_blocks
from repro.systems import PimConfig, PimSystem
from .common import row, time_call

_BACKENDS = ("jnp_ref", "pallas_interpret")


def run():
    rows = []
    rng = np.random.RandomState(0)

    # -- per-op backend wall times -----------------------------------------
    x = jnp.asarray(rng.randint(-2047, 2048, (4096, 16)), jnp.int16)
    c = jnp.asarray(rng.randint(-2047, 2048, (16, 16)), jnp.int16)
    ts = {be: time_call(dispatch.launch, "kmeans_assign", x, c, backend=be)
          for be in _BACKENDS}
    rows.append(row("dispatch_kmeans_assign_ref_us",
                    ts["jnp_ref"] * 1e6,
                    f"interp_us={ts['pallas_interpret'] * 1e6:.0f}"))

    xq = jnp.asarray(rng.randint(-1024, 1024, (4096, 16)), jnp.int32)
    xq = to_feature_blocks(xq[None], BLOCK_N)[0]   # the int32 view's layout
    wq = jnp.asarray(rng.randint(-1024, 1024, (16,)), jnp.int32)
    ts = {be: time_call(dispatch.launch, "fx_matvec", xq, wq, 10,
                        backend=be) for be in _BACKENDS}
    rows.append(row("dispatch_fx_matvec_ref_us", ts["jnp_ref"] * 1e6,
                    f"interp_us={ts['pallas_interpret'] * 1e6:.0f}"))

    # -- trainer parity: no accuracy regression vs the jnp path ------------
    X = rng.normal(0, 1, (512, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.int32)
    km, acc = {}, {}
    for be in _BACKENDS:
        pim = PimSystem(PimConfig(n_cores=4))
        r = kmeans.fit(pim.put(X), kmeans.KMeansConfig(
            k=8, max_iters=10, kernel_backend=be))
        km[be] = r.inertia
        tree = dtree.fit(pim.put(X, y), dtree.TreeConfig(
            max_depth=5, kernel_backend=be))
        acc[be] = float((tree.predict(X) == y).mean())
    rows.append(row("dispatch_kmeans_inertia_delta",
                    abs(km["jnp_ref"] - km["pallas_interpret"]),
                    f"ref_inertia={km['jnp_ref']:.2f}"))
    rows.append(row("dispatch_dtree_acc_delta",
                    abs(acc["jnp_ref"] - acc["pallas_interpret"]),
                    f"ref_acc={acc['jnp_ref']:.4f}"))
    return rows
