"""Pallas kernel ``kmeans_assign`` (``repro/kernels/kmeans_assign``): K-Means
assignment and per-cluster accumulation.

One call covers every PIM core (a batched kernel under ``vmap``).  It
reads the int16 rows once and writes one int32 label per row.  Its
distances are int32 VPU work, for which v5e publishes no peak; its
per-cluster sums are two one-hot bfloat16 matrix products on the MXU,
2 x 2 n k F operations, held against the bfloat16 peak.
"""

#: what the kernel's events are called in the device trace
TRACE_NAME = "kmeans_assign"


def cost(n: int, n_features: int, params: dict) -> dict:
    k = int(params["n_clusters"])
    return {"flops": 4 * n * k * n_features,
            "bytes": n * n_features * 2 + n * 4}
