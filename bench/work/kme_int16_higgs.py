"""Work one KME fit requires, from the shapes alone.

Each Lloyd's iteration reads the int16 view once (n F 2 bytes) and
computes n k F multiply-adds of distances; the final pass reads the view
once more for the labels and the inertia and writes n int32 labels.
"""


def work(n: int, n_features: int, params: dict) -> dict:
    iters, k = int(params["max_iter"]), int(params["n_clusters"])
    view = n * n_features * 2
    return {"flops": (iters + 1) * 2 * n * k * n_features,
            "bytes": (iters + 1) * view + n * 4}
