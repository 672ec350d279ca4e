"""Work one LOG fit requires, from the shapes alone.

Each gradient-descent iteration reads the stored data once: X at its
stored width (int32 fixed point, 4 bytes) and the labels (int32).  Its
arithmetic is a matrix-vector product forward and one back, 2 n F
operations each.  How the program lays the data out, pads it or moves it
again is not work the algorithm needs, so it is not counted.
"""


def work(n: int, n_features: int, params: dict) -> dict:
    iters = int(params["n_iters"])
    return {"flops": iters * 4 * n * n_features,
            "bytes": iters * (n * n_features * 4 + n * 4)}
