"""Pallas kernel ``fx_matvec`` (``repro/kernels/quant_matmul``): the
Q-format row-dot of the LIN/LOG int32 versions.

One call covers every PIM core (the cores are a ``vmap`` axis, so the
call is one batched kernel).  It reads the int32 rows once and writes
one int32 per row.  Its multiply-shift-adds are int32 VPU work, for
which v5e publishes no peak, so its roofline is bounded by bytes alone.
"""

#: what the kernel's events are called in the device trace
TRACE_NAME = "fx_matvec"


def cost(n: int, n_features: int, params: dict) -> dict:
    return {"flops": 0, "bytes": n * n_features * 4 + n * 4}
