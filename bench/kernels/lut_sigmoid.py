"""Pallas kernel ``lut_sigmoid_vmem`` (``repro/kernels/lut_activation``):
the table sigmoid of the LOG LUT versions, with the table in VMEM.

One call covers every PIM core (a batched kernel under ``vmap``).  What
the lookup needs is to read one int32 logit per row and the table once
(``lut_boundary << lut_frac_bits`` int16 entries), and to write one
int32 per row.  A table read is a gather, VPU work for which v5e
publishes no peak, so its roofline is bounded by bytes alone.
"""

#: what the kernel's events are called in the device trace
TRACE_NAME = "lut_sigmoid"


def cost(n: int, n_features: int, params: dict) -> dict:
    entries = int(params.get("lut_boundary", 20)) << int(
        params.get("lut_frac_bits", 10))
    return {"flops": 0, "bytes": 2 * n * 4 + 2 * entries}
