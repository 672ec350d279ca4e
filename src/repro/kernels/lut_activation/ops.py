"""Dispatchable LUT sigmoid with WRAM/MRAM-style placement selection
(op ``lut_sigmoid``).

``placement="vmem"``  -> Pallas kernel, table resident in VMEM
                         (paper: LOG-INT32-LUT (WRAM))
``placement="hbm"``   -> XLA gather straight from HBM
                         (paper: LOG-INT32-LUT (MRAM))
Both are numerically identical (asserted in tests), exactly as the paper
observes — placement is a ~3% performance knob on the DPU.  Backend
routing goes through :mod:`repro.kernels.dispatch`: the ``jnp_ref``
backend IS the HBM/MRAM variant, so kernel availability only changes
where the table lives, never the values.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.lut import SigmoidLut
from ..dispatch import legacy_launch, register_op
from .kernel import BLOCK_ROWS, LANES, lut_sigmoid_vmem, row_blocks
from .ref import lut_sigmoid_ref


def _sigmoid_pallas(x_q: jnp.ndarray, lut: SigmoidLut, *,
                    interpret: bool = True,
                    block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """VMEM-kernel path: flatten, pad to blocks of (br, 128) (see
    :func:`row_blocks`), slice back."""
    shape = x_q.shape
    flat = x_q.reshape(-1)
    n = flat.shape[0]
    blocks, br = row_blocks(max(-(-n // LANES), 1), block_rows)
    padded = jnp.pad(flat, (0, blocks * br * LANES - n))
    out = lut_sigmoid_vmem(padded.reshape(blocks * br, LANES), lut.table,
                           value_frac=lut.value_frac, block_rows=br,
                           interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)


def _sigmoid_ref(x_q: jnp.ndarray, lut: SigmoidLut, *,
                 block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    del block_rows  # jnp oracle needs no tiling
    return lut_sigmoid_ref(x_q, lut.table, lut.value_frac)


def lut_sigmoid(x_q: jnp.ndarray, lut: SigmoidLut, *,
                placement: str = "vmem", backend=None,
                use_pallas: bool = None, interpret: bool = None,
                block_rows: int = BLOCK_ROWS) -> jnp.ndarray:
    """Fixed-point sigmoid via LUT.  x_q int32 Q(lut.frac_bits), any shape.

    ``placement="hbm"`` forces the XLA gather (MRAM variant); otherwise
    ``backend`` picks the implementation (None = auto-select).
    """
    if placement == "hbm":
        return _sigmoid_ref(x_q, lut)
    # placement="vmem" historically meant "the kernel": keep that
    # meaning when neither backend nor use_pallas says otherwise
    if backend is None and use_pallas is None:
        use_pallas = True
    return legacy_launch("lut_sigmoid", x_q, lut, backend=backend,
                         use_pallas=use_pallas, interpret=interpret,
                         block_rows=block_rows)


register_op("lut_sigmoid", family="lut_activation",
            pallas=_sigmoid_pallas, ref=_sigmoid_ref)
