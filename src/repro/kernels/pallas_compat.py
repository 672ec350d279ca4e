"""Where every kernel gets its Pallas TPU pieces from (DESIGN.md §6.1).

Every ``kernels/*/kernel.py`` builds its ``pallas_call`` through
:func:`pallas_call` below, which turns a plain tuple of dimension
semantics into ``pltpu.CompilerParams``.  Pallas is a hard dependency:
a jax build without it fails at import, never by quietly routing
kernels to another backend.

Nothing here imports the rest of ``repro`` — this is the bottom of the
kernel-layer dependency graph (dispatch.py sits on top).
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401  (re-exported)
from jax.experimental.pallas import tpu as pltpu


def vmem_scratch(shape, dtype):
    """``pltpu.VMEM`` scratch allocation."""
    return pltpu.VMEM(tuple(shape), dtype)


def pallas_call(kernel_fn, *, name: str, grid=None, in_specs=None,
                out_specs=None, out_shape=None, scratch_shapes=None,
                dimension_semantics=None, interpret: bool = False):
    """``pl.pallas_call`` with the compiler parameters every kernel sets.

    ``name`` is the kernel's dispatch op name (``fx_matvec``,
    ``lut_sigmoid``, ...): the compiled kernel carries it, whatever
    Python function wraps the call.  ``dimension_semantics`` is a plain
    tuple of strings that becomes a ``pltpu.CompilerParams``; all other
    arguments pass through.
    """
    kwargs: dict = {"out_shape": out_shape, "interpret": interpret,
                    "name": name}
    if grid is not None:
        kwargs["grid"] = grid
    if in_specs is not None:
        kwargs["in_specs"] = in_specs
    if out_specs is not None:
        kwargs["out_specs"] = out_specs
    if scratch_shapes is not None:
        kwargs["scratch_shapes"] = scratch_shapes
    if dimension_semantics is not None:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics))
    return pl.pallas_call(kernel_fn, **kwargs)
