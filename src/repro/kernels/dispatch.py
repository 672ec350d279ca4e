"""Backend dispatch for the kernel tier (DESIGN.md §6.2).

The paper's speedups exist only when the necessary operations and
datatypes are natively supported by the hardware; in this reproduction
the "native" tier is the Pallas kernel layer.  This module makes that
tier a first-class, swappable interface (the kernel/offload boundary
PIM-Opt and the DPU programmability study both call for):

  * :class:`KernelBackend` — where an op runs:
      ``pallas_tpu``       compiled Mosaic kernel (real TPU targets)
      ``pallas_interpret`` the same kernel under the Pallas interpreter
                           (CPU CI / debugging; slow but bit-faithful)
      ``jnp_ref``          the family's pure-jnp oracle in ``ref.py``
                           (lowers anywhere, fuses well under vmap /
                           shard_map — the fallback fast path off-TPU)
  * :func:`resolve_backend` — per-platform auto-selection with an
    ``REPRO_KERNEL_BACKEND`` environment override;
  * :func:`launch` — the uniform entry: ``launch(op, *args,
    backend=..., **kw)`` routes to the family's kernel or ref
    implementation.  A Pallas backend is always honoured: nothing
    falls back to ref behind the caller's back.

Every op family registers a (pallas, ref) implementation pair from its
``ops.py`` at import time; :func:`launch` lazily imports the families on
first use, so importing this module costs nothing and cannot cycle.

The trainers (core/kmeans.py, core/dtree.py, core/logreg.py,
core/linreg.py) call :func:`launch` from inside their per-core kernels;
the op name + backend are baked into the ``PimSystem`` named-kernel
registration, so ``ReduceStrategy`` selection and ``TransferStats``
accounting apply unchanged to the kernel-accelerated paths.
"""
from __future__ import annotations

import dataclasses
import enum
import importlib
import os
from typing import Callable, Dict, Optional, Union

import jax


class KernelBackend(enum.Enum):
    """Where a kernel-family op executes."""

    PALLAS_TPU = "pallas_tpu"
    PALLAS_INTERPRET = "pallas_interpret"
    JNP_REF = "jnp_ref"

    @property
    def is_pallas(self) -> bool:
        return self is not KernelBackend.JNP_REF

    @property
    def interpret(self) -> bool:
        return self is KernelBackend.PALLAS_INTERPRET


BackendLike = Union[None, str, KernelBackend]

#: environment override consulted by :func:`default_backend`
BACKEND_ENV_VAR = "REPRO_KERNEL_BACKEND"


def default_backend() -> KernelBackend:
    """Auto-select the backend for this process.

    Order: ``REPRO_KERNEL_BACKEND`` env var if set; ``pallas_tpu`` on a
    real TPU; otherwise ``jnp_ref`` (XLA fuses the oracles into the
    platform-native fast path — running the Pallas *interpreter* in a
    hot loop would be strictly slower; it remains an explicit opt-in
    for parity testing and kernel debugging).
    """
    env = os.environ.get(BACKEND_ENV_VAR)
    if env:
        return resolve_backend(env)
    if jax.default_backend() == "tpu":
        return KernelBackend.PALLAS_TPU
    return KernelBackend.JNP_REF


def resolve_backend(spec: BackendLike = None) -> KernelBackend:
    """Coerce None/string/enum to a :class:`KernelBackend`."""
    if spec is None:
        be = default_backend()
    elif isinstance(spec, KernelBackend):
        be = spec
    elif isinstance(spec, str):
        try:
            be = KernelBackend(spec.lower())
        except ValueError:
            raise ValueError(
                f"unknown kernel backend {spec!r}; known: "
                f"{[b.value for b in KernelBackend]}") from None
    else:
        raise TypeError(f"backend must be None, str or KernelBackend, "
                        f"got {type(spec).__name__}")
    return be


def legacy_backend(backend: BackendLike, use_pallas: Optional[bool],
                   interpret: Optional[bool]) -> KernelBackend:
    """Map the pre-dispatch ``(use_pallas, interpret)`` flag pair onto a
    backend.  ``backend`` wins when given; ``use_pallas=None`` defers to
    auto-selection.  Kept so existing callers/tests/benchmarks keep
    their meaning while the dispatch layer is the single router."""
    if backend is not None:
        return resolve_backend(backend)
    if use_pallas is None:
        return default_backend()
    if not use_pallas:
        return KernelBackend.JNP_REF
    if interpret is None or interpret:
        return resolve_backend(KernelBackend.PALLAS_INTERPRET)
    return resolve_backend(KernelBackend.PALLAS_TPU)


# ---------------------------------------------------------------------------
# Op registry.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelOp:
    """One dispatchable op: a Pallas implementation + its jnp oracle.

    ``pallas`` is called as ``pallas(*args, interpret=bool, **kw)``;
    ``ref`` as ``ref(*args, **kw)`` (adapters registered by each family
    drop pallas-only tuning kwargs such as block sizes).
    """

    name: str
    family: str
    pallas: Callable
    ref: Callable


_OPS: Dict[str, KernelOp] = {}

#: kernel families auto-imported on first launch()/get_op() call; each
#: family's ops.py calls register_op at import time.
_FAMILIES = ("kmeans_assign", "gini_split", "lut_activation",
             "quant_matmul", "flash_attention", "sparse_gather")
_registered = False

#: per-op launch counters (diagnostics + the trainer-routing tests)
launch_counts: Dict[str, int] = {}


def register_op(name: str, *, family: str, pallas: Callable,
                ref: Callable) -> None:
    _OPS[name] = KernelOp(name=name, family=family, pallas=pallas, ref=ref)


def _ensure_registered() -> None:
    global _registered
    if _registered:
        return
    _registered = True
    for fam in _FAMILIES:
        importlib.import_module(f"repro.kernels.{fam}.ops")


def get_op(name: str) -> KernelOp:
    _ensure_registered()
    try:
        return _OPS[name]
    except KeyError:
        raise KeyError(f"unknown kernel op {name!r}; known: "
                       f"{sorted(_OPS)}") from None


def available_ops() -> tuple:
    _ensure_registered()
    return tuple(sorted(_OPS))


def launch(op: str, *args, backend: BackendLike = None, **kwargs):
    """Run kernel-family op ``op`` on ``backend`` (auto-selected when
    None)."""
    entry = get_op(op)
    be = resolve_backend(backend)
    launch_counts[op] = launch_counts.get(op, 0) + 1
    if be is KernelBackend.JNP_REF:
        return entry.ref(*args, **kwargs)
    return entry.pallas(*args, interpret=be.interpret, **kwargs)


def legacy_launch(op: str, *args, backend: BackendLike = None,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None, **kwargs):
    """:func:`launch` with the pre-dispatch ``(use_pallas, interpret)``
    flag pair mapped onto a backend.  The single router behind every
    family's public ``ops.py`` wrapper — the wrappers and
    :func:`launch` share one code path (including ragged-shape
    padding), so they cannot diverge."""
    return launch(op, *args,
                  backend=legacy_backend(backend, use_pallas, interpret),
                  **kwargs)


def backend_tag(backend: BackendLike = None) -> str:
    """Short backend label for PimSystem kernel names (``be=jnp_ref``)."""
    return f"be={resolve_backend(backend).value}"


__all__ = [
    "KernelBackend", "BACKEND_ENV_VAR", "default_backend",
    "resolve_backend", "legacy_backend", "register_op", "get_op",
    "available_ops", "launch", "legacy_launch", "launch_counts",
    "backend_tag",
]
