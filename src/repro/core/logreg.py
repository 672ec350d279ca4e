"""Logistic regression with gradient descent on the PIM system (paper §3.2).

Six versions, exactly the paper's ladder:
  LOG-FP32            float32 + Taylor-series sigmoid (DPUs lack exp)
  LOG-INT32           Q(frac_bits) fixed point + fixed-point Taylor sigmoid
  LOG-INT32-LUT(MRAM) fixed point + LUT sigmoid, LUT resident in DRAM bank
  LOG-INT32-LUT(WRAM) fixed point + LUT sigmoid, LUT in the scratchpad
  LOG-HYB-LUT         8-bit inputs x 16-bit weights + WRAM LUT
  LOG-BUI-LUT         LOG-HYB-LUT numerics + built-in multiply (cost model)

The MRAM/WRAM variants are numerically identical (same table); they differ
in *placement*, which on the DPU is a ~3% effect (§5.2.2) and on TPU maps
to HBM-gather vs VMEM-resident LUT (kernels/lut_activation).  Here the
functional semantics are shared; the placement flag routes the cost model
and (on TPU) kernel selection.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import dispatch
from ..systems import System, run_steps
from . import linreg
from .fixed_point import _shift_round, fx_dot_hybrid, fx_sum
from .linreg import GdConfig, GdResult
from .lut import SigmoidLut, build_sigmoid_lut, taylor_sigmoid_fixed

VERSIONS = ("fp32", "int32", "int32_lut_mram", "int32_lut_wram",
            "hyb_lut", "bui_lut")


@dataclasses.dataclass
class LogRegConfig(GdConfig):
    version: str = "fp32"
    lr: float = 5.0              # logistic loss needs larger steps (flat
                                 # gradients; validated in quality tests)
    taylor_terms: int = 8
    lut_boundary: int = 20       # paper Fig. 4: boundary 20, 10 frac bits
    lut_frac_bits: int = 10


def _sigmoid_taylor_f32(z: jnp.ndarray, terms: int) -> jnp.ndarray:
    """Float Taylor sigmoid — the paper's LOG-FP32 path on DPUs.

    exp(-|z|) via range-reduced Taylor (m=3 halvings), then reflect.
    """
    a = jnp.minimum(jnp.abs(z), 20.0)
    t = a / 8.0
    acc = jnp.ones_like(t)
    for k in range(terms - 1, 0, -1):
        acc = 1.0 - acc * t / k
    e = acc ** 8  # (exp(-t))**8 = exp(-a)
    pos = 1.0 / (1.0 + e)
    return jnp.where(z < 0, 1.0 - pos, pos)


def make_local_grad(cfg: LogRegConfig, lut: Optional[SigmoidLut],
                    exact_sigmoid: bool = False):
    """Build the per-core kernel for the configured version.

    The two kernel-dispatch hooks (repro.kernels.dispatch):

      * the INT32 Q-format matvec routes through op ``fx_matvec``;
      * the LUT sigmoid routes through op ``lut_sigmoid`` — but the
        paper's MRAM variant *is* the HBM-gather ref path, so
        ``int32_lut_mram`` pins ``jnp_ref`` while the WRAM/HYB/BUI
        variants follow the configured backend (VMEM kernel on TPU).

    ``exact_sigmoid`` selects the native-transcendental fp32 sigmoid a
    processor-centric :class:`~repro.systems.base.System` provides (the
    paper's MKL baseline, §5.4) instead of the DPU Taylor expansion; it
    only applies to the fp32 version.
    """
    f = cfg.frac_bits
    be = dispatch.resolve_backend(cfg.kernel_backend)
    # MRAM placement == HBM gather == the ref path, by definition
    lut_be = (dispatch.KernelBackend.JNP_REF
              if cfg.version == "int32_lut_mram" else be)

    if cfg.version == "fp32":
        terms = cfg.taylor_terms

        def _local_fp32(Xc, yc, mask, w, b):
            z = Xc @ w + b
            p = (jax.nn.sigmoid(z) if exact_sigmoid
                 else _sigmoid_taylor_f32(z, terms))
            err = (p - yc) * mask
            return {"gw": Xc.T @ err, "gb": jnp.sum(err)}
        return _local_fp32

    if cfg.version == "int32":
        terms = cfg.taylor_terms

        def _local_int32_taylor(Xq, yq, mask, wq, bq):
            # blocked int32 view: Xq (nb, F8, bn), yq and mask (nb, bn)
            z = dispatch.launch("fx_matvec", Xq, wq, f,
                                backend=be) + bq          # Q(f) (nb, bn)
            p = taylor_sigmoid_fixed(z, f, terms=terms)   # Q(f)
            err = (p - yq) * mask
            prod = err[:, None, :] * Xq
            gw = fx_sum(_shift_round(prod, f), (0, 2))[:wq.shape[0]]
            return {"gw": gw, "gb": fx_sum(err)}
        return _local_int32_taylor

    if cfg.version in ("int32_lut_mram", "int32_lut_wram"):
        assert lut is not None

        def _local_int32_lut(Xq, yq, mask, wq, bq):
            # blocked int32 view: Xq (nb, F8, bn), yq and mask (nb, bn)
            z = dispatch.launch("fx_matvec", Xq, wq, f,
                                backend=be) + bq          # Q(f) (nb, bn)
            p15 = dispatch.launch("lut_sigmoid", z, lut,
                                  backend=lut_be)         # Q(value_frac)
            p = _shift_round(p15, lut.value_frac - f)     # -> Q(f)
            err = (p - yq) * mask
            prod = err[:, None, :] * Xq
            gw = fx_sum(_shift_round(prod, f), (0, 2))[:wq.shape[0]]
            return {"gw": gw, "gb": fx_sum(err)}
        return _local_int32_lut

    # hyb_lut / bui_lut — identical numerics (paper §3.1/§3.2); the
    # saturating 16-bit dot stays inline (sequential clip semantic —
    # DESIGN.md §6.3), the sigmoid is dispatch-routed
    assert lut is not None
    x8, w16 = cfg.x8_frac, cfg.w16_frac

    def _local_hyb_lut(Xq8, yq, mask, wq16, bq):
        z = fx_dot_hybrid(Xq8, wq16, x8, w16, f) + bq     # Q(f), 16-bit acc
        p15 = dispatch.launch("lut_sigmoid", z, lut, backend=lut_be)
        p = _shift_round(p15, lut.value_frac - f)
        err = (p - yq) * mask
        prod = err[:, None] * Xq8.astype(jnp.int32)
        gw = fx_sum(_shift_round(prod, x8), 0)
        return {"gw": gw, "gb": fx_sum(err)}
    return _local_hyb_lut


def build_local_grad(cfg: LogRegConfig,
                     exact_sigmoid: bool = False) -> Callable:
    """Per-core kernel for ``cfg.version`` with its LUT built in
    (unregistered) — shared by the GD driver and the scheduler's fused
    gang step through :data:`LOG` (DESIGN.md §7.3)."""
    lut = (build_sigmoid_lut(cfg.lut_boundary, cfg.lut_frac_bits)
           if "lut" in cfg.version else None)
    return make_local_grad(cfg, lut, exact_sigmoid)


def grad_kernel_name(cfg: LogRegConfig, exact_sigmoid: bool = False) -> str:
    """Registry name encoding every parameter baked into the closure
    (version, Q formats, Taylor terms, LUT geometry, sigmoid flavor) so
    the compiled kernel is reused across fits and never served stale."""
    return (f"log.grad/{cfg.version}"
            + ("x" if exact_sigmoid else "")
            + f"/f{cfg.frac_bits}"
            f".x{cfg.x8_frac}.w{cfg.w16_frac}"
            f".t{cfg.taylor_terms}"
            f".lb{cfg.lut_boundary}.lf{cfg.lut_frac_bits}"
            f"/{dispatch.backend_tag(cfg.kernel_backend)}")


def _kernel(system: System, cfg: LogRegConfig):
    """LOG's per-core kernel on ``system``: fp32 on a processor-centric
    target uses the exact sigmoid (the paper's MKL/cuML baselines);
    every other combination keeps the paper's DPU Taylor expansion.
    The sigmoid LUT is built inside the builder — pay-once like the
    kernel, not per fit."""
    exact = cfg.version == "fp32" and system.exact_transcendentals
    return (grad_kernel_name(cfg, exact),
            lambda: build_local_grad(cfg, exact))


#: LOG in the shared GD driver: the logistic gradient's 1/n scale, and
#: LUT versions quantize their weights like their int32/hyb base
LOG = linreg.GdFamily(
    name="log", kernel=_kernel, grad_scale=1.0,
    base_version={"fp32": "fp32", "int32": "int32",
                  "int32_lut_mram": "int32", "int32_lut_wram": "int32",
                  "hyb_lut": "hyb", "bui_lut": "bui"})


def fit_steps(dataset, cfg: Optional[LogRegConfig] = None,
              eval_fn: Optional[Callable] = None, *,
              state: Optional[dict] = None):
    """Generator form of the LOG fit: the shared GD driver
    (:func:`repro.core.linreg.fit_steps`) run with LOG's record."""
    return linreg.fit_steps(dataset, cfg or LogRegConfig(), eval_fn,
                            state=state, family=LOG)


def fit(dataset, cfg: Optional[LogRegConfig] = None,
        eval_fn: Optional[Callable] = None) -> GdResult:
    """LOG training over a bank-resident PimDataset.  The data view is
    shared with LIN (same precision ladder), so a LIN fit followed by a
    LOG fit on one dataset still transfers the shards once."""
    return run_steps(fit_steps(dataset, cfg, eval_fn))


def train(X: np.ndarray, y: np.ndarray, pim: System,
          cfg: Optional[LogRegConfig] = None,
          eval_fn: Optional[Callable] = None) -> GdResult:
    """Deprecated shim: re-partitions (X, y) on every call.  Prefer
    ``fit(pim.put(X, y), cfg)`` (repro.api)."""
    warnings.warn("logreg.train(X, y, pim, ...) is deprecated; use "
                  "logreg.fit(pim.put(X, y), cfg)", DeprecationWarning,
                  stacklevel=2)
    from ..api.dataset import as_dataset
    return fit(as_dataset(X, y, pim), cfg, eval_fn)

# The CPU comparison point (float32, *exact* sigmoid — MKL-style) is no
# longer an ad-hoc numpy loop here: fp32 on repro.systems.HostSystem
# selects the exact sigmoid automatically (``exact_transcendentals``),
# e.g. ``logreg.fit(make_system("host").put(X, y), LogRegConfig("fp32"))``.
