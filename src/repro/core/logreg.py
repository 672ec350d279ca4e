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
from ..systems import (ChunkPipeline, ChunkTick, System, chunk_schedule,
                       run_steps)
from .fixed_point import _shift_round, fx_dot_hybrid, fx_sum
from .linreg import GdConfig, GdResult, make_gd_step_fns
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


def _gd_version_of(version: str) -> str:
    return {"fp32": "fp32", "int32": "int32", "int32_lut_mram": "int32",
            "int32_lut_wram": "int32", "hyb_lut": "hyb",
            "bui_lut": "bui"}[version]


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
    (unregistered) — shared by the serial trainer and the scheduler's
    fused gang step (DESIGN.md §7.3)."""
    lut = (build_sigmoid_lut(cfg.lut_boundary, cfg.lut_frac_bits)
           if "lut" in cfg.version else None)
    return make_local_grad(cfg, lut, exact_sigmoid)


def _exact_sigmoid(system: System, cfg: LogRegConfig) -> bool:
    """fp32 on a processor-centric target uses the exact sigmoid (the
    paper's MKL/cuML baselines); every other combination keeps the
    paper's DPU Taylor expansion."""
    return cfg.version == "fp32" and system.exact_transcendentals


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


def _grad_kernel(pim: System, cfg: LogRegConfig) -> str:
    """Named per-core kernel.  The sigmoid LUT is built inside the
    builder — pay-once like the kernel, not per fit."""
    exact = _exact_sigmoid(pim, cfg)
    return pim.named_kernel(grad_kernel_name(cfg, exact),
                            lambda: build_local_grad(cfg, exact))


def fit_steps(dataset, cfg: Optional[LogRegConfig] = None,
              eval_fn: Optional[Callable] = None, *,
              state: Optional[dict] = None):
    """Generator form of the LOG loop (GdResult on StopIteration) — the
    gang-stepping surface; :func:`fit` drains it.  Each ``next()``
    yields a :class:`~repro.systems.base.ChunkTick`: the number of GD
    iterations it advanced (1 per host-orchestrated step, up to
    ``cfg.fuse_steps`` per fused :class:`~repro.core.pim.StepProgram`
    chunk — DESIGN.md §9) with a lazy carry snapshot; pass a snapshot
    back as ``state`` to resume bit-exactly at that chunk boundary
    (DESIGN.md §11.2)."""
    cfg = cfg or LogRegConfig()
    assert cfg.version in VERSIONS, cfg.version
    pim = dataset.system
    n, nf = dataset.n, dataset.n_features

    # reuse linreg's weight quantization via the base data version
    base_cfg = dataclasses.replace(cfg, version=_gd_version_of(cfg.version))
    Xs, ys, mask = dataset.gd_view(cfg.version, cfg.frac_bits, cfg.x8_frac)
    local = _grad_kernel(pim, cfg)
    prepare, update = make_gd_step_fns(base_cfg)

    w = jnp.zeros(nf, jnp.float32)
    b = jnp.float32(0.0)
    s = jnp.float32(cfg.lr * (1.0 / n))
    history = []
    it_done = 0
    if state is not None:
        arrays, meta = state["arrays"], state["meta"]
        w = jnp.asarray(arrays["w"], jnp.float32)
        b = jnp.asarray(arrays["b"], jnp.float32)
        s = jnp.asarray(arrays["s"], jnp.float32)
        it_done = int(meta["iters"])
        history = [tuple(h) for h in meta.get("history", [])]

    def record(it, wv, bv):
        if cfg.record_every and (it % cfg.record_every == 0
                                 or it == cfg.n_iters):
            metric = None
            if eval_fn:
                wh, bh = pim.read((wv, bv))
                metric = eval_fn(wh, float(bh))
            history.append((it, metric))

    def _make_snapshot(wv, bv, sv, it):
        """Snapshot closure bound to one chunk boundary's carry (the
        live carry races ahead of drained boundaries when pipelined —
        DESIGN.md §14.1)."""
        def _snap():
            wh, bh, sh = pim.read((wv, bv, sv))
            return {"arrays": {"w": np.asarray(wh, np.float32),
                               "b": np.asarray(bh, np.float32),
                               "s": np.asarray(sh, np.float32)},
                    "meta": {"iters": int(it),
                             "history": [[int(i),
                                          None if m is None else float(m)]
                                         for i, m in history]}}
        return _snap

    def _snapshot():
        return _make_snapshot(w, b, s, it_done)()

    if cfg.fuse_steps > 1:
        program = pim.step_program(
            local, prepare, update,
            name=(f"log.step/{grad_kernel_name(cfg, _exact_sigmoid(pim, cfg))}"
                  f"/lr{cfg.lr}/n{n}"))
        # double-buffered chunk pipeline — see linreg.fit_steps
        pipe = ChunkPipeline(program, max(1, int(cfg.pipeline_depth)))

        def _drain(bnd):
            nonlocal it_done
            it_done = bnd.tag
            bw, bb, bs = bnd.carry
            record(it_done, bw, bb)
            return ChunkTick(bnd.k, _make_snapshot(bw, bb, bs, it_done))

        it_disp = it_done
        for k in chunk_schedule(cfg.n_iters, cfg.fuse_steps,
                                cfg.record_every, start=it_done):
            it_disp += k
            (w, b, s), drained = pipe.dispatch((w, b, s), (Xs, ys, mask),
                                               k, tag=it_disp)
            for bnd in drained:
                yield _drain(bnd)
        for bnd in pipe.flush():
            yield _drain(bnd)
    else:
        for it in range(it_done, cfg.n_iters):
            wq, bq = pim.broadcast(prepare((w, b, s)))
            partial = pim.map_reduce(local, (Xs, ys, mask), (wq, bq))
            (w, b, s), _ = update((w, b, s), partial)
            it_done = it + 1
            record(it_done, w, b)
            yield ChunkTick(1, _snapshot)
    w, b = pim.read((w, b))
    return GdResult(w=np.asarray(w, np.float32), b=float(b),
                    history=history, n_iters=cfg.n_iters)


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
