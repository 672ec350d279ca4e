"""Fused gang stepping: K same-shape GD jobs in one kernel launch.

Gang stepping (DESIGN.md §7.3) has two tiers.  The *round-robin* tier —
handled by the scheduler itself — advances each running job's
``fit_steps`` generator one iteration per turn, so K concurrent jobs
interleave on one host thread.  This module implements the *fused* tier:
gradient-descent jobs (LIN/LOG) that share a dataset, version, and every
shape-determining hyperparameter differ only in their host-side update
(the learning rate), so their per-core gradient kernels can be ``vmap``-ed
over a job axis and the whole gang advances with ONE ``map_reduce``
launch per step.  An 8-point learning-rate sweep becomes one batched
dispatch instead of eight — the host<->PIM command overhead the paper
identifies as the serial bottleneck is paid once per step, not once per
job per step.

The fused kernel wraps the *same* per-core function the serial trainers
register (``linreg.build_local_grad`` / ``logreg.build_local_grad``), so
fused and unfused fits cannot drift numerically; for the integer
versions they are bit-identical (asserted by tests/test_sched.py).

Step fusion composes with lane fusion (DESIGN.md §9.3): when the gang's
specs carry ``fuse_steps > 1``, the lane-batched kernel is driven by a
:class:`~repro.core.pim.StepProgram` — K jobs × k iterations advance in
ONE ``lax.scan`` launch, with the ``(K, F)`` lane weights as the donated
carry and a per-lane active mask freezing cancelled lanes on device.

A new workload opts into fusion by (a) exposing a GD-shaped config via
``Workload._config`` and (b) being added to :data:`FUSABLE_WORKLOADS`
with its per-core kernel builder and host update scale — see DESIGN.md
§7.3 for the walkthrough.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..api.registry import FitResult, TrainerSpec, Workload
from ..core import linreg, logreg
from ..core.fixed_point import from_fixed_sum, mul_round_f32
from ..core.linreg import GdResult, _quantize_weights
from ..core.logreg import _gd_version_of


@dataclasses.dataclass(frozen=True)
class _GdFamily:
    """How one workload plugs into the fused step."""

    build_local: Callable          # cfg -> per-core kernel
    kernel_name: Callable          # cfg -> registry name
    grad_scale: Callable           # n_samples -> host update scale
    base_version: Callable         # version -> weight-quantization version


#: workloads eligible for fusion; the registry name of the workload maps
#: to its GD family adapter.  LIN's update uses the 2/n MSE gradient
#: scale, LOG's the 1/n logistic scale (mirroring their fit loops).
FUSABLE_WORKLOADS = {
    "linreg": _GdFamily(
        build_local=linreg.build_local_grad,
        kernel_name=linreg.grad_kernel_name,
        grad_scale=lambda n: 2.0 / n,
        base_version=lambda v: v),
    "logreg": _GdFamily(
        build_local=logreg.build_local_grad,
        kernel_name=logreg.grad_kernel_name,
        grad_scale=lambda n: 1.0 / n,
        base_version=_gd_version_of),
}

#: spec params that may differ between fused lanes: the learning rate is
#: the sweep axis (host-side update only); the seed never reaches the
#: device for full-batch GD.
_LANE_LOCAL_PARAMS = ("lr", "seed")


def fuse_key(workload: Workload, spec: TrainerSpec):
    """Hashable fusion-eligibility key, or None when ``spec`` cannot fuse.

    Jobs fuse iff their keys are equal: same workload, version, and every
    shape/kernel-determining hyperparameter.  Minibatch SGD and history
    recording are excluded — per-lane minibatch offsets would need
    per-lane shard slices (no longer one batched launch) and history
    hooks run per lane anyway.
    """
    if workload.name not in FUSABLE_WORKLOADS:
        return None
    p = dict(spec.params)
    if p.get("minibatch") or p.get("record_every"):
        return None
    shared = tuple(sorted((k, v) for k, v in p.items()
                          if k not in _LANE_LOCAL_PARAMS))
    return (workload.name, spec.version, shared)


class FusedGdSweep:
    """K gradient-descent jobs advanced by one batched launch per step.

    Weights live host-side per lane, exactly as in the serial loop; per
    step the lanes' quantized weights are stacked to ``(K, F)``,
    broadcast once, and the vmapped per-core kernel produces per-lane
    gradients ``{"gw": (K, F), "gb": (K,)}`` in a single ``map_reduce``
    (integer versions: ``fixed_point.fx_sum`` pairs, a trailing axis of 2).
    """

    def __init__(self, workload: Workload, specs: Sequence[TrainerSpec],
                 dataset):
        keys = {fuse_key(workload, s) for s in specs}
        if len(keys) != 1 or None in keys:
            raise ValueError(
                f"specs are not fusable together (keys {keys}); fuse "
                f"only jobs with identical fuse_key")
        self.workload = workload
        self.specs = list(specs)
        self.dataset = dataset
        self.pim = dataset.system
        family = FUSABLE_WORKLOADS[workload.name]
        self.cfgs = [workload._config(s) for s in self.specs]
        cfg0 = self.cfgs[0]
        # weight quantization runs at the collapsed data precision, as in
        # logreg.fit (LUT variants quantize like their int32/hyb base)
        self.base_cfgs = [
            dataclasses.replace(c, version=family.base_version(c.version))
            for c in self.cfgs]
        self.scale = family.grad_scale(dataset.n)
        self.n_iters = cfg0.n_iters
        self.it = 0
        self.k = len(self.specs)
        f = dataset.n_features
        self.w = [np.zeros(f, np.float32) for _ in self.specs]
        # float32 lane biases: the serial trainers accumulate the bias in
        # float32 (a scan carry cannot hold host float64), and bit parity
        # with them requires the gang to match precision
        self.b = np.zeros(self.k, np.float32)
        self.active = [True] * self.k
        #: per-lane float32 update scale, rounded from the float64
        #: product exactly as the serial trainers round theirs
        self._lane_scale = np.asarray(
            [c.lr * self.scale for c in self.cfgs], np.float32)

        self.view = dataset.gd_view(cfg0.version, cfg0.frac_bits,
                                    cfg0.x8_frac)
        local = family.build_local(cfg0)

        def fused(Xc, yc, mc, Wq, Bq):
            return jax.vmap(lambda w, b: local(Xc, yc, mc, w, b))(Wq, Bq)

        self.kernel = self.pim.named_kernel(
            f"sched.fused/K{self.k}/{family.kernel_name(cfg0)}",
            lambda: fused)

        # step fusion x lane fusion: drive the batched kernel from a
        # StepProgram so one launch advances all K lanes k iterations
        self.fuse_steps = max(1, int(getattr(cfg0, "fuse_steps", 1)))
        self._program = None
        self._carry = None      # device-resident lane state between chunks
        if self.fuse_steps > 1:
            prepare, update = self._make_lane_step_fns()
            lrs = ",".join(repr(c.lr) for c in self.cfgs)
            self._program = self.pim.step_program(
                self.kernel, prepare, update,
                name=(f"sched.fusedstep/K{self.k}"
                      f"/{family.kernel_name(cfg0)}/lr{lrs}"
                      f"/n{dataset.n}"))

    @property
    def done(self) -> bool:
        return self.it >= self.n_iters or not any(self.active)

    def _quantize_lanes(self):
        """Batched lane quantization: the serial trainer's own
        ``_quantize_weights`` applied once to the stacked ``(K, F)`` /
        ``(K,)`` lane arrays (it is purely elementwise, so each lane's
        bits equal a serial fit's).  Batching is what makes fusion pay:
        the host-side dispatch cost per step stays O(1) in K — K eager
        per-lane quantize calls would eat the batched-launch saving."""
        return _quantize_weights(self.base_cfgs[0], np.stack(self.w),
                                 np.asarray(self.b, np.float32))

    def _grads_to_float(self, partial):
        """Batched inverse of the lane quantization (elementwise, so
        per-lane rows are bit-identical to the serial trainers'
        device-side dequantize in ``linreg.make_gd_step_fns``)."""
        cfg = self.base_cfgs[0]
        if cfg.version == "fp32":
            return (np.asarray(partial["gw"], np.float32),
                    np.asarray(partial["gb"], np.float32))
        return (np.asarray(from_fixed_sum(partial["gw"], cfg.frac_bits)),
                np.asarray(from_fixed_sum(partial["gb"], cfg.frac_bits)))

    def _make_lane_step_fns(self):
        """Lane-batched (prepare, update) for the StepProgram scan —
        per-lane rows bit-identical to the serial trainers' step fns
        (same elementwise quantize, dequantize, barrier'd f32 update)."""
        cfg = self.base_cfgs[0]
        f = cfg.frac_bits
        fp32 = cfg.version == "fp32"

        def prepare(carry):
            W, B, _, _ = carry
            return _quantize_weights(cfg, W, B)

        def update(carry, reduced):
            # ``ls`` (per-lane f32 scale) rides in the carry so
            # mul_round_f32 sees a traced value (see its caveat)
            W, B, act, ls = carry
            if fp32:
                GW = jnp.asarray(reduced["gw"], jnp.float32)
                GB = jnp.asarray(reduced["gb"], jnp.float32)
            else:
                GW = from_fixed_sum(reduced["gw"], f)
                GB = from_fixed_sum(reduced["gb"], f)
            # two-rounding update pinned against FMA contraction, per
            # lane exactly as the serial trainers round (fixed_point.
            # mul_round_f32)
            dW = mul_round_f32(ls[:, None], GW)
            dB = mul_round_f32(ls, GB)
            W = jnp.where(act[:, None], W - dW, W)
            B = jnp.where(act, B - dB, B)
            return (W, B, act, ls), None
        return prepare, update

    def _sync_carry(self) -> None:
        """Adopt the device-resident chunk carry into the host lane
        state (inactive lanes were frozen on device, so adopting every
        row is equivalent to the serial path's skip)."""
        if self._carry is None:
            return
        W = np.asarray(self._carry[0], np.float32)
        self.w = [W[i] for i in range(self.k)]
        self.b = np.asarray(self._carry[1], np.float32)

    def step(self) -> bool:
        """Advance every active lane one GD iteration — or, with
        ``fuse_steps`` set, one whole scan chunk of iterations in a
        single launch; True when done."""
        if self.done:
            return True
        Xs, ys, mask = self.view
        if self._program is not None:
            k = min(self.fuse_steps, self.n_iters - self.it)
            if self._carry is None:
                # built from host state once (and again after a lane
                # cancellation changes the active mask); between chunks
                # the lane weights stay device-resident — no per-chunk
                # host round-trip, that is the point of the engine
                self._carry = (jnp.asarray(np.stack(self.w)),
                               jnp.asarray(self.b),
                               jnp.asarray(self.active),
                               jnp.asarray(self._lane_scale))
            self._carry, _ = self._program.run(self._carry,
                                               (Xs, ys, mask), k)
            self.it += k
            if self.done:
                self._sync_carry()
                self._carry = None
            return self.done
        Wq, Bq = self.pim.broadcast(self._quantize_lanes())
        partial = self.pim.map_reduce(self.kernel, (Xs, ys, mask),
                                      (Wq, Bq))
        gw_all, gb_all = self._grads_to_float(partial)
        for i in range(self.k):
            if not self.active[i]:
                continue
            self.w[i] = self.w[i] - self._lane_scale[i] * gw_all[i]
            self.b[i] = self.b[i] - self._lane_scale[i] * gb_all[i]
        self.it += 1
        return self.done

    def deactivate(self, lane: int) -> None:
        """Stop updating a cancelled lane (the batched kernel still
        computes its gradient — one launch is all-or-nothing — but the
        lane's host state freezes and it reports no result)."""
        self.active[lane] = False
        if self._carry is not None:
            # pull the surviving state back and rebuild the carry next
            # chunk so the new active mask reaches the device
            self._sync_carry()
            self._carry = None

    def lane_state(self, lane: int) -> dict:
        """One lane's chunk-boundary snapshot — the same
        ``{"arrays", "meta"}`` schema the serial GD trainers emit from
        their ``ChunkTick``s (DESIGN.md §11.2), so a preempted gang
        lane resumes as an ordinary single job via
        ``fit_steps(state=...)``.  Gang lanes are bit-identical to
        serial fits, so the resumed trajectory is too.  Call after
        :meth:`deactivate` (which syncs any device-resident carry) or
        between steps; fused specs never record history or draw
        minibatches, so the snapshot carries neither."""
        self._sync_carry()
        return {"arrays": {"w": np.asarray(self.w[lane], np.float32),
                           "b": np.asarray(self.b[lane], np.float32),
                           "s": np.asarray(self._lane_scale[lane],
                                           np.float32)},
                "meta": {"iters": int(self.it), "history": []}}

    def result(self, lane: int) -> Optional[FitResult]:
        if not self.active[lane]:
            return None
        r = GdResult(w=self.w[lane], b=float(self.b[lane]), history=[],
                     n_iters=self.it)
        return FitResult(self.specs[lane], r,
                         {"coef_": r.w, "intercept_": r.b})


def plan_fusion(workload: Workload, specs: Sequence[TrainerSpec]
                ) -> List[List[int]]:
    """Partition spec indices into fusable gangs (singletons stay solo).

    Grouping preserves submission order inside each gang; specs whose
    ``fuse_key`` is None each get their own group.
    """
    groups: dict = {}
    order: List[List[int]] = []
    for i, spec in enumerate(specs):
        key = fuse_key(workload, spec)
        if key is None:
            order.append([i])
            continue
        if key not in groups:
            groups[key] = []
            order.append(groups[key])
        groups[key].append(i)
    return order
