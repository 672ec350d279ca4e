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

The fused kernel wraps the *same* per-core function the trainers
register, from the workload's :class:`~repro.core.linreg.GdFamily`
record, so fused and unfused fits cannot drift numerically; for the
integer versions they are bit-identical (asserted by tests/test_sched.py).

The lane-batched kernel is driven by a
:class:`~repro.systems.base.StepProgram` (DESIGN.md §9.3), with the
``(K, F)`` lane weights as the device-resident carry and a per-lane
active mask freezing cancelled lanes on device: with ``fuse_steps > 1``
K jobs × k iterations advance in ONE ``lax.scan`` launch, at one step a
chunk runs as one broadcast and one batched ``map_reduce``.

A new workload opts into fusion by (a) exposing a GD-shaped config via
``Workload._config`` and (b) writing its family record and naming it in
:data:`_FAMILIES` — see DESIGN.md §7.3 for the walkthrough.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..api.registry import FitResult, TrainerSpec, Workload
from ..core import linreg, logreg
from ..core.fixed_point import from_fixed_sum, mul_round_f32
from ..core.linreg import GdResult, _quantize_weights

#: the workloads eligible for fusion, by registry name
_FAMILIES = {"linreg": linreg.LIN, "logreg": logreg.LOG}

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
    if workload.name not in _FAMILIES:
        return None
    p = dict(spec.params)
    if p.get("minibatch") or p.get("record_every"):
        return None
    shared = tuple(sorted((k, v) for k, v in p.items()
                          if k not in _LANE_LOCAL_PARAMS))
    return (workload.name, spec.version, shared)


class FusedGdSweep:
    """K gradient-descent jobs advanced by one batched launch per chunk.

    The lanes' weights stay on the device as one ``(K, F)`` carry of a
    lane-batched :class:`~repro.systems.base.StepProgram`; per step the
    carry is quantized, broadcast once, and the vmapped per-core kernel
    produces per-lane gradients ``{"gw": (K, F), "gb": (K,)}`` in a
    single reduce (integer versions: ``fixed_point.fx_sum`` pairs, a
    trailing axis of 2).  The host copies ``w``/``b`` are synced at the
    end, on a lane cancellation, and for a lane snapshot.
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
        family = _FAMILIES[workload.name]
        self.cfgs = [workload._config(s) for s in self.specs]
        cfg0 = self.cfgs[0]
        self.n_iters = cfg0.n_iters
        self.it = 0
        self.k = len(self.specs)
        f = dataset.n_features
        self.w = [np.zeros(f, np.float32) for _ in self.specs]
        # float32 lane biases: the trainers accumulate the bias in
        # float32 (a scan carry cannot hold host float64), and bit parity
        # with them requires the gang to match precision
        self.b = np.zeros(self.k, np.float32)
        self.active = [True] * self.k
        #: per-lane float32 update scale, rounded from the float64
        #: product exactly as the GD driver rounds its own
        scale = family.grad_scale / dataset.n
        self._lane_scale = np.asarray([c.lr * scale for c in self.cfgs],
                                      np.float32)

        self.view = dataset.gd_view(cfg0.version, cfg0.frac_bits,
                                    cfg0.x8_frac)
        kname, build = family.kernel(self.pim, cfg0)

        def lanes():
            local = build()
            return lambda Xc, yc, mc, Wq, Bq: jax.vmap(
                lambda w, b: local(Xc, yc, mc, w, b))(Wq, Bq)

        self.kernel = self.pim.named_kernel(
            f"sched.fused/K{self.k}/{kname}", lanes)

        # weight quantization runs at the collapsed data precision, as in
        # the GD driver (LUT variants quantize like their int32/hyb base)
        prepare, update = self._make_lane_step_fns(dataclasses.replace(
            cfg0, version=family.base_version[cfg0.version]))
        lrs = ",".join(repr(c.lr) for c in self.cfgs)
        self.fuse_steps = max(1, int(cfg0.fuse_steps))
        self._program = self.pim.step_program(
            self.kernel, prepare, update,
            name=(f"sched.fusedstep/K{self.k}/{kname}/lr{lrs}"
                  f"/n{dataset.n}"))
        self._carry = None      # device-resident lane state between chunks

    @property
    def done(self) -> bool:
        return self.it >= self.n_iters or not any(self.active)

    @staticmethod
    def _make_lane_step_fns(cfg):
        """Lane-batched (prepare, update) — per-lane rows bit-identical
        to the GD driver's step fns (same elementwise quantize,
        dequantize, barrier'd f32 update)."""
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
            # lane exactly as the GD driver rounds (fixed_point.
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
        row keeps them as they were at cancellation)."""
        if self._carry is None:
            return
        W = np.asarray(self._carry[0], np.float32)
        self.w = [W[i] for i in range(self.k)]
        self.b = np.asarray(self._carry[1], np.float32)

    def step(self) -> bool:
        """Advance every active lane one chunk of up to ``fuse_steps``
        GD iterations in a single launch; True when done."""
        if self.done:
            return True
        k = min(self.fuse_steps, self.n_iters - self.it)
        if self._carry is None:
            # built from host state once (and again after a lane
            # cancellation changes the active mask); between chunks
            # the lane weights stay device-resident — no per-chunk
            # host round-trip
            self._carry = (jnp.asarray(np.stack(self.w)),
                           jnp.asarray(self.b),
                           jnp.asarray(self.active),
                           jnp.asarray(self._lane_scale))
        self._carry, _ = self._program.run(self._carry, self.view, k)
        self.it += k
        if self.done:
            self._sync_carry()
            self._carry = None
        return self.done

    def deactivate(self, lane: int) -> None:
        """Stop updating a cancelled lane (the batched kernel still
        computes its gradient — one launch is all-or-nothing — but the
        lane's carry row freezes and it reports no result)."""
        self.active[lane] = False
        if self._carry is not None:
            # pull the surviving state back and rebuild the carry next
            # chunk so the new active mask reaches the device
            self._sync_carry()
            self._carry = None

    def lane_state(self, lane: int) -> dict:
        """One lane's chunk-boundary snapshot — the same
        ``{"arrays", "meta"}`` schema the GD driver emits from
        their ``ChunkTick``s (DESIGN.md §11.2), so a preempted gang
        lane resumes as an ordinary single job via
        ``fit_steps(state=...)``.  Gang lanes are bit-identical to
        solo fits, so the resumed trajectory is too.  Call after
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
