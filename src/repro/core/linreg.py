"""Linear regression with gradient descent on the PIM system (paper §3.1).

Four versions, exactly the paper's ladder of optimizations:
  LIN-FP32   32-bit float training data and arithmetic (emulated on DPUs —
             native on TPU, so this doubles as the CPU/GPU-style baseline).
  LIN-INT32  32-bit fixed-point (Q. frac_bits) data + arithmetic.
  LIN-HYB    hybrid precision: 8-bit inputs x 16-bit weights, 16-bit dot
             products, 32-bit gradients.
  LIN-BUI    same numerics as LIN-HYB (paper: "same behavior, since they
             use the same datatypes") + the custom built-in multiply, which
             only changes the instruction count -> modeled by DpuCostModel.

Workload distribution mirrors §3.1: rows are partitioned across PIM cores;
each core computes partial gradients over its resident shard; the host
reduces partials, updates w, and re-broadcasts it.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..elastic.state import pack_rng, unpack_rng
from ..kernels import dispatch
from ..systems import (ChunkPipeline, ChunkTick, System, chunk_schedule,
                       run_steps)
from .fixed_point import (_shift_round, from_fixed_sum, fx_dot_hybrid,
                          fx_sum, mul_round_f32, to_fixed)

VERSIONS = ("fp32", "int32", "hyb", "bui")


@dataclasses.dataclass
class GdConfig:
    version: str = "fp32"
    n_iters: int = 500
    lr: float = 0.1
    frac_bits: int = 10      # Q format for INT32 data / all fixed-point grads
    x8_frac: int = 7         # Q format of 8-bit inputs (HYB/BUI)
    w16_frac: int = 8        # Q format of 16-bit weights (HYB/BUI)
    record_every: int = 0    # 0 = only final metrics
    minibatch: int = 0       # 0 = full-batch GD (paper default); >0 =
    #                          SGD with per-core minibatches of this size
    #                          (paper §2: "gradient descent or stochastic
    #                          gradient descent")
    seed: int = 0
    #: kernel backend for the dispatch-routed pieces of the per-core
    #: gradient kernel (None = auto-select; repro.kernels.dispatch).
    #: INT32 versions route their Q-format matvec through the
    #: ``fx_matvec`` op; HYB/BUI keep the inline saturating 16-bit
    #: accumulation (a sequential-clip semantic no matmul kernel can
    #: express — DESIGN.md §6.3).
    kernel_backend: Optional[str] = None
    #: step fusion (DESIGN.md §9): compile this many consecutive GD
    #: iterations into ONE lax.scan launch — the whole kernel -> reduce
    #: -> update -> re-quantize cycle stays on device between chunk
    #: boundaries.  1 = the host-orchestrated per-step loop.  Works for
    #: minibatch SGD too (DESIGN.md §9.5): the host pre-draws each
    #: chunk's batch offsets from the same rng stream the serial loop
    #: uses and feeds them through the scan as per-step inputs, so the
    #: fused trajectory equals the serial one exactly.  Bit-identical
    #: to the serial loop for the integer versions.  ``record_every``
    #: still works: chunks are clipped so recording points land on
    #: chunk boundaries.
    fuse_steps: int = 1
    #: chunk pipelining (DESIGN.md §14.1): how many fused chunks may be
    #: in flight before the host drains a boundary (record/eval,
    #: snapshot).  2 = double-buffered — chunk N+1 executes while the
    #: host processes boundary N; 1 = the serial dispatch-drain cadence
    #: (with carry donation).  Bit-identical either way: pipelining
    #: reorders host work only.  Ignored unless ``fuse_steps > 1``.
    pipeline_depth: int = 2


@dataclasses.dataclass
class GdResult:
    w: np.ndarray            # float32 [F]
    b: float
    history: list            # [(iter, metric)] if record_every else []
    n_iters: int = 0

    def predict(self, X: np.ndarray) -> np.ndarray:
        return np.asarray(X, np.float32) @ self.w + self.b


# ---------------------------------------------------------------------------
# Per-core kernels (run on every PIM core over its resident shard).
# ---------------------------------------------------------------------------

def _local_grad_fp32(Xc, yc, mask, w, b):
    pred = Xc @ w + b
    err = (pred - yc) * mask
    return {"gw": Xc.T @ err, "gb": jnp.sum(err)}


def make_local_grad_int32(frac_bits: int, backend=None):
    be = dispatch.resolve_backend(backend)

    def _local(Xq, yq, mask, wq, bq):
        # the int32 view's blocked layout (api/dataset.py::gd_view):
        # Xq (nb, F8, bn), yq and mask (nb, bn).  Q-format matvec through
        # the kernel-dispatch layer (op ``fx_matvec``; bit-identical to
        # fixed_point.fx_dot on the rows)
        dot = dispatch.launch("fx_matvec", Xq, wq, frac_bits,
                              backend=be) + bq          # Q(f) (nb, bn)
        err = (dot - yq) * mask                         # Q(f)
        prod = err[:, None, :] * Xq                     # Q(2f)
        gw = fx_sum(_shift_round(prod, frac_bits), (0, 2))[:wq.shape[0]]
        return {"gw": gw, "gb": fx_sum(err)}
    return _local


def make_local_grad_hyb(x8_frac: int, w16_frac: int, out_frac: int):
    def _local(Xq8, yq, mask, wq16, bq):
        # 16-bit saturating dot product (the paper's stated precision)
        dot = fx_dot_hybrid(Xq8, wq16, x8_frac, w16_frac, out_frac) + bq
        err = (dot - yq) * mask                          # Q(out_frac) int32
        prod = err[:, None] * Xq8.astype(jnp.int32)      # Q(out+x8)
        gw = fx_sum(_shift_round(prod, x8_frac), 0)      # Q(out_frac)
        return {"gw": gw, "gb": fx_sum(err)}
    return _local


def row_window(shards: tuple, start, size: int) -> tuple:
    """The ``size`` rows from row ``start`` (may be traced) of every
    shard of a GD view.  Row-major shards are sliced on axis 1; of the
    int32 view's blocked layout (a 4-D ``Xs``) come the blocks that hold
    the window, with the rows outside it masked out — a masked row adds
    exact zeros to the integer gradient, so both give the same sums."""
    if shards[0].ndim < 4:
        return tuple(jax.lax.dynamic_slice_in_dim(a, start, size, axis=1)
                     for a in shards)
    nb, bn = shards[1].shape[1:]
    k = min(nb, -(-size // bn) + 1)         # blocks that hold any window
    b0 = jnp.minimum(start // bn, nb - k)
    rows = (b0 + jnp.arange(k))[:, None] * bn + jnp.arange(bn)
    Xs, ys, mask = (jax.lax.dynamic_slice_in_dim(a, b0, k, axis=1)
                    for a in shards)
    return Xs, ys, mask * ((rows >= start) & (rows < start + size))


# ---------------------------------------------------------------------------
# Host-orchestrated training loop (paper §3.1 flow).
# ---------------------------------------------------------------------------

def _quantize_weights(cfg: GdConfig, w: np.ndarray, b: float):
    if cfg.version == "fp32":
        return jnp.asarray(w), jnp.float32(b)
    if cfg.version == "int32":
        return to_fixed(w, cfg.frac_bits), to_fixed(b, cfg.frac_bits)
    return (to_fixed(w, cfg.w16_frac, dtype=jnp.int16),
            to_fixed(b, cfg.frac_bits))


def make_gd_step_fns(quant_cfg: GdConfig):
    """The (prepare, update) closure pair of one GD step.

    ``prepare(carry) -> (wq, bq)`` quantizes the float32 carry for the
    broadcast; ``update(carry, reduced) -> (carry, None)`` dequantizes
    the reduced gradient and applies ``w -= scale_f32 * gw`` — all jnp
    ops, so ONE definition serves the host-orchestrated per-step loop,
    the fused :class:`~repro.core.pim.StepProgram` scan, and (batched
    over a lane axis) the scheduler's fused gangs; the paths cannot
    drift numerically.  ``quant_cfg`` is the weight-quantization config
    (LOG's LUT versions pass their collapsed int32/hyb base).

    Gradients stay on device: the old loop's per-step
    ``np.asarray``/``jnp.asarray`` ping-pong (ex-``_grad_to_float``) is
    gone — host floats materialize only at record/final points.  The
    update runs in float32 (including the bias, previously a float64
    python scalar) so the fused scan — which cannot do host float64 —
    and the serial loop share bit-exact weight trajectories.

    The carry is ``(w, b, s)``: the f32 update scale ``s`` travels IN
    the carry (constant across steps) because ``mul_round_f32`` needs
    it as a traced value inside the scan — see its caveat.
    """
    f = quant_cfg.frac_bits

    def apply(w, b, s, gw, gb):
        # mul_round_f32 pins the two-rounding (multiply, then subtract)
        # sequence: compiled as one scan body XLA CPU would otherwise
        # contract mul+sub into an FMA and the fused chunk would drift
        # ULPs from the serial loop (see fixed_point.mul_round_f32)
        return w - mul_round_f32(s, gw), b - mul_round_f32(s, gb), s

    if quant_cfg.version == "fp32":
        def prepare(carry):
            return carry[0], carry[1]

        def update(carry, reduced):
            w, b, s = carry
            gw = jnp.asarray(reduced["gw"], jnp.float32)
            gb = jnp.asarray(reduced["gb"], jnp.float32)
            return apply(w, b, s, gw, gb), None
        return prepare, update

    def prepare(carry):
        w, b, _ = carry
        return _quantize_weights(quant_cfg, w, b)

    def update(carry, reduced):
        w, b, s = carry
        # integer gradients arrive as summed fx_sum pairs (host-strategy
        # reduces as promoted numpy int64 — small enough for int32)
        gw = from_fixed_sum(reduced["gw"], f)
        gb = from_fixed_sum(reduced["gb"], f)
        return apply(w, b, s, gw, gb), None
    return prepare, update


def build_local_grad(cfg: GdConfig) -> Callable:
    """The per-core gradient kernel for ``cfg.version`` (unregistered).

    Exposed separately from the named registration so the scheduler's
    fused gang step can vmap the *same* per-core function over a job
    axis (DESIGN.md §7.3) — fused and serial paths share one kernel
    definition and cannot drift numerically."""
    if cfg.version == "fp32":
        return _local_grad_fp32
    if cfg.version == "int32":
        return make_local_grad_int32(cfg.frac_bits,
                                     dispatch.resolve_backend(
                                         cfg.kernel_backend))
    return make_local_grad_hyb(cfg.x8_frac, cfg.w16_frac, cfg.frac_bits)


def grad_kernel_name(cfg: GdConfig) -> str:
    """Registry name encoding every parameter baked into the kernel."""
    if cfg.version == "fp32":
        return "lin.grad/fp32"
    if cfg.version == "int32":
        be = dispatch.resolve_backend(cfg.kernel_backend)
        return f"lin.grad/int32/f{cfg.frac_bits}/{dispatch.backend_tag(be)}"
    return f"lin.grad/hyb/x{cfg.x8_frac}.w{cfg.w16_frac}.f{cfg.frac_bits}"


def _grad_kernel(pim: System, cfg: GdConfig):
    """Named per-core gradient kernel for the configured version
    (registered once per System; reused across fits and sweeps)."""
    return pim.named_kernel(grad_kernel_name(cfg),
                            lambda: build_local_grad(cfg))


def fit_steps(dataset, cfg: Optional[GdConfig] = None,
              eval_fn: Optional[Callable] = None,
              _local_override: Optional[Callable] = None, *,
              state: Optional[dict] = None):
    """Generator form of the training loop; the GdResult travels on
    StopIteration.  This is the gang-stepping surface the job scheduler
    interleaves (DESIGN.md §7.3); :func:`fit` drains it.

    Each ``next()`` advances one *scheduling step* and yields a
    :class:`~repro.systems.base.ChunkTick` — the number of GD iterations
    it covered (1 per host-orchestrated step, up to ``cfg.fuse_steps``
    per fused :class:`~repro.core.pim.StepProgram` chunk — DESIGN.md
    §9) carrying a lazy chunk-boundary snapshot of the carry.  Passing
    such a snapshot back as ``state`` resumes the fit exactly where it
    was preempted: the carry, the history, and the full minibatch rng
    stream restore, so a resumed integer fit is bit-identical to an
    uninterrupted one (DESIGN.md §11.2)."""
    cfg = cfg or GdConfig()
    assert cfg.version in VERSIONS, cfg.version
    pim = dataset.system
    n, f = dataset.n, dataset.n_features
    Xs, ys, mask = dataset.gd_view(cfg.version, cfg.frac_bits, cfg.x8_frac)

    if _local_override is not None:
        local = _local_override
    else:
        local = _grad_kernel(pim, cfg)

    n_pc = dataset.n_pc
    minibatch = bool(cfg.minibatch and cfg.minibatch < n_pc)
    # per-shard minibatches: n_shards == n_cores on PIM, 1 on a host
    # target (one resident image draws one batch)
    n_eff = cfg.minibatch * pim.n_shards if minibatch else n
    prepare, update = make_gd_step_fns(cfg)

    w = jnp.zeros(f, jnp.float32)
    b = jnp.float32(0.0)
    s = jnp.float32(cfg.lr * (2.0 / n_eff))
    history = []
    rng = np.random.RandomState(cfg.seed)
    it_done = 0
    if state is not None:
        arrays, meta = state["arrays"], state["meta"]
        w = jnp.asarray(arrays["w"], jnp.float32)
        b = jnp.asarray(arrays["b"], jnp.float32)
        s = jnp.asarray(arrays["s"], jnp.float32)
        it_done = int(meta["iters"])
        history = [tuple(h) for h in meta.get("history", [])]
        rng = unpack_rng(arrays, meta) or rng

    def record(it, wv, bv):
        if cfg.record_every and (it % cfg.record_every == 0
                                 or it == cfg.n_iters):
            metric = None
            if eval_fn:
                wh, bh = pim.read((wv, bv))
                metric = eval_fn(wh, float(bh))
            history.append((it, metric))

    def _make_snapshot(wv, bv, sv, it, ra, rm):
        """Snapshot closure bound to ONE chunk boundary's state.  Under
        pipelining the live carry has already been dispatched past this
        boundary by drain time, so everything the snapshot serializes is
        captured per boundary (the rng pack eagerly at dispatch — the
        stream advances with the next chunk's draws)."""
        def _snap():
            wh, bh, sh = pim.read((wv, bv, sv))
            arrays = {"w": np.asarray(wh, np.float32),
                      "b": np.asarray(bh, np.float32),
                      "s": np.asarray(sh, np.float32)}
            meta = {"iters": int(it),
                    "history": [[int(i), None if m is None else float(m)]
                                for i, m in history]}
            arrays.update(ra)
            meta.update(rm)
            return {"arrays": arrays, "meta": meta}
        return _snap

    def _snapshot():
        ra, rm = pack_rng(rng)
        return _make_snapshot(w, b, s, it_done, ra, rm)()

    if cfg.fuse_steps > 1:
        select = None
        if minibatch:
            # minibatch SGD fuses too (DESIGN.md §9.5): the select hook
            # slices every shard to the step's batch window; the
            # offsets arrive as scan xs, pre-drawn per chunk below from
            # the SAME rng stream the serial loop consumes — the fused
            # trajectory is the serial one, bit for bit
            def select(shards, off):
                return row_window(shards, off, cfg.minibatch)
        program = pim.step_program(
            local, prepare, update,
            name=(f"lin.step/{grad_kernel_name(cfg)}"
                  f"/lr{cfg.lr}/n{n_eff}"
                  + (f"/mb{cfg.minibatch}" if minibatch else "")),
            select=select)
        # Double-buffered chunk pipeline (DESIGN.md §14.1): dispatch
        # chunk N+1, then drain boundary N — record/eval and the
        # snapshot closure read the boundary's own carry while the next
        # chunk executes.  The only host reads are on drained
        # boundaries, so the device never waits on record work.
        pipe = ChunkPipeline(program, max(1, int(cfg.pipeline_depth)))

        def _drain(bnd):
            nonlocal it_done
            it_done, ra, rm = bnd.tag
            bw, bb, bs = bnd.carry
            record(it_done, bw, bb)
            return ChunkTick(bnd.k, _make_snapshot(bw, bb, bs, it_done,
                                                   ra, rm))

        # resume replays identical chunk boundaries: chunk_schedule is a
        # deterministic function of the iteration index (DESIGN.md §11.2)
        it_disp = it_done
        for k in chunk_schedule(cfg.n_iters, cfg.fuse_steps,
                                cfg.record_every, start=it_done):
            xs = None
            if minibatch:
                xs = jnp.asarray(
                    [rng.randint(0, n_pc - cfg.minibatch + 1)
                     for _ in range(k)], jnp.int32)
            it_disp += k
            # rng packed AFTER this chunk's draws: restoring boundary N
            # replays chunk N+1's batch offsets bit-exactly
            (w, b, s), drained = pipe.dispatch(
                (w, b, s), (Xs, ys, mask), k, xs=xs,
                tag=(it_disp, *pack_rng(rng)))
            for bnd in drained:
                yield _drain(bnd)
        for bnd in pipe.flush():
            yield _drain(bnd)
    else:
        for it in range(it_done, cfg.n_iters):
            wq, bq = pim.broadcast(prepare((w, b, s)))
            if minibatch:
                # SGD: every core samples the same per-core slice offset
                # (keeps shards aligned; bank-resident data never moves)
                start = int(rng.randint(0, n_pc - cfg.minibatch + 1))
                args = row_window((Xs, ys, mask), start, cfg.minibatch)
            else:
                args = (Xs, ys, mask)
            partial = pim.map_reduce(local, args, (wq, bq))
            (w, b, s), _ = update((w, b, s), partial)
            it_done = it + 1
            record(it_done, w, b)
            yield ChunkTick(1, _snapshot)
    w, b = pim.read((w, b))
    return GdResult(w=np.asarray(w, np.float32), b=float(b),
                    history=history, n_iters=cfg.n_iters)


def fit(dataset, cfg: Optional[GdConfig] = None,
        eval_fn: Optional[Callable] = None,
        _local_override: Optional[Callable] = None) -> GdResult:
    """Full PIM training loop over a bank-resident PimDataset: iterate
    (kernel -> reduce -> host update -> broadcast) until cfg.n_iters.
    The dataset's quantized view is materialized at most once per
    (version, Q-format) — repeated fits reuse the resident shards."""
    return run_steps(fit_steps(dataset, cfg, eval_fn, _local_override))


def train(X: np.ndarray, y: np.ndarray, pim: System,
          cfg: Optional[GdConfig] = None,
          eval_fn: Optional[Callable] = None,
          _local_override: Optional[Callable] = None) -> GdResult:
    """Deprecated shim: re-partitions (X, y) on every call.  Prefer
    ``fit(pim.put(X, y), cfg)`` which keeps the shards bank-resident
    across fits (repro.api)."""
    warnings.warn("linreg.train(X, y, pim, ...) is deprecated; use "
                  "linreg.fit(pim.put(X, y), cfg)", DeprecationWarning,
                  stacklevel=2)
    from ..api.dataset import as_dataset
    return fit(as_dataset(X, y, pim), cfg, eval_fn, _local_override)

# The CPU comparison point (paper §5.4) is no longer an ad-hoc numpy
# loop here: run this same workload on repro.systems.HostSystem — the
# processor-centric System target — e.g.
# ``linreg.fit(make_system("host").put(X, y), GdConfig("fp32"))``.
