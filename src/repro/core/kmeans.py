"""K-Means clustering on the PIM system (paper §3.4, Lloyd's method).

PIM flow exactly as §3.4: the training set is partitioned over PIM cores and
quantized to 16-bit integers; per iteration every core (1) finds each
point's nearest centroid with integer distance arithmetic, (2) accumulates
per-cluster per-coordinate sums + counts; the host (3) reduces partials,
recomputes centroids in float, checks the relative Frobenius norm for
convergence, and re-broadcasts quantized centroids.  The whole algorithm is
restarted ``n_init`` times; the host keeps the clustering with the lowest
inertia (within-cluster sum of squares), which the PIM cores compute after
convergence.

Numerics adaptation (DESIGN.md §2): UPMEM accumulates distances/sums in
int64; TPUs have no fast int64, so we quantize coordinates to +-2047
(12-bit range stored in int16), which keeps the int32 distances exact for
up to 2^9 features.  A coordinate sum does not fit one int32: one cluster
of more than 2^31 / 2047 (about 1.05M) rows near the range limit wraps,
and at the Higgs shape (11M rows, K=16) a cluster often holds more.  So
each core returns its sums as ``fx_sum`` pairs (``core/fixed_point.py``:
``hi * 256 + lo``), the cores' pairs add element-wise, and the host (or
the fused update) turns the reduced pair into a float.  The paper's own
quantization (+-32767) exists to avoid the same overflow problem on the
DPU; quality parity is preserved (ARI ~ 0.999 vs float CPU, §5.1.4).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..elastic.state import pack_rng, unpack_rng
from ..kernels import dispatch
from ..obs import TRACER
from ..systems import (ChunkPipeline, ChunkTick, System, chunk_schedule,
                       run_steps)
from .fixed_point import from_fixed_sum, fx_pair_value
from .metrics import frobenius_shift

# 12-bit symmetric range stored in int16 (see docstring).  The quantizing
# + sharding path, PimDataset.kmeans_view (repro/api/dataset.py), imports
# this constant — single source of truth.
QUANT_RANGE = 2047

#: "int16" is the paper's PIM version (quantized Lloyd's); "fp32" is the
#: processor-centric float path — the baseline the paper compares
#: against (sklearn, §5.1.4), now runnable on ANY System through the
#: same trainer (DESIGN.md §10.3).
VERSIONS = ("int16", "fp32")


@dataclasses.dataclass
class KMeansConfig:
    k: int = 16
    max_iters: int = 300
    tol: float = 1e-4           # relative Frobenius norm (paper §5.1.4)
    n_init: int = 1
    seed: int = 0
    #: data/arithmetic precision: "int16" (paper's quantized PIM
    #: version) or "fp32" (un-quantized float Lloyd's — the processor-
    #: centric baseline; no quantization round-trip)
    version: str = "int16"
    #: kernel backend for the assignment hot path (None = auto-select;
    #: see repro.kernels.dispatch) — all backends are numerically
    #: identical (integer ops, asserted by the parity tests)
    kernel_backend: Optional[str] = None
    #: step fusion (DESIGN.md §9): compile this many Lloyd's iterations
    #: into ONE lax.scan launch per chunk.  Convergence is checked on
    #: device (a ``done`` flag in the scan carry freezes the centroids),
    #: so a chunk may cover fewer *effective* iterations than its length;
    #: the host still stops draining chunks at the first converged one.
    #: The fused update recomputes centroids in float32 on device where
    #: the per-step host loop uses float64 — inertia/centroids agree to
    #: float tolerance, not bit-exactly (the assignment kernel itself is
    #: integer and exact).  1 = the paper's host-orchestrated loop.
    fuse_steps: int = 1
    #: chunk pipelining (DESIGN.md §14.1): fused chunks in flight before
    #: the host drains a boundary (2 = double-buffered, 1 = serial
    #: cadence).  The done-latch makes overshot in-flight chunks frozen
    #: no-ops, so pipelined convergence is exact — a discarded chunk
    #: never changes the centroids.  Ignored unless ``fuse_steps > 1``.
    pipeline_depth: int = 2


@dataclasses.dataclass
class KMeansResult:
    centroids: np.ndarray       # float32 [k, F] (dequantized)
    inertia: float
    n_iters: int
    labels: Optional[np.ndarray] = None


def _assign_kernel_factory(k: int, backend=None, quantized: bool = True):
    """Assignment + accumulation.

    The int16 (PIM) version routes through the kernel-dispatch layer
    (op ``kmeans_assign``: Pallas on TPU, jnp oracle elsewhere); the
    fp32 (processor-centric baseline) version is an inline float
    distance + one-hot accumulation — no quantization, native float
    matmul, the paper's sklearn-style hot loop.

    The int16 version's ``sums`` are int32 ``fx_sum`` pairs ``(K, F,
    2)``, the fp32 version's plain float32 ``(K, F)``.  Neither path has
    a validity-mask concept, so padding is corrected here: shard padding
    rows are all-zero vectors (see ``PimSystem.shard_rows``), which
    contribute nothing to ``sums`` and exactly one spurious count at
    their assigned label — subtracted via a masked one-hot.
    """
    be = dispatch.resolve_backend(backend)

    def _kernel(Xq, valid, Cq):
        if quantized:
            labels, sums, counts = dispatch.launch(
                "kmeans_assign", Xq, Cq, backend=be)
        else:
            x = Xq
            c = Cq
            # same tie-breaking expression as the quantized op: the
            # per-row ||x||^2 constant cannot change an argmin
            dist = jnp.sum(c * c, axis=1)[None, :] - 2.0 * (x @ c.T)
            labels = jnp.argmin(dist, axis=1).astype(jnp.int32)
            oh = (labels[:, None] ==
                  jnp.arange(k, dtype=jnp.int32)[None, :])
            sums = oh.astype(jnp.float32).T @ x
            counts = jnp.sum(oh.astype(jnp.int32), axis=0)
        pad_oh = ((labels[:, None] ==
                   jnp.arange(k, dtype=jnp.int32)[None, :])
                  & ~valid[:, None]).astype(jnp.int32)
        return {"sums": sums, "counts": counts - jnp.sum(pad_oh, axis=0)}
    return _kernel


def _inertia_kernel_factory(k: int, quantized: bool = True):
    def _kernel(Xq, valid, Cq):
        acc = jnp.int32 if quantized else jnp.float32
        x = Xq.astype(acc)
        c = Cq.astype(acc)
        cross = x @ c.T
        xnorm = jnp.sum(x * x, axis=1)
        cnorm = jnp.sum(c * c, axis=1)
        dist = xnorm[:, None] - 2 * cross + cnorm[None, :]
        best = jnp.min(dist, axis=1)
        # int32 sums can overflow over a whole shard: accumulate in f32 on
        # the way out (the host reduces in f64)
        return {"inertia": jnp.sum(
            jnp.where(valid, best, 0).astype(jnp.float32))}
    return _kernel


def _labels_kernel_factory(k: int, quantized: bool = True):
    """Labels-only predict path: a plain argmin over the same distance
    expression the assignment kernel uses (identical tie-breaking),
    WITHOUT routing through the full assign+accumulate kernel — a
    Pallas kernel computes every declared output, so the dispatch op
    would materialize (K, F) sums nobody reads on the inference path."""
    def _kernel(Xq, valid, Cq):
        acc = jnp.int32 if quantized else jnp.float32
        x = Xq.astype(acc)
        c = Cq.astype(acc)
        dist = jnp.sum(c * c, axis=1)[None, :] - 2 * (x @ c.T)
        return jnp.argmin(dist, axis=1).astype(jnp.int32)
    return _kernel


def _make_lloyd_step_fns(cfg: KMeansConfig):
    """(prepare, update) for one fused Lloyd's iteration (DESIGN.md §9).

    Carry: ``(C float32 [k,F] in quantized units, done bool, n_it
    int32)``.  ``done`` latches once the relative Frobenius shift drops
    below ``cfg.tol`` and freezes the centroids, so a chunk that
    overshoots convergence is a no-op for the tail steps; ``n_it``
    counts only the steps taken while not yet converged — matching the
    host loop's iteration count exactly."""
    tol = np.float32(cfg.tol)
    quantized = cfg.version == "int16"

    def prepare(carry):
        C, _, _ = carry
        if quantized:
            return (jnp.round(C).astype(jnp.int16),)
        return (C,)

    def update(carry, reduced):
        C, done, n_it = carry
        sums = (from_fixed_sum(reduced["sums"], 0) if quantized
                else jnp.asarray(reduced["sums"], jnp.float32))
        counts = jnp.asarray(reduced["counts"], jnp.float32)
        newC = jnp.where(counts[:, None] > 0,
                         sums / jnp.maximum(counts[:, None], 1.0), C)
        shift = (jnp.linalg.norm(newC - C)
                 / jnp.maximum(jnp.linalg.norm(C), 1e-12))
        newC = jnp.where(done, C, newC)
        n_it = n_it + jnp.where(done, 0, 1).astype(jnp.int32)
        done = done | (shift < tol)
        return (newC, done, n_it), None
    return prepare, update


def fit_steps(dataset, cfg: Optional[KMeansConfig] = None,
              return_labels: bool = True, *,
              state: Optional[dict] = None):
    """Generator form of Lloyd's: one assign/update scheduling step per
    ``next()`` (across all ``n_init`` restarts), KMeansResult on
    StopIteration — the gang-stepping surface; :func:`fit` drains it.
    Each ``next()`` yields a :class:`~repro.systems.base.ChunkTick`:
    the number of Lloyd's iterations it covered (1, or a whole
    ``cfg.fuse_steps`` scan chunk — DESIGN.md §9) with a lazy snapshot
    of the restart state (centroids, done-latch, restart index, rng
    stream, best-so-far).  Pass a snapshot back as ``state`` to resume
    mid-restart bit-exactly: the rng restores to the same stream
    position, so later restarts draw the same init points an
    uninterrupted fit would (DESIGN.md §11.2).
    The end-of-restart inertia/labels passes don't get their own step;
    they run at the head of the ``next()`` that follows convergence."""
    cfg = cfg or KMeansConfig()
    assert cfg.version in VERSIONS, cfg.version
    quantized = cfg.version == "int16"
    pim = dataset.system
    n = dataset.n
    rng = np.random.RandomState(cfg.seed)
    view = dataset.kmeans_view(cfg.version)
    Xs, valid = view.shards, view.mask
    Xq_np, scale = view.host_q, view.scale

    def _cast_centroids(C):
        """Broadcast form of the carry: rounded int16 on the quantized
        path (the paper's re-quantized centroids), plain float32 on the
        processor-centric fp32 path."""
        if quantized:
            return jnp.asarray(np.round(C).astype(np.int16))
        return jnp.asarray(C, jnp.float32)

    be = dispatch.resolve_backend(cfg.kernel_backend)
    tag = dispatch.backend_tag(be)
    # the int16 names predate the fp32 version and tests/benchmarks
    # match them verbatim; fp32 kernels get their own namespace
    vtag = "" if quantized else "fp32/"
    assign_k = pim.named_kernel(
        f"kme.assign/{vtag}k{cfg.k}/{tag}",
        lambda: _assign_kernel_factory(cfg.k, be, quantized))
    inertia_k = pim.named_kernel(
        f"kme.inertia/{vtag}k{cfg.k}",
        lambda: _inertia_kernel_factory(cfg.k, quantized))
    labels_k = pim.named_kernel(
        f"kme.labels/{vtag}k{cfg.k}",
        lambda: _labels_kernel_factory(cfg.k, quantized))

    program = None
    if cfg.fuse_steps > 1:
        prepare, update = _make_lloyd_step_fns(cfg)
        program = pim.step_program(
            assign_k, prepare, update,
            name=f"kme.step/{vtag}k{cfg.k}/{tag}/tol{cfg.tol}/n{n}")

    best: Optional[KMeansResult] = None
    init0 = 0
    it_total = 0        # iterations yielded across all restarts
    resume: Optional[dict] = None
    if state is not None:
        arrays, meta = state["arrays"], state["meta"]
        init0 = int(meta["init"])
        it_total = int(meta["iters"])
        resume = {"C": np.asarray(arrays["C"], np.float32),
                  "done": bool(meta["done"]),
                  "n_it": int(meta["n_it"]),
                  "it_sched": int(meta["it_sched"])}
        if meta.get("has_best"):
            best = KMeansResult(
                centroids=np.asarray(arrays["best_centroids"],
                                     np.float32),
                inertia=float(meta["best_inertia"]),
                n_iters=int(meta["best_n_iters"]),
                labels=(np.asarray(arrays["best_labels"])
                        if "best_labels" in arrays else None))
        restored = unpack_rng(arrays, meta)
        if restored is not None:
            rng = restored

    init = init0
    C = None
    done = False
    n_it = 0
    it_sched = 0        # chunk-scheduled iterations (fused resume key)

    def _make_snapshot(C_v, done_v, n_it_v, it_total_v, it_sched_v,
                       ra, rm):
        """Snapshot closure bound to one chunk boundary's state.  Under
        pipelining the device carry has been dispatched past this
        boundary by drain time; ``best``/``init`` stay live — they only
        change between restarts, and every boundary of a restart drains
        (or is discarded) before the restart ends (DESIGN.md §14.1)."""
        def _snap():
            arrays = {"C": np.asarray(pim.read(C_v), np.float32)}
            meta = {"iters": int(it_total_v), "init": int(init),
                    "done": bool(done_v), "n_it": int(n_it_v),
                    "it_sched": int(it_sched_v),
                    "has_best": best is not None}
            if best is not None:
                arrays["best_centroids"] = np.asarray(best.centroids,
                                                      np.float32)
                meta["best_inertia"] = float(best.inertia)
                meta["best_n_iters"] = int(best.n_iters)
                if best.labels is not None:
                    arrays["best_labels"] = np.asarray(best.labels)
            arrays.update(ra)
            meta.update(rm)
            return {"arrays": arrays, "meta": meta}
        return _snap

    def _snapshot():
        ra, rm = pack_rng(rng)
        return _make_snapshot(C, done, n_it, it_total, it_sched,
                              ra, rm)()

    for init in range(init0, cfg.n_init):
        if resume is not None:
            # re-enter the preempted restart: NO new init draw — the
            # rng stream was saved post-draw, so later restarts stay
            # aligned with an uninterrupted fit
            C, done = resume["C"], resume["done"]
            n_it, it_sched = resume["n_it"], resume["it_sched"]
            resume = None
        else:
            # host picks random points as initial centroids (paper:
            # random init)
            with TRACER.span("repro.init", "fit", "step"):
                idx = rng.choice(n, size=cfg.k, replace=False)
                C = Xq_np[idx].astype(np.float32)       # quantized units
            done = False
            n_it = 0
            it_sched = 0
        if program is not None:
            # Double-buffered chunk pipeline (DESIGN.md §14.1): the
            # convergence flag of boundary N is read while chunk N+1
            # executes.  The done-latch freezes a converged carry, so
            # the overshot in-flight chunk is a frozen no-op — it is
            # discarded unread, and the converged boundary's carry is
            # the exact serial result.  Iteration counters advance at
            # drain time (from dispatch-side tags), so discarded chunks
            # never count.
            dcarry = (jnp.asarray(C), jnp.asarray(bool(done)),
                      jnp.asarray(n_it, jnp.int32))
            pipe = ChunkPipeline(program, max(1, int(cfg.pipeline_depth)))
            final = None        # carry of the last drained boundary

            def _drain(bnd):
                nonlocal it_sched, it_total
                it_sched, it_total, ra, rm = bnd.tag
                return ChunkTick(
                    bnd.k, _make_snapshot(bnd.carry[0], bnd.carry[1],
                                          bnd.carry[2], it_total,
                                          it_sched, ra, rm))

            disp_sched, disp_total = it_sched, it_total
            stop = bool(done)   # resumed post-convergence: dispatch nothing
            for k in chunk_schedule(cfg.max_iters, cfg.fuse_steps, 0,
                                    start=it_sched):
                if stop:
                    break
                disp_sched += k
                disp_total += k
                dcarry, drained = pipe.dispatch(
                    dcarry, (Xs, valid), k,
                    tag=(disp_sched, disp_total, *pack_rng(rng)))
                for bnd in drained:
                    final = bnd.carry
                    yield _drain(bnd)
                    if bool(pim.read(bnd.carry[1])):  # converged here
                        stop = True
                        break
            if not stop:
                for bnd in pipe.flush():
                    final = bnd.carry
                    yield _drain(bnd)
                    if bool(pim.read(bnd.carry[1])):
                        break
            if final is not None:
                C, n_it = pim.read((final[0], final[2]))
                C = C.astype(np.float32)
                n_it = int(n_it)
        else:
            while not done and n_it < cfg.max_iters:
                Cq = pim.broadcast((_cast_centroids(C),))[0]
                part = pim.read(pim.map_reduce(assign_k, (Xs, valid), (Cq,)))
                sums = (fx_pair_value(part["sums"]) if quantized
                        else np.asarray(part["sums"], np.float64))
                counts = np.asarray(part["counts"], np.float64)
                newC = np.where(counts[:, None] > 0,
                                sums / np.maximum(counts[:, None], 1), C)
                shift = frobenius_shift(C, newC)
                C = newC.astype(np.float32)
                n_it += 1
                it_sched = n_it
                done = shift < cfg.tol
                it_total += 1
                yield ChunkTick(1, _snapshot)
        with TRACER.span("repro.finish", "fit", "step"):
            part = pim.read(pim.map_reduce(
                inertia_k, (Xs, valid), (_cast_centroids(C),)))
            # inertia needs + ||x||^2 which the kernel includes; convert units
            inertia = float(part["inertia"]) * float(scale) ** 2
            if best is None or inertia < best.inertia:
                best = KMeansResult(centroids=C * scale, inertia=inertia,
                                    n_iters=n_it)
                if return_labels:
                    lbl = pim.map_elementwise(
                        labels_k, (Xs, valid), (_cast_centroids(C),))
                    best.labels = pim.read(lbl).reshape(-1)[: n]
    return best


def fit(dataset, cfg: Optional[KMeansConfig] = None,
        return_labels: bool = True) -> KMeansResult:
    """Lloyd's over a bank-resident PimDataset.  The int16-quantized view
    is materialized once; all ``n_init`` restarts — and any later refit
    with different (k, seed, tol) — reuse the resident shards."""
    return run_steps(fit_steps(dataset, cfg, return_labels))


def train(X: np.ndarray, pim: System,
          cfg: Optional[KMeansConfig] = None,
          return_labels: bool = True) -> KMeansResult:
    """Deprecated shim: re-quantizes + re-partitions X on every call.
    Prefer ``fit(pim.put(X), cfg)`` (repro.api)."""
    warnings.warn("kmeans.train(X, pim, ...) is deprecated; use "
                  "kmeans.fit(pim.put(X), cfg)", DeprecationWarning,
                  stacklevel=2)
    from ..api.dataset import as_dataset
    return fit(as_dataset(X, None, pim), cfg, return_labels)

# The CPU comparison point (float Lloyd's — the paper uses sklearn) is
# no longer an ad-hoc numpy loop here: run version="fp32" on
# repro.systems.HostSystem, e.g. ``kmeans.fit(make_system("host").
# put(X), KMeansConfig(version="fp32"))`` — same trainer, no
# quantization round-trip.
