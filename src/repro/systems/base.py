"""The backend-portable ``System`` protocol (DESIGN.md §10).

The paper's central contribution is the processor-centric vs
memory-centric comparison (Figs. 13-17, Tables 5-7): every workload is
evaluated on a real PIM machine AND on matched CPU/GPU baselines driven
through identical harnesses.  This module makes that comparison a
first-class API: :class:`System` is the abstract execution surface the
trainers, the estimator facade, the workload registry, the scheduler,
and the fused step engine are written against, with three
implementations:

  ``PimSystem``        (systems/pim.py)       the paper's memory-centric
                       target: data sharded across banks, host-
                       orchestrated reduce, quantized hot loops.
  ``HostSystem``       (systems/host.py)      the processor-centric
                       baseline: one resident image, fp32 jnp hot
                       loops, ``TransferStats`` counting DRAM traffic.
  ``ModeledGpuSystem`` (systems/gpu_model.py) HostSystem numerics with
                       time/energy reported through a calibrated A100
                       roofline model (launch/roofline.py).

The surface (shared by all systems):
  put / shard_rows / row_validity_mask / broadcast     data placement
  register_kernel / named_kernel / registered_kernels  kernel registry
  map_reduce / map_reduce_custom / map_elementwise     execution
  step_program                                         fused k-step scan
  stats (TransferStats), slice(lease)                  accounting, tenancy

Per-system behavior lives in a small set of overridable hooks — the
placement methods plus the ``_charge_*`` accounting hooks — so the
execution semantics (kernel resolution, jit caching, reduce strategies,
scan fusion) are defined exactly once and cannot drift between targets.
Ghose et al. (arXiv:1907.12947) argue a PIM programming model must hide
the memory-centric/processor-centric split from the workload author;
here a trainer sees only ``dataset.system`` and never knows which side
it is running on.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import threading
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import TRACER


class ReduceVia(enum.Enum):
    """Legacy reduction selector (kept for config compatibility; the
    per-call ``strategy=`` argument accepts these, their string values,
    or a :class:`ReduceStrategy` instance)."""

    FABRIC = "fabric"   # on-fabric psum (TPU-native; strictly cheaper)
    HOST = "host"       # explicit host round trip (paper-faithful schedule)
    HIERARCHICAL = "hierarchical"  # rank-level fabric sum + host combine


@dataclasses.dataclass
class TransferStats:
    """Byte counters mirroring the paper's CPU-PIM / PIM-CPU breakdowns.

    The counters are shared across systems but their *semantics* are
    per-system (DESIGN.md §10.2):

    On a :class:`~repro.systems.pim.PimSystem`, ``cpu_to_pim`` counts
    every host->bank byte (dataset shards AND model broadcasts) and
    ``pim_to_cpu`` the reduce legs back — the paper's transfer
    breakdown.  On a :class:`~repro.systems.host.HostSystem` there is no
    CPU<->PIM boundary; those counters stay zero and ``dram_bytes``
    counts the memory traffic of the hot loop instead (the dataset
    bytes each training pass streams from DRAM — the processor-centric
    bottleneck the roofline model prices).

    ``shard_transfers``/``shard_bytes`` count dataset view
    materializations on every system, so callers can assert that a
    hyperparameter sweep over one :class:`PimDataset` pays for the
    partition exactly once (DESIGN.md §3).  ``kernel_launches`` counts
    host-issued kernel dispatches (one per ``map_reduce``/
    ``map_reduce_custom``/``map_elementwise`` call) — the scheduler's
    fused gang step is asserted against it (DESIGN.md §7.3).

    ``host_syncs`` counts host synchronization points — places where the
    host blocks on device results (one per ``map_reduce``/
    ``map_reduce_custom`` call, one per fused :class:`StepProgram`
    chunk).  The step-fusion engine's whole point is that a k-step chunk
    costs ONE sync instead of k (DESIGN.md §9).  It is counted by
    launch arithmetic, not where the host waits: ``device_reads``
    counts the calls of :meth:`System.read`, the one place the host
    blocks on device results and copies them back.

    ``snapshot()``/``delta(snapshot)`` make the counters attributable
    when several jobs share one system: snapshot before the job, delta
    after, and the job's own bytes fall out even though the globals keep
    interleaving (DESIGN.md §7.2).
    """

    cpu_to_pim: int = 0
    pim_to_cpu: int = 0
    inter_core_via_host: int = 0
    shard_transfers: int = 0
    shard_bytes: int = 0
    kernel_launches: int = 0
    host_syncs: int = 0
    device_reads: int = 0
    #: processor-centric targets only: bytes the training hot loop
    #: streams from DRAM (HostSystem / ModeledGpuSystem); 0 on PIM.
    dram_bytes: int = 0
    #: topology split of the reduce legs (PIM only — DESIGN.md §12.3):
    #: ``rank_local_bytes`` is intra-rank combine traffic (a rank-aligned
    #: HierarchicalReduce group folding its partials inside the rank);
    #: ``cross_rank_bytes`` is everything that crosses a rank boundary on
    #: its way to the host — the serialized leg the hierarchical cost
    #: model prices and contention-aware placement tries to localize.
    rank_local_bytes: int = 0
    cross_rank_bytes: int = 0
    #: EMB deferred-update accounting (DESIGN.md §15): ``flush_bytes``
    #: is the logical sparse update payload (ids + delta rows) shipped
    #: to the table shards by eager applies and deferred flushes alike —
    #: the counter the deferred-vs-eager traffic claim is asserted on.
    flush_bytes: int = 0
    #: actual wire bytes moved by int8 error-feedback compression
    #: (CompressedReduce and compressed EMB flushes) in place of the
    #: uncompressed payload counted above / in the reduce legs.
    compressed_bytes: int = 0

    def reset(self) -> None:
        for field in dataclasses.fields(TransferStats):
            setattr(self, field.name, 0)

    def snapshot(self) -> "TransferStats":
        """Point-in-time copy of every counter (a plain TransferStats).
        Taken under the mirroring lock so a caller-thread reading never
        sees a slice increment half-propagated to its parent."""
        with _STATS_LOCK:
            return TransferStats(
                **{f.name: getattr(self, f.name)
                   for f in dataclasses.fields(TransferStats)})

    def delta(self, snapshot: "TransferStats") -> "TransferStats":
        """Counters accumulated since ``snapshot`` was taken."""
        return TransferStats(
            **{f.name: getattr(self, f.name) - getattr(snapshot, f.name)
               for f in dataclasses.fields(TransferStats)})


_STAT_FIELDS = tuple(f.name for f in dataclasses.fields(TransferStats))

#: Serializes _MirrorStats increment mirroring: the scheduler's serve
#: thread charges slice counters while caller threads read ``stats()``
#: snapshots or submit work (DESIGN.md §14.2).  Reentrant because a
#: mirror's parent can itself be a mirror (slice-of-slice), nesting the
#: read-modify-write chain under one acquisition.
_STATS_LOCK = threading.RLock()


class _MirrorStats(TransferStats):
    """Slice-local counters that forward every *increment* to the parent
    system's stats.  ``reset()`` zeroes only the slice view — cumulative
    parent totals are never rolled back (only positive deltas mirror)."""

    def __init__(self, parent: TransferStats):
        object.__setattr__(self, "_parent", parent)
        super().__init__()

    def __setattr__(self, name, value):
        if name in _STAT_FIELDS:
            with _STATS_LOCK:
                delta = value - getattr(self, name, 0)
                if delta > 0:
                    setattr(self._parent, name,
                            getattr(self._parent, name) + delta)
                object.__setattr__(self, name, value)
            return
        object.__setattr__(self, name, value)


def check_lease_bounds(parent: "System", lease, unit: str = "cores") -> None:
    """Reject a lease extending past the parent's capacity (shared by
    every slice type — PimSlice, HostSlice, GpuModelSlice)."""
    if lease.stop > parent.config.n_cores:
        raise ValueError(f"lease {lease} exceeds the parent system "
                         f"({parent.config.n_cores} {unit})")


def adopt_parent_session(slice_: "System", parent: "System") -> None:
    """Wire a slice to its parent's session state: mirrored stats plus
    the shared kernel registry and jit cache (one compile serves every
    tenant).  Shared by the lane-scoped host/gpu slices; PimSlice keeps
    its own wiring because its cache sharing is backend-conditional."""
    slice_.stats = _MirrorStats(parent.stats)
    slice_._kernels = parent._kernels
    slice_._kernel_gen = parent._kernel_gen
    slice_._jit_cache = parent._jit_cache


def run_steps(gen):
    """Drain a trainer step generator and return its result.

    The iterative trainers expose ``fit_steps(dataset, cfg)`` generators
    (one host-orchestrated iteration per ``next()``) so the job
    scheduler can gang-step many fits concurrently; ``fit`` is simply
    this drain loop.  The fitted result travels on ``StopIteration``.
    Each ``next()`` is one ``repro.step`` span: one iteration when the
    trainer runs serially, one chunk when it fuses, and a last step
    that reads back the result.
    """
    while True:
        try:
            with TRACER.span("repro.step", "fit", "step"):
                next(gen)
        except StopIteration as stop:
            return stop.value


class ChunkTick(int):
    """What a resumable trainer's ``fit_steps`` yields per chunk.

    Behaves as the plain iteration count (an ``int`` — every existing
    consumer keeps working), but additionally carries a lazy
    ``snapshot()`` hook: calling it while the generator is suspended at
    this yield materializes the trainer's chunk-boundary state as a
    ``{"arrays": {...}, "meta": {...}}`` dict — the serializable carry
    the elastic job runtime checkpoints (DESIGN.md §11).  The snapshot
    is lazy so trainers pay the device->host copies only when someone
    (preemption, the scheduler's checkpoint cadence) actually asks.
    """

    def __new__(cls, iters: int, snapshot_fn: Optional[Callable] = None):
        tick = super().__new__(cls, iters)
        tick._snapshot_fn = snapshot_fn
        return tick

    @property
    def resumable(self) -> bool:
        return self._snapshot_fn is not None

    def snapshot(self) -> Optional[dict]:
        """Materialize the chunk-boundary trainer state (None when the
        trainer is not resumable).  Only valid while the generator that
        yielded this tick is suspended at the yield."""
        if self._snapshot_fn is None:
            return None
        return self._snapshot_fn()


def chunk_schedule(n_iters: int, fuse_steps: int, record_every: int,
                   start: int = 0):
    """Chunk sizes covering ``n_iters`` fused-step iterations, with
    record points forced onto chunk boundaries: each chunk is
    ``min(fuse_steps, next record point, remaining)`` (shared by the GD
    and K-Means trainers and the fused gang — DESIGN.md §9.3).

    ``start`` resumes the schedule mid-run (elastic restore, DESIGN.md
    §11): chunks continue from iteration ``start`` exactly as the
    uninterrupted schedule would have cut them — checkpoints always land
    on chunk boundaries, so a resumed fit replays the identical chunk
    sequence from that boundary on."""
    it = start
    while it < n_iters:
        k = min(fuse_steps, n_iters - it)
        if record_every:
            next_rec = (it // record_every + 1) * record_every
            k = min(k, next_rec - it)
        yield k
        it += k


# ---------------------------------------------------------------------------
# Reduction strategies (pluggable per map_reduce call).
# ---------------------------------------------------------------------------

class ReduceStrategy:
    """How per-shard partials are combined into the host-visible result.

    ``device_reduce`` runs inside the compiled step (traced); ``finalize``
    runs on the host afterwards; ``count_pim_to_cpu`` models the PIM->CPU
    bytes the schedule moves (PIM systems only — processor-centric
    systems bypass strategy byte accounting entirely, see
    ``System._charge_reduce``).  ``cache_token`` namespaces the jit cache.

    Step fusion (DESIGN.md §9): ``fusable`` says whether the schedule can
    run entirely on device inside a ``lax.scan`` chunk;
    ``device_reduce_full`` is the fully-on-device reduction the scan body
    uses (for :class:`HierarchicalReduce` it completes the host-combine
    leg on fabric); ``count_chunk`` is the analytic per-chunk byte
    accounting — the reduce still moves k× the single-step bytes even
    when the host round-trip is fused away.
    """

    name = "base"
    #: False when the per-step reduction needs the host (HostReduce): a
    #: StepProgram then degrades to per-step map_reduce syncs.
    fusable = True

    def bind(self, system: "System") -> "ReduceStrategy":
        """Resolve any topology-derived parameters against the system
        about to execute (called once per map_reduce / StepProgram).
        Base strategies have none — they bind to themselves;
        :class:`HierarchicalReduce` derives an unset ``group_size`` from
        the system's rank tree here."""
        return self

    def device_reduce(self, partials):
        return partials

    def device_reduce_full(self, partials):
        """Complete on-device reduction for use inside a fused scan."""
        return self.device_reduce(partials)

    def finalize(self, system: "System", out):
        return out

    def count_pim_to_cpu(self, system: "System", out) -> int:
        raise NotImplementedError

    def count_topology(self, system: "System", out) -> tuple:
        """Rank-level split ``(rank_local_bytes, cross_rank_bytes)`` of
        one step's reduce movement (DESIGN.md §12.3).  Flat schedules
        ship every partial over the host link — all bytes cross a rank
        boundary; :class:`HierarchicalReduce` reclassifies the
        intra-group leg as rank-local when its groups sit inside ranks.
        """
        return 0, self.count_pim_to_cpu(system, out)

    def count_chunk(self, system: "System", out, k: int) -> None:
        """Account k fused steps' reduce movement (``out`` is the
        abstract per-step ``device_reduce`` result)."""
        system.stats.pim_to_cpu += k * self.count_pim_to_cpu(system, out)
        rank_local, cross_rank = self.count_topology(system, out)
        system._charge_topology(k * rank_local, k * cross_rank)

    def cache_token(self):
        return self.name


def _kernel_name(kkey) -> str:
    """A kernel's name for trace args: its registered name, or the
    callable's own."""
    return kkey[1] if kkey[0] == "named" else getattr(kkey[1], "__name__",
                                                      "fn")


def _leaf_bytes(v) -> int:
    """nbytes of an array OR an abstract value (ShapeDtypeStruct)."""
    nb = getattr(v, "nbytes", None)
    if nb is None:
        nb = int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
    return int(nb)


def _tree_bytes(tree) -> int:
    return sum(_leaf_bytes(v) for v in jax.tree_util.tree_leaves(tree))


def _host_sum(tree, axis=0):
    """Promoted numpy reduction (int64 / float64 accumulators)."""
    return jax.tree_util.tree_map(
        lambda v: np.sum(np.asarray(v, np.int64)
                         if np.issubdtype(np.asarray(v).dtype, np.integer)
                         else np.asarray(v, np.float64), axis=axis),
        tree)


class FabricReduce(ReduceStrategy):
    """On-device sum over the cores axis (psum under shard_map)."""

    name = "fabric"

    def device_reduce(self, partials):
        return jax.tree_util.tree_map(lambda v: jnp.sum(v, axis=0),
                                      partials)

    def count_pim_to_cpu(self, system, out) -> int:
        # every core ships its partial of the reduced shape to the host
        return _tree_bytes(out) * system.config.n_cores

    def finalize(self, system, out):
        return out


class HostReduce(ReduceStrategy):
    """Paper-faithful schedule: per-core partials are copied to the host
    and reduced with numpy; the result lives on the host (the caller then
    ``broadcast``s the updated model, completing the round trip).

    Not fusable: the reduce itself IS a host round trip, so a
    :class:`StepProgram` chunk degrades to k per-step syncs (DESIGN.md
    §9) — faithful to the UPMEM topology, where fusing the update
    on-device would still leave per-step host reduction."""

    name = "host"
    fusable = False

    def count_pim_to_cpu(self, system, out) -> int:
        return _tree_bytes(out)  # stacked (n_cores, ...) leaves

    def finalize(self, system, out):
        return _host_sum(system.read(out))


class HierarchicalReduce(ReduceStrategy):
    """Two-level schedule: fabric sum inside each rank of ``group_size``
    cores, then a host combine of the rank partials — the PIM analogue of
    the multi-pod RS->AR->AG decomposition in distributed/collectives.py
    (each rank's leader ships 1/group_size of the flat-host bytes over the
    host link; see ``cross_pod_bytes``).

    ``group_size=None`` derives the group from the executing system's
    rank tree at :meth:`bind` time (the largest divisor of the core
    count that fits one rank) — the group that keeps the fabric leg
    rank-local instead of a hand-picked constant (DESIGN.md §12.3)."""

    def __init__(self, group_size: Optional[int] = 8):
        self.group_size = group_size
        self.name = f"hier{group_size}" if group_size is not None else "hier-auto"

    def bind(self, system: "System") -> "HierarchicalReduce":
        if self.group_size is not None:
            return self
        from .topology import DEFAULT_DPUS_PER_RANK  # no cycle: topology is leaf
        topo = getattr(system, "topology", None)
        cap = topo.dpus_per_rank if topo is not None else DEFAULT_DPUS_PER_RANK
        n = system.config.n_cores
        group = max((d for d in range(1, min(cap, n) + 1) if n % d == 0),
                    default=1)
        return HierarchicalReduce(group)

    def cache_token(self):
        return ("hier", self.group_size)

    def _groups(self, n_cores: int) -> int:
        g = self.group_size
        return n_cores // g if g > 1 and n_cores % g == 0 else 0

    def device_reduce(self, partials):
        def _grouped(v):
            n_cores = v.shape[0]
            n_groups = self._groups(n_cores)
            if not n_groups:        # awkward core count: flat host schedule
                return v
            return jnp.sum(
                v.reshape(n_groups, self.group_size, *v.shape[1:]), axis=1)
        return jax.tree_util.tree_map(_grouped, partials)

    def count_pim_to_cpu(self, system, out) -> int:
        return _tree_bytes(out)  # (n_groups, ...) rank partials

    def _groups_rank_local(self, system: "System") -> bool:
        """Do the reduce groups sit inside physical ranks?  True when
        the system exposes a topology whose rank is a whole multiple of
        the group (aligned groups never straddle a rank boundary)."""
        topo = getattr(system, "topology", None)
        return (topo is not None and self.group_size is not None
                and 1 < self.group_size <= topo.dpus_per_rank
                and topo.dpus_per_rank % self.group_size == 0)

    def count_topology(self, system, out) -> tuple:
        # Two legs per step: every core's partial folds into its group
        # (group_size x the rank-partial bytes), then the rank partials
        # cross to the host.  The intra-group leg is rank-local only
        # when the groups are rank-aligned; straddling groups drag it
        # across rank boundaries too.
        if not self._groups(system.config.n_cores):
            return 0, _tree_bytes(out)        # flat fallback: all cross
        out_bytes = _tree_bytes(out)
        intra = out_bytes * self.group_size
        if self._groups_rank_local(system):
            return intra, out_bytes
        return 0, intra + out_bytes

    def device_reduce_full(self, partials):
        """In a fused scan the rank partials combine on fabric instead of
        on the host (int32 accumulation — exact whenever the flat fabric
        sum is: the GD gradients and the KME cluster sums travel as
        ``fx_sum`` pairs, whose elements stay far inside int32)."""
        return jax.tree_util.tree_map(
            lambda v: jnp.sum(v, axis=0), self.device_reduce(partials))

    def count_chunk(self, system, out, k: int) -> None:
        # same per-step movement as the unfused schedule: each step the
        # rank partials leave the ranks AND cross the (modeled) host
        # link, k times per chunk
        system.stats.pim_to_cpu += k * self.count_pim_to_cpu(system, out)
        if self._groups(system.config.n_cores):
            system._charge_inter_core(k * _tree_bytes(out))
        rank_local, cross_rank = self.count_topology(system, out)
        system._charge_topology(k * rank_local, k * cross_rank)

    def finalize(self, system, out):
        # intra-rank movement happened "on fabric"; record the rank->host
        # leg separately so the hierarchy's saving is visible in the
        # stats (1/group_size of the flat-host bytes, same napkin as
        # collectives.cross_pod_bytes).  If the core count forced the
        # flat fallback, no rank-level reduction occurred — record none.
        # The write goes through the system hook: on a processor-centric
        # target there is no host link, and the counter must stay 0.
        if self._groups(system.config.n_cores):
            system._charge_inter_core(_tree_bytes(out))
        return _host_sum(system.read(out))


_STRATEGIES: dict[str, Callable[[], ReduceStrategy]] = {
    "fabric": FabricReduce,
    "host": HostReduce,
    "hierarchical": HierarchicalReduce,
    # topology-derived group (resolved per system at bind time)
    "hierarchical-auto": lambda: HierarchicalReduce(group_size=None),
}

StrategyLike = Union[None, str, ReduceVia, ReduceStrategy]


def resolve_reduce_strategy(spec: StrategyLike,
                            default: StrategyLike = None) -> ReduceStrategy:
    if spec is None:
        spec = default if default is not None else "fabric"
    if isinstance(spec, ReduceStrategy):
        return spec
    if isinstance(spec, ReduceVia):
        spec = spec.value
    if isinstance(spec, str) and spec in _STRATEGIES:
        return _STRATEGIES[spec]()
    raise ValueError(f"unknown reduce strategy {spec!r}; "
                     f"known: {sorted(_STRATEGIES)}")


# ---------------------------------------------------------------------------
# The System protocol.
# ---------------------------------------------------------------------------

class System:
    """Abstract execution target behind the workload-session API.

    Subclasses implement the data-placement surface (``shard_rows``,
    ``row_validity_mask``, ``broadcast``), declare their identity
    (``kind``, ``n_shards``), and override the ``_charge_*`` accounting
    hooks; the execution machinery — kernel registry, jit caching,
    reduce strategies, :class:`StepProgram` fusion — is shared and
    defined exactly once here.

    ``config`` must expose ``n_cores`` (the scheduling width the bank
    allocator carves — physical PIM cores, or thread-pool lanes on a
    host target), ``n_threads``, and ``reduce`` (the default strategy).
    ``n_shards`` is the *data-parallel* width of the leading shard axis
    — equal to ``n_cores`` on PIM, and 1 on processor-centric targets,
    which keep one resident image regardless of lane count.
    """

    #: target identity: "pim" | "host" | "gpu-model" (CLI spelling)
    kind: str = "abstract"
    #: True on processor-centric targets with native transcendentals:
    #: the LOG fp32 baseline then uses the exact sigmoid (the paper's
    #: MKL/cuML baselines), not the DPU Taylor expansion.
    exact_transcendentals: bool = False

    def __init__(self, config):
        self.config = config
        self.stats = TransferStats()
        self._jit_cache: dict = {}
        self._kernels: dict[str, Callable] = {}
        self._kernel_gen: dict[str, int] = {}
        #: trace timeline for this system's kernel launches (precomputed
        #: so the hot path never builds the string — DESIGN.md §13.2)
        self._trace_track = f"system:{self.kind}"

    # -- identity ------------------------------------------------------------

    @property
    def n_shards(self) -> int:
        """Width of the leading shard axis ``shard_rows`` produces."""
        raise NotImplementedError

    # -- data placement ------------------------------------------------------

    def put(self, X, y=None) -> "Any":
        """Place a dataset on this system ONCE and return a
        :class:`repro.api.dataset.PimDataset` handle.

        The handle owns the resident arrays, the validity mask, and
        per-version views (lazily materialized, cached), so repeated
        fits / restarts / sweeps reuse one placement per view."""
        from ..api.dataset import PimDataset  # local import: api -> systems
        return PimDataset(self, X, y)

    def put_table(self, weights, *, placement: str = "mod",
                  seed: int = 0) -> "Any":
        """Row-shard an embedding table across this system's bank
        extents ONCE and return a
        :class:`repro.api.table.ShardedTable` handle (the PimDataset
        sibling for sharded model state — DESIGN.md §15.1)."""
        from ..api.table import ShardedTable  # local import: api -> systems
        return ShardedTable(self, weights, placement=placement, seed=seed)

    def shard_rows(self, x: np.ndarray, pad_value=0) -> jnp.ndarray:
        """Partition rows: (n, ...) -> (n_shards, n_per_shard, ...)."""
        raise NotImplementedError

    def row_validity_mask(self, n: int) -> jnp.ndarray:
        """(n_shards, n_per_shard) bool mask marking real rows."""
        raise NotImplementedError

    def broadcast(self, tree: Any) -> Any:
        """Model-state broadcast to every execution site (accounted)."""
        raise NotImplementedError

    # -- kernel registry -----------------------------------------------------

    def register_kernel(self, name: str, fn: Callable) -> str:
        """Register (or replace) a named per-shard kernel.

        Re-registering a name with a different function bumps a generation
        counter, orphaning any compiled entries for the old function — a
        stale kernel can never be served for a new registration."""
        if self._kernels.get(name) is not fn:
            self._kernel_gen[name] = self._kernel_gen.get(name, -1) + 1
            self._kernels[name] = fn
        return name

    def named_kernel(self, name: str, builder: Callable[[], Callable]) -> str:
        """Register ``builder()`` under ``name`` unless already present.

        The idiom for parameterized kernel factories: encode the factory
        parameters in the name (e.g. ``"kme.assign/k=16"``) and the
        compiled kernel is reused across fits and restarts."""
        if name not in self._kernels:
            self.register_kernel(name, builder())
        return name

    def registered_kernels(self) -> tuple:
        """Sorted names of all registered kernels (diagnostics/tests).

        Trainer kernel names encode their dispatch routing — e.g.
        ``"kme.assign/k16/be=pallas_tpu"`` — so this is also how tests
        assert that a fit actually went through the kernel tier."""
        return tuple(sorted(self._kernels))

    def _resolve_kernel(self, kernel) -> tuple:
        """Map a kernel reference to (stable cache key, callable).

        Named kernels key by (name, generation).  Raw callables key by the
        function object itself — the cache then holds a strong reference,
        so the function cannot be collected and its identity can never be
        recycled for a different kernel (the id()-reuse bug this replaced).
        """
        if isinstance(kernel, str):
            fn = self._kernels.get(kernel)
            if fn is None:
                raise KeyError(
                    f"no kernel registered under {kernel!r}; "
                    f"known: {sorted(self._kernels)}")
            return ("named", kernel, self._kernel_gen[kernel]), fn
        if not callable(kernel):
            raise TypeError(f"kernel must be a registered name or a "
                            f"callable, got {type(kernel).__name__}")
        return ("fn", kernel), kernel

    # -- accounting hooks (per-system TransferStats semantics) ---------------

    def _charge_launch_operands(self, sharded, replicated) -> None:
        """Per-launch operand movement.  PIM: none (data is bank-
        resident).  Host targets: the pass streams the shards from DRAM.
        """

    def _charge_reduce(self, strat: ReduceStrategy, out) -> None:
        """Post-reduce movement of one map_reduce launch."""
        self.stats.pim_to_cpu += strat.count_pim_to_cpu(self, out)
        rank_local, cross_rank = strat.count_topology(self, out)
        self._charge_topology(rank_local, cross_rank)

    def _charge_reduce_custom(self, out) -> None:
        self.stats.pim_to_cpu += _tree_bytes(out) * self.config.n_cores
        # flat custom reduce: every per-core partial crosses to the host
        self._charge_topology(0, _tree_bytes(out) * self.config.n_cores)

    def _charge_topology(self, rank_local: int, cross_rank: int) -> None:
        """Rank-level classification of reduce movement (DESIGN.md
        §12.3).  Host targets override to a no-op: a single resident
        image has no rank tree."""
        self.stats.rank_local_bytes += rank_local
        self.stats.cross_rank_bytes += cross_rank

    def _charge_inter_core(self, nbytes: int) -> None:
        """Modeled inter-core-via-host movement (HierarchicalReduce's
        rank->host leg).  Host targets override to a no-op: there is no
        host link between shards of a single resident image."""
        self.stats.inter_core_via_host += nbytes

    def _charge_elementwise(self, sharded, replicated) -> None:
        self.stats.cpu_to_pim += sum(
            np.asarray(v).nbytes for v in replicated) * self.config.n_cores

    def _charge_chunk(self, carry, sharded, reduced_shape,
                      strat: ReduceStrategy, k: int) -> None:
        """Analytic accounting of one fused k-step chunk (DESIGN.md
        §9.2): the carry (model state) enters the banks once per chunk;
        the reduce legs move k× the single-step bytes."""
        self.stats.cpu_to_pim += _tree_bytes(carry) * self.config.n_cores
        strat.count_chunk(self, reduced_shape, k)

    def _charge_chunk_boundary(self, carry, outs) -> None:
        """One sync per chunk boundary: final carry + stacked emits."""
        self.stats.pim_to_cpu += _tree_bytes(carry) + _tree_bytes(outs)

    def _record_execution(self, key, step, args, k: int = 1) -> None:
        """Post-launch modeling hook (``ModeledGpuSystem`` prices the
        compiled program on a roofline here).  ``step`` is the jitted
        callable, ``args`` its call arguments, ``k`` the number of
        training iterations the launch covered."""

    # -- execution ------------------------------------------------------------

    def read(self, tree):
        """Wait for device results and copy them to the host as numpy:
        the one place the host blocks on the device.  Each call is one
        ``repro.read`` span and one ``device_reads``."""
        self.stats.device_reads += 1
        with TRACER.span("repro.read", self._trace_track, "read"):
            return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))

    def map_reduce(self, kernel, sharded: tuple, replicated: tuple,
                   strategy: StrategyLike = None):
        """Run ``kernel(*shard_args, *replicated)`` on every shard and
        reduce the resulting pytree across the shard axis.

        ``kernel`` is a registered name or a callable.  ``strategy`` picks
        the reduction schedule per call ("fabric" | "host" |
        "hierarchical" | a ReduceStrategy); default is the system config.
        Movement is tracked for every schedule in the system's own
        TransferStats semantics."""
        strat = resolve_reduce_strategy(strategy, self.config.reduce).bind(self)
        kkey, fn = self._resolve_kernel(kernel)
        key = ("map_reduce", kkey, len(sharded), len(replicated),
               strat.cache_token())
        step = self._jit_cache.get(key)
        if step is None:
            step = self._build_step(fn, strat)
            self._jit_cache[key] = step
        self.stats.kernel_launches += 1
        self.stats.host_syncs += 1
        self._charge_launch_operands(sharded, replicated)
        with TRACER.span("repro.launch", self._trace_track, "launch",
                         op="map_reduce", kernel=_kernel_name(kkey)):
            out = step(tuple(sharded), tuple(replicated))
        self._record_execution(key, step, (tuple(sharded),
                                           tuple(replicated)))
        self._charge_reduce(strat, out)
        return strat.finalize(self, out)

    def map_reduce_custom(self, kernel, sharded: tuple,
                          replicated: tuple, reduce: dict):
        """Like map_reduce but with per-key reduce ops ("sum"|"min"|"max").

        Used by DTR's min-max command (the host reduces per-core extrema).
        """
        kkey, fn = self._resolve_kernel(kernel)
        key = ("custom", kkey, tuple(sorted(reduce.items())))
        step = self._jit_cache.get(key)
        if step is None:
            def _step(sharded_, replicated_, _fn=fn):
                partials = self._per_core(_fn, sharded_, replicated_)
                return {k: (jnp.sum(v, axis=0) if reduce[k] == "sum"
                            else jnp.min(v, axis=0) if reduce[k] == "min"
                            else jnp.max(v, axis=0))
                        for k, v in partials.items()}
            step = jax.jit(_step)
            self._jit_cache[key] = step
        self.stats.kernel_launches += 1
        self.stats.host_syncs += 1
        self._charge_launch_operands(sharded, replicated)
        with TRACER.span("repro.launch", self._trace_track, "launch",
                         op="custom", kernel=_kernel_name(kkey)):
            out = step(tuple(sharded), tuple(replicated))
        self._record_execution(key, step, (tuple(sharded),
                                           tuple(replicated)))
        self._charge_reduce_custom(out)
        return out

    def map_elementwise(self, kernel, sharded: tuple, replicated: tuple):
        """Per-shard kernel with *no* reduction: output stays resident
        (DTR's split-commit).  Only the replicated command arguments
        cross the boundary; counted accordingly."""
        kkey, fn = self._resolve_kernel(kernel)
        key = ("elem", kkey)
        step = self._jit_cache.get(key)
        if step is None:
            step = jax.jit(
                lambda s, r, _fn=fn: self._per_core(_fn, s, r))
            self._jit_cache[key] = step
        self.stats.kernel_launches += 1
        self._charge_elementwise(sharded, replicated)
        with TRACER.span("repro.launch", self._trace_track, "launch",
                         op="elem", kernel=_kernel_name(kkey)):
            out = step(tuple(sharded), tuple(replicated))
        self._record_execution(key, step, (tuple(sharded),
                                           tuple(replicated)))
        return out

    def _per_core(self, local_fn, sharded, replicated):
        """Trace the per-shard kernel (vmap over the shard axis)."""
        return jax.vmap(lambda *s: local_fn(*s, *replicated))(*sharded)

    def _build_step(self, local_fn, strat: ReduceStrategy):
        """Compile one step: per-shard kernel + on-device reduce stage."""
        def step(sharded, replicated):
            partials = self._per_core(local_fn, sharded, replicated)
            return strat.device_reduce(partials)
        return jax.jit(step)

    def step_program(self, kernel, prepare: Callable, update: Callable,
                     *, name: str, strategy: StrategyLike = None,
                     select: Optional[Callable] = None) -> "StepProgram":
        """Build a :class:`StepProgram` over a registered kernel.

        ``prepare(carry) -> replicated`` derives the per-step broadcast
        arguments (e.g. quantized weights) from the carry; ``update(carry,
        reduced) -> (carry, out)`` applies the host-update math — both
        pure jnp functions, traced into the fused chunk.  ``select(
        sharded, x) -> sharded`` (optional) derives each step's shard
        view from a per-step scan input ``x`` — how minibatch SGD feeds
        precomputed batch offsets into the fused scan (DESIGN.md §9.5).
        ``name`` is the jit-cache namespace for the closure set and must
        encode every parameter baked into it (same convention as
        ``named_kernel``)."""
        return StepProgram(self, kernel, prepare, update, name=name,
                           strategy=strategy, select=select)

    # -- multi-tenancy -------------------------------------------------------

    def slice(self, lease) -> "System":
        """Execution view scoped to a :class:`~repro.sched.allocator.
        BankLease` — the surface the job scheduler runs tenants on."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support scheduling slices")


class StepProgram:
    """k consecutive training steps compiled into ONE ``lax.scan`` launch.

    The unfused trainers drive every iteration from the host: broadcast
    the model, launch the kernel, reduce, pull the result back, update in
    numpy, repeat — the CPU<->PIM synchronization cadence the paper (and
    PIM-Opt, arXiv:2404.07164) identify as the dominant cost once kernels
    are resident.  A StepProgram keeps the whole iterate-update-broadcast
    cycle on device: per scan step it runs ``prepare(carry)`` (weight
    quantization), the per-core kernel, the strategy's full on-device
    reduce, and ``update(carry, reduced)`` (dequantize + GD update) —
    with the carry buffers donated, so k steps cost one dispatch and one
    host sync instead of k of each (DESIGN.md §9).

    Works on ANY :class:`System` (DESIGN.md §10): on a processor-centric
    target there is no reduce leg to fuse away, so the chunk collapses
    to a plain k-iteration scan over the resident image — still one
    dispatch and one sync per chunk.

    Minibatch SGD (DESIGN.md §9.5): a ``select`` hook plus per-chunk
    ``xs`` feed precomputed batch offsets through the scan, so SGD
    configs fuse too — the host draws the chunk's offsets from the same
    rng stream the serial loop uses, then sleeps for the whole chunk.

    Numerics: prepare/update are the *same* closures the serial loop
    applies between launches, so for the integer versions a fused chunk
    is bit-identical to k unfused steps (asserted by
    tests/test_step_fusion.py).

    :meth:`run` alone decides how a chunk executes: as one scan, or as
    k host-orchestrated steps (one-step chunks, non-``fusable``
    strategies); every LIN/LOG fit and fused gang dispatches through
    it, whatever its ``fuse_steps``.
    """

    def __init__(self, system: System, kernel, prepare: Callable,
                 update: Callable, *, name: str,
                 strategy: StrategyLike = None,
                 select: Optional[Callable] = None):
        self.system = system
        self.prepare = prepare
        self.update = update
        self.select = select
        self.name = name
        self.strategy = resolve_reduce_strategy(
            strategy, system.config.reduce).bind(system)
        self._kernel = kernel
        self._kkey, self._fn = system._resolve_kernel(kernel)

    # -- fused chunk ---------------------------------------------------------

    def _build_chunk(self, k: int, with_xs: bool, donate: bool = True):
        prepare, update, strat = self.prepare, self.update, self.strategy
        per_core, fn, select = self.system._per_core, self._fn, self.select

        def chunk(carry, sharded, xs):
            def one_step(carry, x):
                shards = select(sharded, x) if with_xs else sharded
                replicated = prepare(carry)
                partials = per_core(fn, shards, replicated)
                reduced = strat.device_reduce_full(partials)
                return update(carry, reduced)
            return jax.lax.scan(one_step, carry, xs, length=k)
        # donate the carry: the model state is updated in place on
        # device, never materialized on the host inside the chunk.
        # Pipelined callers (ChunkPipeline depth >= 2) must keep the
        # chunk-N boundary carry readable while chunk N+1 is in flight,
        # so they compile without donation — same numerics, extra buffer.
        return jax.jit(chunk, donate_argnums=0 if donate else ())

    def _reduced_shape(self, carry, sharded, xs):
        """Abstract per-step ``device_reduce`` output (eval_shape, cached)
        — what the analytic chunk accounting sizes the reduce legs by.
        Keyed by the operand shapes: one system can run same-named
        programs over datasets of different widths (and slices share
        the parent cache), so name alone would serve stale shapes and
        corrupt the byte accounting."""
        sig = tuple((v.shape, str(v.dtype)) for v in
                    jax.tree_util.tree_leaves((carry, sharded, xs)))
        key = ("step_bytes", self._kkey, self.name,
               self.strategy.cache_token(), sig,
               self.system.config.n_cores)
        out = self.system._jit_cache.get(key)
        if out is None:
            def reduce_stage(carry, sharded, xs):
                shards = sharded
                if xs is not None and self.select is not None:
                    x0 = jax.tree_util.tree_map(lambda v: v[0], xs)
                    shards = self.select(sharded, x0)
                partials = self.system._per_core(
                    self._fn, shards, self.prepare(carry))
                return self.strategy.device_reduce(partials)
            out = jax.eval_shape(reduce_stage, carry, sharded, xs)
            self.system._jit_cache[key] = out
        return out

    def run(self, carry, sharded: tuple, k: int, xs=None, *,
            donate: bool = True):
        """Advance ``carry`` by ``k`` fused steps over the resident
        shards; returns ``(carry, outs)`` where ``outs`` stacks the
        per-step emits (None when ``update`` emits nothing).  ``xs`` is
        an optional pytree of per-step scan inputs with leading dim
        ``k`` routed to the ``select`` hook (minibatch offsets).

        One kernel launch and one host sync for the whole chunk; the
        analytic byte accounting charges the carry broadcast once, the
        reduce movement k times, and one chunk-boundary PIM->CPU sync of
        the final carry + emits (DESIGN.md §9.2).

        ``donate=False`` compiles the chunk without carry donation so
        the input carry stays readable after dispatch — required when a
        :class:`ChunkPipeline` overlaps chunk N+1 with the host drain of
        boundary N (DESIGN.md §14.1).  Donation only affects buffer
        reuse, never numerics.

        Which path a chunk takes is decided here and nowhere else.  A
        chunk runs as k host-orchestrated steps (:meth:`_run_per_step`:
        one broadcast, launch and sync per step) when ``k == 1`` or the
        strategy is not ``fusable``; otherwise as one scan.  The
        ``k == 1`` condition must hold for ``fuse_steps=1`` fits to keep
        the per-step accounting (one launch, one sync and one broadcast
        per step) and the ``map_reduce`` jit entries of that cadence.
        A one-step scan with a donated carry may serve that
        cadence faster; it may replace the per-step path only once it
        gives the per-step results bit for bit, which on XLA CPU it does
        not yet (LIN int32 drifts by an ulp)."""
        sharded = tuple(sharded)
        if k <= 0:
            return carry, None
        with_xs = xs is not None
        if with_xs and self.select is None:
            raise ValueError("xs given but this StepProgram has no "
                             "select hook")
        if k == 1 or not self.strategy.fusable:
            return self._run_per_step(carry, sharded, k, xs)
        # n_cores in the key: slices share the parent jit cache (vmap
        # backend) and hierarchical rank-partial shapes depend on width
        key = ("step_program", self._kkey, self.name,
               self.strategy.cache_token(), len(sharded), k, with_xs,
               donate, self.system.config.n_cores)
        chunk = self.system._jit_cache.get(key)
        if chunk is None:
            chunk = self._build_chunk(k, with_xs, donate)
            self.system._jit_cache[key] = chunk
        stats = self.system.stats
        stats.kernel_launches += 1
        stats.host_syncs += 1
        self.system._charge_chunk(
            carry, sharded, self._reduced_shape(carry, sharded, xs),
            self.strategy, k)
        with TRACER.span("repro.chunk", self.system._trace_track, "launch",
                         program=self.name, k=k):
            carry, outs = chunk(carry, sharded, xs)
        self.system._record_execution(key, chunk, (carry, sharded, xs),
                                      k=k)
        # one pim->cpu sync per chunk boundary: final carry + emits
        self.system._charge_chunk_boundary(carry, outs)
        return carry, outs

    def _run_per_step(self, carry, sharded: tuple, k: int, xs=None):
        """k host-orchestrated steps, each a broadcast of
        ``prepare(carry)``, one ``map_reduce`` launch and sync, and an
        eager ``update`` (the paper's per-step cadence)."""
        outs = []
        for i in range(k):
            shards = sharded
            if xs is not None:
                x = jax.tree_util.tree_map(lambda v: v[i], xs)
                shards = tuple(self.select(sharded, x))
            replicated = self.system.broadcast(self.prepare(carry))
            reduced = self.system.map_reduce(
                self._kernel, shards, tuple(replicated),
                strategy=self.strategy)
            carry, out = self.update(carry, reduced)
            outs.append(out)
        if outs and outs[0] is not None:
            outs = jax.tree_util.tree_map(
                lambda *vals: jnp.stack(vals), *outs)
        else:
            outs = None
        return carry, outs


@dataclasses.dataclass
class ChunkBoundary:
    """One dispatched-but-not-yet-drained chunk inside a
    :class:`ChunkPipeline`: the post-chunk carry/emits (device futures
    until someone reads them) plus the caller's ``tag`` — the
    host-side state captured at dispatch time (iteration count, packed
    rng, ...) that the boundary's record/snapshot work needs."""

    k: int
    carry: Any
    outs: Any
    tag: Any = None


class ChunkPipeline:
    """Double-buffered :class:`StepProgram` driver (DESIGN.md §14.1).

    JAX dispatch is asynchronous: ``StepProgram.run`` returns device
    futures, and the host only blocks when it *reads* them (``record``
    eval, convergence flags, ``ChunkTick.snapshot()``).  The serial
    trainer loop wastes that: it drains boundary N before dispatching
    chunk N+1, so the device idles for every host-side record.  A
    ChunkPipeline keeps ``depth`` chunks in flight — ``dispatch()``
    launches the next chunk immediately and hands back the boundaries
    that have fallen ``depth`` behind, which the caller drains while
    the device works.

    Sync discipline: the drained :class:`ChunkBoundary` is the only
    place reads happen; everything the drain needs that lives on the
    host (iteration counters, rng state) must be captured eagerly at
    dispatch time via ``tag`` — by drain time the trainer's live
    variables have already advanced past this boundary.

    ``depth=1`` degenerates to the serial cadence (dispatch, drain,
    repeat) and keeps carry donation; ``depth>=2`` disables donation so
    boundary N stays readable while chunk N+1 executes.  Numerics are
    untouched either way — pipelining reorders host work only, so a
    pipelined fit is bit-identical to the serial one (asserted by
    tests/test_step_fusion.py).
    """

    def __init__(self, program: StepProgram, depth: int = 2):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.program = program
        self.depth = depth
        self._pending: collections.deque = collections.deque()

    @property
    def donate(self) -> bool:
        """Depth 1 never holds a boundary while the next chunk runs, so
        the in-place carry update stays safe."""
        return self.depth == 1

    @property
    def in_flight(self) -> int:
        return len(self._pending)

    def dispatch(self, carry, sharded: tuple, k: int, xs=None, tag=None):
        """Launch the next ``k``-step chunk and return ``(new_carry,
        drained)`` where ``drained`` lists the boundaries now due for
        host processing (empty until the pipeline fills).  ``new_carry``
        is a device future — feed it straight into the next dispatch,
        never read it directly (read drained boundaries instead)."""
        carry, outs = self.program.run(carry, sharded, k, xs=xs,
                                       donate=self.donate)
        self._pending.append(ChunkBoundary(k, carry, outs, tag))
        drained = []
        while len(self._pending) >= self.depth:
            drained.append(self._pending.popleft())
        return carry, drained

    def flush(self) -> list:
        """Hand back every still-in-flight boundary (end of schedule or
        early stop).  Boundaries dispatched after a stop decision are
        the caller's to discard — for the convergence-latched trainers
        an overshot chunk is a frozen no-op, so discarding it is exact
        (DESIGN.md §14.1)."""
        drained = list(self._pending)
        self._pending.clear()
        return drained
