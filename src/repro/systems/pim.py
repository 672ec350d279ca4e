"""PIM execution model (paper §2.2, Fig. 3) mapped onto JAX.

The paper's system: N PIM cores, each owning a DRAM bank; training data is
partitioned once and stays bank-resident; each iteration every core computes
a partial result over its shard; partials are reduced *via the host* (DPUs
cannot talk to each other) and the updated model is re-broadcast.

JAX mapping (DESIGN.md §2):
  PIM core            -> one mesh element of a 1-D "cores" axis
  bank-resident shard -> device-resident leading-axis shard of the dataset
  host reduction      -> jax.lax.psum over "cores" (FabricReduce) or an
                         actual device_get/numpy/device_put round trip
                         (HostReduce — faithful to UPMEM's topology), or a
                         two-level rank schedule (HierarchicalReduce)

:class:`PimSystem` is the memory-centric implementation of the
:class:`~repro.systems.base.System` protocol (DESIGN.md §10); the
execution surface — ``put``/``register_kernel``/``map_reduce``/
``step_program`` — is defined on the shared base and behaves here
exactly as it did when this class WAS the surface (bit-identical fits,
identical TransferStats; asserted by tests/test_pim_system.py and
tests/test_step_fusion.py).

Backends:
  "vmap"      single-device semantic model (cores simulated by vmap) — used
              by unit tests and quality reproduction; bit-identical to the
              sharded path because the kernels are deterministic integer ops.
  "shard_map" real multi-device execution over a jax.Mesh "cores" axis —
              used by the scaling benchmarks and the dry-run.

Cost modeling moved to :mod:`repro.systems.topology` (DESIGN.md §12):
:class:`~repro.systems.topology.HierarchicalCostModel` prices launches
over the explicit channel -> rank -> DPU tree (per-DPU instruction
tables as the leaf compute term, segmented MRAM<->WRAM DMA,
rank-serialized transfer legs, channel contention).  The flat
``DpuCostModel`` remains below as a one-warning deprecation shim so old
imports keep working; every in-repo consumer now uses the hierarchical
model.  Still here: the on-bank storage-dtype table
(``WORKLOAD_STORAGE_DTYPE``/``workload_element_bytes``) the model's
MRAM byte counting reads, because it mirrors what ``PimDataset``
materializes.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.quantization import storage_bytes
from .base import ReduceVia, System
from .topology import (DEFAULT_RANKS_PER_CHANNEL, DPU_FREQ_HZ,
                       DPU_MRAM_BYTES_PER_CYCLE, DPU_OP_CYCLES,
                       DPU_PIPELINE_SATURATION_THREADS,
                       HierarchicalCostModel, PimTopology)


@dataclasses.dataclass
class PimConfig:
    n_cores: int = 64
    n_threads: int = 16          # tasklets per core (cost model + layouts)
    reduce: ReduceVia = ReduceVia.FABRIC   # default strategy for map_reduce
    backend: str = "vmap"        # "vmap" | "shard_map"
    dpus_per_rank: Optional[int] = None    # None -> auto (largest divisor <=64)
    ranks_per_channel: int = DEFAULT_RANKS_PER_CHANNEL


class PimSystem(System):
    """Host-orchestrated data-parallel execution over PIM cores.

    The redesigned surface (DESIGN.md §3, §10):
      put(X, y)                 -> PimDataset (bank-resident, view-cached)
      register_kernel(name, fn) -> kernel name usable with map_* calls
      named_kernel(name, build) -> register-once helper for kernel factories
      map_reduce(kernel, ...)   -> kernel may be a registered name or a
                                   callable; ``strategy=`` picks the
                                   reduction per call
    """

    kind = "pim"

    def __init__(self, config: PimConfig, devices: Optional[Sequence] = None):
        super().__init__(config)
        self._mesh = None
        if config.backend == "shard_map":
            devices = list(devices if devices is not None else jax.devices())
            if len(devices) < config.n_cores:
                raise ValueError(
                    f"shard_map backend needs >= {config.n_cores} devices, "
                    f"got {len(devices)} (set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=...)")
            self._mesh = Mesh(np.array(devices[: config.n_cores]), ("cores",))

    @property
    def n_shards(self) -> int:
        return self.config.n_cores

    @property
    def topology(self) -> PimTopology:
        """The channel -> rank -> DPU tree this machine models
        (DESIGN.md §12) — shared by the cost model, the reduce
        strategies' rank-local/cross-rank accounting, and the
        bank allocator's contention scoring."""
        return PimTopology.for_cores(
            self.config.n_cores,
            dpus_per_rank=self.config.dpus_per_rank,
            ranks_per_channel=self.config.ranks_per_channel)

    def cost_model(self) -> HierarchicalCostModel:
        """A :class:`HierarchicalCostModel` over this machine's tree."""
        return HierarchicalCostModel(self.topology)

    # -- data placement ------------------------------------------------------

    def shard_rows(self, x: np.ndarray, pad_value=0) -> jnp.ndarray:
        """Partition rows across cores: (n, ...) -> (n_cores, n_pc, ...).

        Equal-size shards (padding as needed) mirror the paper's requirement
        that parallel CPU->PIM transfers need equal buffer sizes per bank.
        Counts the modeled CPU->PIM transfer bytes (and the dedicated
        shard_transfers/shard_bytes counters — see TransferStats)."""
        c = self.config.n_cores
        n = x.shape[0]
        n_pc = -(-n // c)
        pad = c * n_pc - n
        if pad:
            x = np.concatenate(
                [x, np.full((pad,) + x.shape[1:], pad_value, x.dtype)], 0)
        out = x.reshape(c, n_pc, *x.shape[1:])
        self.stats.cpu_to_pim += out.nbytes
        self.stats.shard_transfers += 1
        self.stats.shard_bytes += out.nbytes
        arr = jnp.asarray(out)
        if self._mesh is not None:
            arr = jax.device_put(
                arr, NamedSharding(self._mesh, P("cores")))
        return arr

    def row_validity_mask(self, n: int) -> jnp.ndarray:
        """(n_cores, n_pc) bool mask marking real (non-padding) rows."""
        c = self.config.n_cores
        n_pc = -(-n // c)
        idx = np.arange(c * n_pc).reshape(c, n_pc)
        mask = jnp.asarray(idx < n)
        if self._mesh is not None:
            mask = jax.device_put(mask, NamedSharding(self._mesh, P("cores")))
        return mask

    def broadcast(self, tree: Any) -> Any:
        """Host -> all cores broadcast of model state (counted per core).
        The byte count reads the state back to the host: one
        :meth:`System.read` per broadcast."""
        nbytes = sum(v.nbytes for v in
                     jax.tree_util.tree_leaves(self.read(tree)))
        self.stats.cpu_to_pim += nbytes * self.config.n_cores
        if self._mesh is not None:
            tree = jax.device_put(
                tree, NamedSharding(self._mesh, P()))  # replicated
        return tree

    # -- execution ------------------------------------------------------------

    def _per_core(self, local_fn, sharded, replicated):
        """Trace the per-core kernel under vmap or shard_map."""
        if self._mesh is None:
            return jax.vmap(lambda *s: local_fn(*s, *replicated))(*sharded)
        mesh = self._mesh

        # check_vma=False: the per-core kernels may be Pallas calls, whose
        # out_shape carries no varying-axes annotation; every output is
        # per-core (out_specs P("cores")) by construction
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(tuple(P("cores") for _ in sharded), P()),
            out_specs=P("cores"), check_vma=False)
        def _shmap(shard_args, rep):
            local = [jnp.squeeze(a, 0) for a in shard_args]
            out = local_fn(*local, *rep)
            return jax.tree_util.tree_map(lambda v: v[None], out)
        return _shmap(sharded, replicated)

    # -- multi-tenancy -------------------------------------------------------

    def slice(self, lease) -> "PimSystem":
        """A :class:`~repro.sched.allocator.PimSlice` over the leased
        extent — itself a PimSystem, so trainers run on it unmodified."""
        from ..sched.allocator import PimSlice  # local: sched -> systems
        return PimSlice(self, lease)


# ---------------------------------------------------------------------------
# Storage-dtype table (feeds the cost model's MRAM byte counting).
# ---------------------------------------------------------------------------

#: on-bank storage dtype of the training data per (workload, version) —
#: the explicit table the cost model's MRAM byte counting reads, with the
#: per-dtype widths shared with quantization.STORAGE_BYTES.  Mirrors the
#: quantized views PimDataset materializes (repro/api/dataset.py).
WORKLOAD_STORAGE_DTYPE: dict[tuple[str, str], str] = {
    ("lin", "fp32"): "fp32",
    ("lin", "int32"): "int32",
    ("lin", "hyb"): "int8",
    ("lin", "bui"): "int8",
    ("log", "fp32"): "fp32",
    ("log", "int32"): "int32",
    ("log", "int32_lut_mram"): "int32",
    ("log", "int32_lut_wram"): "int32",
    ("log", "hyb_lut"): "int8",
    ("log", "bui_lut"): "int8",
    ("dtr", "fp32"): "fp32",
    ("kme", "int16"): "int16",
    ("kme", "fp32"): "fp32",
    ("emb", "fp32"): "fp32",     # ShardedTable float shards
    ("emb", "int32"): "int32",   # ShardedTable Q(frac_bits) shards
}


def workload_element_bytes(workload: str, version: str) -> int:
    """Bytes per stored feature value for a workload version."""
    try:
        name = WORKLOAD_STORAGE_DTYPE[(workload, version)]
    except KeyError:
        raise ValueError(
            f"no storage dtype recorded for {workload}/{version}; "
            f"add it to WORKLOAD_STORAGE_DTYPE") from None
    return storage_bytes(name)


# ---------------------------------------------------------------------------
# DpuCostModel — deprecation shim over the hierarchical model.
# ---------------------------------------------------------------------------

_DPU_COST_MODEL_WARNED = False


class DpuCostModel(HierarchicalCostModel):
    """Deprecated flat cost model — use
    :class:`repro.systems.topology.HierarchicalCostModel`.

    Kept so old imports (``repro.core.pim.DpuCostModel``) keep working:
    this is the hierarchical model pinned to a single-DPU topology, so
    ``kernel_seconds``/``workload_seconds`` keep their historical
    per-DPU semantics (no transfer legs).  Emits one
    ``DeprecationWarning`` per process.
    """

    def __init__(self, freq_hz: float = DPU_FREQ_HZ,
                 saturation_threads: int = DPU_PIPELINE_SATURATION_THREADS):
        global _DPU_COST_MODEL_WARNED
        if not _DPU_COST_MODEL_WARNED:
            _DPU_COST_MODEL_WARNED = True
            warnings.warn(
                "DpuCostModel is deprecated; use "
                "repro.systems.topology.HierarchicalCostModel (topology-"
                "aware launch pricing, DESIGN.md §12)",
                DeprecationWarning, stacklevel=2)
        super().__init__(topology=PimTopology(n_cores=1),
                         freq_hz=freq_hz,
                         saturation_threads=saturation_threads)
