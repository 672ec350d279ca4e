"""Bank-resident dataset handles (DESIGN.md §3.2).

A :class:`PimDataset` is created by ``PimSystem.put(X, y)`` and owns

  * the host-side arrays (for centroid init / host-side prediction),
  * the padded row-validity mask, and
  * per-version quantized, sharded device views — lazily materialized
    and cached, so repeated ``fit``s, ``n_init`` restarts, and
    hyperparameter sweeps reuse ONE CPU->PIM transfer per view and the
    ``TransferStats`` counters stop double-counting the partition.

This mirrors the paper's execution model exactly: the training set is
partitioned across the DRAM banks once and never moves again; only model
state (weights / centroids / split commands) crosses the host<->PIM
boundary per iteration.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fixed_point import to_fixed
from ..kernels.quant_matmul.kernel import block_rows, to_feature_blocks
# 12-bit symmetric range stored in int16 — keeps int32 distance and
# coordinate-sum accumulations exact on TPU; single source of truth in
# core/kmeans.py (see its docstring for the derivation)
from ..core.kmeans import QUANT_RANGE as KMEANS_QUANT_RANGE

#: data-precision families; LIN/LOG versions map onto one of these, so
#: e.g. the "hyb" and "bui" versions share a single cached view.
GD_DATA_VERSIONS = ("fp32", "int32", "hyb")

_GD_DATA_VERSION = {
    "fp32": "fp32", "int32": "int32", "hyb": "hyb", "bui": "hyb",
    "int32_lut_mram": "int32", "int32_lut_wram": "int32",
    "hyb_lut": "hyb", "bui_lut": "hyb",
}


def gd_data_version(version: str) -> str:
    """Collapse a LIN/LOG version name to its on-bank data precision."""
    try:
        return _GD_DATA_VERSION[version]
    except KeyError:
        raise ValueError(f"unknown workload version {version!r}") from None


@dataclasses.dataclass(frozen=True)
class KMeansView:
    """Quantized K-Means view: device shards + host copy for init."""

    shards: jnp.ndarray      # (n_cores, n_pc, F) int16
    mask: jnp.ndarray        # (n_cores, n_pc) bool
    host_q: np.ndarray       # (n, F) int16 — centroid init draws from it
    scale: np.float32        # dequantization scale


class PimDataset:
    """Handle to a dataset partitioned once across the PIM banks."""

    def __init__(self, system, X, y=None):
        X = np.asarray(X)
        if X.ndim == 1:
            X = X[:, None]
        self.system = system
        self.X = X
        self.y = None if y is None else np.asarray(y)
        self.n = int(X.shape[0])
        self.n_features = int(X.shape[1])
        self._views: dict[tuple, Any] = {}

    # -- caching core --------------------------------------------------------

    def _cached(self, key: tuple, builder):
        view = self._views.get(key)
        if view is None:
            from ..obs.trace import TRACER   # local: api -> obs, no cycle
            track = getattr(self.system, "_trace_track", "system:?")
            with TRACER.span("repro.view", track, "transfer", view=key[0]):
                view = builder()
            self._views[key] = view
        return view

    @property
    def n_pc(self) -> int:
        """Rows of each shard ``System.shard_rows`` makes, padding
        included."""
        return -(-self.n // self.system.n_shards)

    @property
    def n_views(self) -> int:
        """Number of materialized (transferred) views — diagnostics."""
        return sum(1 for k in self._views if k[0] != "mask")

    def _require_y(self, who: str) -> np.ndarray:
        if self.y is None:
            raise ValueError(
                f"{who} needs labels/targets; create the dataset with "
                f"PimSystem.put(X, y)")
        return self.y

    # -- views ---------------------------------------------------------------

    def mask(self, dtype=None) -> jnp.ndarray:
        """Row-validity mask, optionally cast (cached per dtype)."""
        key = ("mask", None if dtype is None else jnp.dtype(dtype).name)
        return self._cached(key, lambda: (
            self.system.row_validity_mask(self.n) if dtype is None
            else self.system.row_validity_mask(self.n).astype(dtype)))

    def gd_view(self, version: str, frac_bits: int = 10, x8_frac: int = 7):
        """(Xs, ys, mask) for the gradient-descent workloads (LIN/LOG).

        ``version`` may be any LIN/LOG version name; it is collapsed to
        the data precision family, so HYB and BUI (same datatypes, paper
        §3.1) share one transfer, as do the LUT placement variants.

        fp32 and hyb shards are row-major: ``(shards, n_pc, F)``,
        ``(shards, n_pc)``.  The int32 family is stored in
        ``fx_matvec``'s blocked feature-major layout, built once on the
        device: ``Xs`` ``(shards, nb, F8, bn)`` with the rows of each
        block on the lanes and ``F`` padded to whole sublanes, ``ys``
        and an int32 ``mask`` of its own ``(shards, nb, bn)``; pad rows
        and features are zero, and 0 in the mask.  Rows stay on axis 1
        in every layout (DESIGN.md §3.2).
        """
        y = self._require_y("gd_view")
        data_ver = gd_data_version(version)

        if data_ver == "fp32":
            key = ("gd", "fp32")

            def build():
                return (self.system.shard_rows(self.X.astype(np.float32)),
                        self.system.shard_rows(y.astype(np.float32)),
                        self.mask(jnp.float32))
        elif data_ver == "int32":
            key = ("gd", "int32", frac_bits)

            def build():
                Xq = np.asarray(to_fixed(self.X, frac_bits))
                yq = np.asarray(to_fixed(y, frac_bits))
                Xs = self.system.shard_rows(Xq)
                return _feature_blocks(
                    (Xs, self.system.shard_rows(yq),
                     self.system.row_validity_mask(self.n)),
                    block_rows(Xs.shape[1]))
        else:  # hyb: int8 inputs, fixed-point targets at frac_bits
            key = ("gd", "hyb", x8_frac, frac_bits)

            def build():
                Xq8 = np.asarray(to_fixed(self.X, x8_frac, dtype=jnp.int8))
                yq = np.asarray(to_fixed(y, frac_bits))
                return (self.system.shard_rows(Xq8),
                        self.system.shard_rows(yq),
                        self.mask(jnp.int32))
        return self._cached(key, build)

    def tree_view(self):
        """(Xs, ys, mask) for the decision-tree workload (float32/int32)."""
        y = self._require_y("tree_view")

        def build():
            return (self.system.shard_rows(self.X.astype(np.float32)),
                    self.system.shard_rows(y.astype(np.int32)),
                    self.mask())
        return self._cached(("tree",), build)

    def emb_view(self) -> tuple:
        """(pairs, targets) for the EMB workload: host-side ``(n, 2)``
        int32 (user, item) index pairs plus float32 ratings.

        EMB keeps the *dataset* host-side by design — per-step
        minibatches of index pairs broadcast to the banks, while the
        sharded state is the embedding TABLE (a :class:`ShardedTable`
        from ``System.put_table``), inverting the usual data/model
        placement (DESIGN.md §15.1)."""
        y = self._require_y("emb_view")
        if self.n_features != 2:
            raise ValueError(
                f"emb_view needs (n, 2) (user, item) index pairs, got "
                f"{self.n_features} columns")
        X = np.asarray(self.X)
        if not np.issubdtype(X.dtype, np.integer):
            if not np.all(X == np.round(X)):
                raise ValueError("emb_view indices must be integral")
        Xi = X.astype(np.int32)
        if Xi.size and Xi.min() < 0:
            raise ValueError("emb_view indices must be non-negative")
        return self._cached(("emb",), lambda: (Xi, y.astype(np.float32)))

    def kmeans_view(self, version: str = "int16") -> KMeansView:
        """K-Means data view, cached per precision.

        ``"int16"``: symmetric quantization to +-KMEANS_QUANT_RANGE
        (the paper's PIM version).  ``"fp32"``: un-quantized float32 —
        the processor-centric baseline precision (scale 1.0, no
        quantization round-trip; DESIGN.md §10.3)."""
        if version == "fp32":
            def build():
                Xf = np.asarray(self.X, np.float32)
                return KMeansView(shards=self.system.shard_rows(Xf),
                                  mask=self.mask(),
                                  host_q=Xf,
                                  scale=np.float32(1.0))
            return self._cached(("kmeans", "fp32"), build)
        if version != "int16":
            raise ValueError(f"unknown kmeans view precision {version!r}; "
                             f"known: ('int16', 'fp32')")

        def build():
            X = np.asarray(self.X, np.float32)
            amax = float(np.abs(X).max())
            scale = max(amax, 1e-12) / KMEANS_QUANT_RANGE
            Xq = np.clip(np.round(X / scale),
                         -KMEANS_QUANT_RANGE, KMEANS_QUANT_RANGE)
            Xq = Xq.astype(np.int16)
            return KMeansView(shards=self.system.shard_rows(Xq),
                              mask=self.mask(),
                              host_q=Xq,
                              scale=np.float32(scale))
        return self._cached(("kmeans", "int16"), build)


@functools.partial(jax.jit, static_argnums=1)
def _feature_blocks(shards, bn: int):
    """Row-major ``(Xs, ys, mask)`` shards in ``fx_matvec``'s blocked
    layout, relaid on the device; the mask as int32."""
    Xs, ys, mask = shards
    return (to_feature_blocks(Xs, bn), to_feature_blocks(ys, bn),
            to_feature_blocks(mask.astype(jnp.int32), bn))


def as_dataset(X, y, system) -> PimDataset:
    """Coerce (X, y) to a PimDataset on ``system``.

    Passing an existing PimDataset through is the sweep fast path; raw
    arrays get an ephemeral handle (one transfer, same as the old API).
    """
    if isinstance(X, PimDataset):
        return X
    return PimDataset(system, X, y)
