"""EMB workload family: sparse gather/scatter kernels, ShardedTable
placement, deferred-update training identities, compressed flushes, and
the spool-lane / replay serve satellites (DESIGN.md §15).

The load-bearing claims:

  * ``emb_scatter_add`` is duplicate-safe and bit-exact across backends
    (segment-sum formulation — same reduction order in ref and Pallas);
  * deferred updates with D=1 are BIT-identical to eager (both dtypes);
  * the fused (lax.scan) engine matches the serial loop bit-for-bit;
  * a mid-window preemption resumes bit-identically on another width;
  * deferred windows shrink ``flush_bytes`` on Zipf-skewed traffic.
"""
import json
import os

import numpy as np
import pytest

from repro.api import make_estimator
from repro.api.table import ShardedTable
from repro.data.synthetic import make_recsys
from repro.emb import EmbConfig, fit, fit_steps
from repro.kernels.sparse_gather import (IDX_PAD, ROW_PAD_ID, emb_gather,
                                         emb_scatter_add)
from repro.kernels.sparse_gather.ref import (emb_gather_ref,
                                             emb_scatter_add_ref)
from repro.systems import make_system, run_steps

slow = pytest.mark.slow


def _table(r=22, d=3, vmax=40, dtype=np.int32, seed=0):
    """A shard-like table block: rows + a sparse id map with pads."""
    rng = np.random.RandomState(seed)
    ids = rng.choice(vmax, size=r - 2, replace=False).astype(np.int32)
    ids = np.concatenate(   # two padded slots at the tail
        [ids, np.array([ROW_PAD_ID, ROW_PAD_ID], np.int32)])
    rng.shuffle(ids)
    if dtype == np.int32:
        tab = rng.randint(-500, 500, size=(r, d)).astype(np.int32)
    else:
        tab = rng.randn(r, d).astype(np.float32)
    tab[ids == ROW_PAD_ID] = 0
    return tab, ids


# ---------------------------------------------------------------------------
# Kernel semantics vs a plain numpy oracle (backend-independent).
# ---------------------------------------------------------------------------

class TestSparseGatherSemantics:
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_gather_matches_numpy(self, dtype):
        tab, ids = _table(dtype=dtype)
        rng = np.random.RandomState(1)
        owned = ids[ids >= 0]
        idx = rng.choice(owned, size=17).astype(np.int32)
        out = np.asarray(emb_gather_ref(tab, ids, idx))
        slot = {int(v): s for s, v in enumerate(ids) if v >= 0}
        want = np.stack([tab[slot[int(v)]] for v in idx])
        np.testing.assert_array_equal(out, want)

    def test_gather_miss_returns_zeros(self):
        # ids this shard does NOT own gather zero rows — the cross-shard
        # fabric sum then reconstructs the full row from the owner
        tab, ids = _table()
        missing = np.array([v for v in range(40)
                            if v not in set(ids.tolist())][:5], np.int32)
        out = np.asarray(emb_gather_ref(tab, ids, missing))
        np.testing.assert_array_equal(out, 0)

    def test_idx_pad_never_matches_row_pad(self):
        # padded batch slots (IDX_PAD) must not match padded table
        # slots (ROW_PAD_ID) — distinct sentinels by construction
        assert IDX_PAD != ROW_PAD_ID
        tab, ids = _table()
        idx = np.full(4, IDX_PAD, np.int32)
        np.testing.assert_array_equal(
            np.asarray(emb_gather_ref(tab, ids, idx)), 0)

    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_scatter_add_duplicates(self, dtype):
        # ALL batch slots hit the same row: the segment-sum must add
        # every contribution (the classic scatter-add razor)
        tab, ids = _table(dtype=dtype)
        v = int(ids[ids >= 0][3])
        idx = np.full(9, v, np.int32)
        upd = (np.arange(9 * 3).reshape(9, 3) + 1).astype(dtype)
        out = np.asarray(emb_scatter_add_ref(tab, ids, idx, upd))
        want = tab.copy()
        want[np.nonzero(ids == v)[0][0]] += upd.sum(0).astype(dtype)
        np.testing.assert_array_equal(out, want)

    def test_scatter_add_empty_batch(self):
        tab, ids = _table()
        out = np.asarray(emb_scatter_add(
            tab, ids, np.zeros(0, np.int32), np.zeros((0, 3), np.int32),
            backend="jnp_ref"))
        np.testing.assert_array_equal(out, tab)

    def test_gather_empty_batch(self):
        tab, ids = _table()
        out = np.asarray(emb_gather(tab, ids, np.zeros(0, np.int32),
                                    backend="jnp_ref"))
        assert out.shape == (0, 3)


# ---------------------------------------------------------------------------
# Pallas parity: interpret-mode kernels vs the jnp_ref oracle, bit-exact.
# ---------------------------------------------------------------------------

class TestSparseGatherParity:
    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    @pytest.mark.parametrize("b", [1, 8, 20])   # 20 forces a ragged tail
    def test_gather_parity(self, dtype, b):
        tab, ids = _table(dtype=dtype)
        rng = np.random.RandomState(2)
        idx = rng.choice(ids[ids >= 0], size=b).astype(np.int32)
        ref = np.asarray(emb_gather(tab, ids, idx, backend="jnp_ref"))
        pal = np.asarray(emb_gather(tab, ids, idx,
                                    backend="pallas_interpret", block_b=8))
        np.testing.assert_array_equal(ref, pal)

    @pytest.mark.parametrize("dtype", [np.int32, np.float32])
    def test_scatter_parity_with_duplicates(self, dtype):
        tab, ids = _table(r=22, dtype=dtype)  # 22 pads up to block_r=8
        rng = np.random.RandomState(3)
        idx = rng.choice(ids[ids >= 0], size=30).astype(np.int32)
        idx[:7] = idx[0]                      # heavy duplication
        if dtype == np.int32:
            upd = rng.randint(-9, 9, size=(30, 3)).astype(np.int32)
        else:
            upd = rng.randn(30, 3).astype(np.float32)
        ref = np.asarray(emb_scatter_add(tab, ids, idx, upd,
                                         backend="jnp_ref"))
        pal = np.asarray(emb_scatter_add(tab, ids, idx, upd,
                                         backend="pallas_interpret",
                                         block_r=8))
        np.testing.assert_array_equal(ref, pal)

    def test_cross_shard_straddle(self):
        # one flush batch touching rows owned by DIFFERENT shards:
        # per-shard scatters each absorb only their own rows, and
        # reassembly equals a global numpy scatter
        pim = make_system("pim", n_cores=4)
        V, D = 23, 3
        W = np.random.RandomState(4).randn(V, D).astype(np.float32)
        table = pim.put_table(W, placement="mod")
        shards, ids = table.view("fp32")
        idx = np.array([0, 1, 2, 3, 5, 5, 22], np.int32)  # 4 shards hit
        upd = np.arange(7 * D, dtype=np.float32).reshape(7, D)
        out = np.stack([
            np.asarray(emb_scatter_add(
                np.asarray(shards)[s], table.ids[s], idx, upd,
                backend="pallas_interpret", block_r=4))
            for s in range(4)])
        got = table.unshard(out)
        want = W.copy()
        np.add.at(want, idx, upd)
        np.testing.assert_allclose(got, want, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# ShardedTable: placement, round-trips, the staging ledger.
# ---------------------------------------------------------------------------

class TestShardedTable:
    @pytest.mark.parametrize("placement", ["mod", "hash"])
    def test_placement_round_trip(self, placement):
        pim = make_system("pim", n_cores=4)
        W = np.arange(22 * 3, dtype=np.float32).reshape(22, 3)
        t = pim.put_table(W, placement=placement, seed=7)
        shards, _ids = t.view("fp32")
        np.testing.assert_array_equal(t.unshard(np.asarray(shards)), W)

    def test_mod_placement_round_robin(self):
        pim = make_system("pim", n_cores=4)
        t = pim.put_table(np.zeros((22, 3), np.float32))
        assert t.lookup_shard(0) == (0, 0)
        assert t.lookup_shard(5) == (1, 1)   # 5 % 4, 5 // 4
        # every real row owned exactly once
        owned = t.ids[t.ids >= 0]
        assert sorted(owned.tolist()) == list(range(22))

    def test_int32_view_dtype_and_stats(self):
        pim = make_system("pim", n_cores=4)
        t = pim.put_table(np.random.RandomState(0).randn(22, 3))
        shards, _ = t.view("int32", frac_bits=10)
        assert np.asarray(shards).dtype == np.int32
        assert t.n_views == 1
        assert all(st["bytes"] > 0 for st in t.shard_stats)
        assert sum(st["rows"] for st in t.shard_stats) == 22

    def test_ledger_dedup_sums_duplicates(self):
        pim = make_system("pim", n_cores=2)
        t = pim.put_table(np.zeros((8, 2), np.float32))
        t.stage([1, 1, 3], np.ones((3, 2), np.int32))
        t.stage([3, 5], 2 * np.ones((2, 2), np.int32))
        assert t.pending_batches == 2 and t.pending_rows == 5
        idx, upd = t.drain(dedup=True)
        np.testing.assert_array_equal(idx, [1, 3, 5])
        np.testing.assert_array_equal(upd, [[2, 2], [3, 3], [2, 2]])
        assert t.pending_batches == 0

    def test_drain_no_dedup_is_verbatim(self):
        pim = make_system("pim", n_cores=2)
        t = pim.put_table(np.zeros((8, 2), np.float32))
        t.stage([1, 1], np.ones((2, 2), np.float32))
        idx, upd = t.drain(dedup=False)
        np.testing.assert_array_equal(idx, [1, 1])
        assert upd.shape == (2, 2)


def _recsys(n=768, nu=48, ni=36, d=4, seed=3):
    return make_recsys(n, nu, ni, dim=d, seed=seed)


def _cfg(**kw):
    base = dict(version="int32", n_iters=24, batch=32, dim=4, lr=1.0,
                frac_bits=12, seed=1)
    base.update(kw)
    return EmbConfig(**base)


def _fit_raw(cfg, X, y, cores=8, kind="pim"):
    system = make_system(kind, n_cores=cores)
    res = fit(system.put(X, y), cfg)
    return res, system


# ---------------------------------------------------------------------------
# Trainer identities (the §15.3 deferred-update contract).
# ---------------------------------------------------------------------------

class TestEmbTrainer:
    def test_eager_learns_both_versions(self):
        X, y = _recsys()
        for ver in ("fp32", "int32"):
            res, _ = _fit_raw(_cfg(version=ver, n_iters=40,
                                   record_every=20), X, y)
            first, last = res.history[0][1], res.history[-1][1]
            assert last < first, (ver, res.history)

    @pytest.mark.parametrize("ver", ["int32", "fp32"])
    def test_deferred_d1_bit_identical_to_eager(self, ver):
        X, y = _recsys()
        eager, se = _fit_raw(_cfg(version=ver, deferred=False), X, y)
        lazy, sl = _fit_raw(_cfg(version=ver, flush_every=1,
                                 deferred=True), X, y)
        np.testing.assert_array_equal(eager.user_raw, lazy.user_raw)
        np.testing.assert_array_equal(eager.item_raw, lazy.item_raw)
        # same logical sparse payload shipped, window or no window
        assert se.stats.flush_bytes == sl.stats.flush_bytes

    @pytest.mark.parametrize("ver", ["int32", "fp32"])
    def test_fused_bit_identical_to_serial(self, ver):
        X, y = _recsys()
        a, sa = _fit_raw(_cfg(version=ver, flush_every=6, fuse_steps=1,
                              record_every=6), X, y)
        b, sb = _fit_raw(_cfg(version=ver, flush_every=6, fuse_steps=4,
                              record_every=6), X, y)
        np.testing.assert_array_equal(a.user_raw, b.user_raw)
        np.testing.assert_array_equal(a.item_raw, b.item_raw)
        assert a.history == b.history
        assert sa.stats.flush_bytes == sb.stats.flush_bytes
        # fusion collapses launches: serial pays ~1/step + 1/flush
        assert (sb.stats.kernel_launches
                < sa.stats.kernel_launches)

    def test_host_matches_pim_bitwise(self):
        # shard-local gathers contribute zeros off-owner, so the fabric
        # sum is EXACT even in fp32 — one resident image (host) and 8
        # shards (pim) must agree bit for bit
        X, y = _recsys()
        for ver in ("fp32", "int32"):
            a, _ = _fit_raw(_cfg(version=ver, flush_every=3), X, y,
                            kind="pim")
            b, _ = _fit_raw(_cfg(version=ver, flush_every=3), X, y,
                            kind="host", cores=8)
            np.testing.assert_array_equal(a.user_raw, b.user_raw)
            np.testing.assert_array_equal(a.item_raw, b.item_raw)

    def test_deferred_window_cuts_flush_traffic(self):
        # Zipf-skewed ids: hot rows repeat within a window, dedup ships
        # them once — the LazyDP traffic saving, on flush_bytes
        X, y = make_recsys(2048, 64, 48, dim=4, zipf_a=1.1, seed=0)
        byD = {}
        for D in (1, 8):
            _, s = _fit_raw(_cfg(n_iters=32, batch=128,
                                 flush_every=D), X, y)
            byD[D] = s.stats.flush_bytes
        assert byD[1] / byD[8] >= 2.0, byD

    def test_resume_mid_window_bit_identical(self):
        X, y = _recsys()
        cfg = _cfg(flush_every=4, record_every=8)
        ref, _ = _fit_raw(cfg, X, y)
        gen = fit_steps(make_system("pim", n_cores=8).put(X, y), cfg)
        done, snap = 0, None
        while snap is None:
            tick = next(gen)
            done += int(tick)
            if done >= 10:          # 10 % 4 == 2 -> ledger non-empty
                snap = tick.snapshot()
        assert snap["arrays"]["pend_u_idx"].size > 0
        res = run_steps(fit_steps(
            make_system("pim", n_cores=4).put(X, y), cfg, state=snap))
        np.testing.assert_array_equal(ref.user_raw, res.user_raw)
        np.testing.assert_array_equal(ref.item_raw, res.item_raw)
        assert ref.history == res.history

    def test_compressed_flush_accounting(self):
        X, y = _recsys()
        _, s = _fit_raw(_cfg(flush_every=4, compress_flush=True), X, y)
        # int8 rows + f32 scales on the wire, less than the raw payload
        assert 0 < s.stats.compressed_bytes < s.stats.flush_bytes

    def test_padded_vocab_tail(self):
        # vocab not divisible by shard count: pad slots must stay inert
        X, y = make_recsys(512, 13, 11, dim=4, seed=5)  # 13 % 8 != 0
        res, _ = _fit_raw(_cfg(n_iters=16), X, y)
        assert res.user_emb.shape == (13, 4)
        assert res.item_emb.shape == (11, 4)


# ---------------------------------------------------------------------------
# Registry / estimator / scheduler integration.
# ---------------------------------------------------------------------------

class TestEmbIntegration:
    def test_estimator_round_trip(self):
        X, y = make_recsys(2048, 128, 96, dim=4, seed=0)
        est = make_estimator("emb", version="int32", n_iters=60,
                             batch=64, dim=4, lr=1.0, frac_bits=12,
                             flush_every=4, seed=1)
        est.fit(make_system("pim", n_cores=8).put(X, y))
        assert est.score(X, y) > 0.4
        assert est.predict(X[:5]).shape == (5,)

    def test_manifest_recsys_job_with_cost_model(self):
        from repro.sched.manifest import job_report, run_manifest
        doc = {"system": {"kind": "pim", "cores": 8},
               "datasets": {"clicks": {"kind": "recsys", "samples": 1024,
                                       "n_users": 64, "n_items": 48,
                                       "dim": 4, "seed": 0}},
               "jobs": [{"workload": "emb", "version": "int32",
                         "dataset": "clicks", "name": "emb-j",
                         "params": {"n_iters": 16, "batch": 32, "dim": 4,
                                    "lr": 1.0, "frac_bits": 12,
                                    "flush_every": 4}}]}
        _sched, handles = run_manifest(doc)
        row = job_report(handles)[0]
        assert row["state"] == "done" and row["iters"] == 16
        # _COST_KEYS routes emb into the hierarchical model
        assert row["modeled_dpu_seconds"] > 0


# ---------------------------------------------------------------------------
# Serve satellites: spool priority lane + sidecar replay on restart.
# ---------------------------------------------------------------------------

def _spool_manifest(spool, name, prio=None):
    doc = {"datasets": {"d": {"kind": "linear", "samples": 256,
                              "features": 4}},
           "jobs": [{"workload": "linreg", "version": "fp32",
                     "name": name, "params": {"n_iters": 4}}]}
    if prio is not None:
        doc["priority"] = prio
    with open(os.path.join(spool, name + ".json"), "w") as fh:
        json.dump(doc, fh)


class TestServeSatellites:
    def test_priority_lane_orders_scan(self, tmp_path):
        from repro.sched.manifest import serve_manifests
        from repro.sched.scheduler import PimScheduler
        spool = str(tmp_path)
        _spool_manifest(spool, "aaa")            # default priority 0
        _spool_manifest(spool, "bbb", prio=5)    # jumps the name order
        _spool_manifest(spool, "ccc", prio=5)    # tie -> name order
        sched = PimScheduler(make_system("host", n_cores=2))
        try:
            recs = serve_manifests(sched, spool, poll_interval=0.05,
                                   idle_timeout=0.4)
        finally:
            sched.shutdown()
        order = [os.path.basename(r["path"]) for r in recs]
        assert order == ["bbb.json", "ccc.json", "aaa.json"]
        assert all(r["state"] == "accepted" for r in recs)

    def test_restarted_serve_replays_sidecars(self, tmp_path):
        # kill/restart: the second watcher must replay the durable
        # verdicts (sidecars) instead of re-admitting the manifests
        from repro.sched.manifest import serve_manifests
        from repro.sched.scheduler import PimScheduler
        spool = str(tmp_path)
        _spool_manifest(spool, "job1")
        _spool_manifest(spool, "job2", prio=3)
        s1 = PimScheduler(make_system("host", n_cores=2))
        try:
            first = serve_manifests(s1, spool, poll_interval=0.05,
                                    idle_timeout=0.4)
        finally:
            s1.shutdown()     # "kill" the service
        assert len(first) == 2
        s2 = PimScheduler(make_system("host", n_cores=2))
        try:
            second = serve_manifests(s2, spool, poll_interval=0.05,
                                     idle_timeout=0.4)
        finally:
            s2.shutdown()
        assert len(second) == 2
        assert all(r.get("replayed") for r in second)
        assert all(r["state"] == "accepted" for r in second)


# ---------------------------------------------------------------------------
# CompressedReduce as a general ReduceStrategy (satellite a).
# ---------------------------------------------------------------------------

class TestCompressedReduce:
    def test_float_reduce_approximates_exact(self):
        import jax.numpy as jnp
        from repro.systems.compress import CompressedReduce
        pim = make_system("pim", n_cores=4)
        Xs = pim.shard_rows(np.arange(64, dtype=np.float32).reshape(32, 2))
        k = pim.named_kernel("t.colsum", lambda: (
            lambda xs: {"s": jnp.sum(xs, axis=0)}))
        out = pim.map_reduce(k, (Xs,), (), strategy=CompressedReduce())
        exact = pim.map_reduce(k, (Xs,), ())
        np.testing.assert_allclose(np.asarray(out["s"], np.float64),
                                   np.asarray(exact["s"], np.float64),
                                   rtol=0.05)
        assert pim.stats.compressed_bytes > 0

    def test_integer_leaves_pass_exact(self):
        # Q-format integer trees must NOT quantize — bit-exactness is
        # the whole point of the int32 ladder
        import jax.numpy as jnp
        from repro.systems.compress import CompressedReduce
        pim = make_system("pim", n_cores=4)
        Xs = pim.shard_rows(
            np.random.RandomState(0).randint(-99, 99, (32, 3)).astype(
                np.int32))
        k = pim.named_kernel("t.icolsum", lambda: (
            lambda xs: {"s": jnp.sum(xs, axis=0)}))
        out = pim.map_reduce(k, (Xs,), (), strategy=CompressedReduce())
        exact = pim.map_reduce(k, (Xs,), ())
        np.testing.assert_array_equal(np.asarray(out["s"]),
                                      np.asarray(exact["s"]))

    def test_error_feedback_bounds_cumulative_error(self):
        # EF's contract is about the SUM of repeated reduces: the
        # residual re-injects, so cumulative error stays bounded by
        # ~one quantization step, while stateless compression repeats
        # the same bias every round and accumulates it linearly
        import jax.numpy as jnp
        from repro.systems.compress import CompressedReduce
        pim = make_system("pim", n_cores=4)
        rows = np.random.RandomState(1).randn(32, 4).astype(np.float32)
        Xs = pim.shard_rows(rows)
        k = pim.named_kernel("t.colsum2", lambda: (
            lambda xs: {"s": jnp.sum(xs, axis=0)}))
        exact = rows.sum(0, dtype=np.float64)
        rounds = 6

        def cumulative_err(make_strategy):
            acc = np.zeros(4, np.float64)
            for _ in range(rounds):
                out = pim.map_reduce(k, (Xs,), (),
                                     strategy=make_strategy())
                acc += np.asarray(out["s"], np.float64)
            return float(np.abs(acc - rounds * exact).max())

        persistent = CompressedReduce()      # EF buffers carry over
        with_ef = cumulative_err(lambda: persistent)
        without_ef = cumulative_err(CompressedReduce)  # fresh each time
        assert without_ef > 0                # quantization does bias
        assert with_ef < without_ef


# ---------------------------------------------------------------------------
# Slow tier: the three-system compare driver + the bench-scale claim.
# ---------------------------------------------------------------------------

@slow
class TestEmbCompareSlow:
    def test_compare_tiny_includes_emb_on_three_systems(self):
        from repro.launch.compare import run_compare
        record = run_compare(tiny=True, cores=8)
        emb_rows = [r for r in record["rows"] if r["workload"] == "emb"]
        assert {r["system"] for r in emb_rows} == {"pim", "host",
                                                   "gpu-model"}
        for r in emb_rows:
            assert r["modeled_s"] > 0
        pim_row = next(r for r in emb_rows if r["system"] == "pim")
        assert pim_row["version"] == "int32"
        assert pim_row["modeled_kernel_s"] > 0

    def test_deferred_equal_loss_half_traffic(self):
        # the PR's acceptance claim at bench scale: D=8 cuts the sparse
        # update traffic >= 2x while landing within 1% of eager's
        # final training loss
        X, y = make_recsys(8192, 256, 192, dim=8, zipf_a=1.2, seed=0)
        out = {}
        for D in (1, 8):
            cfg = EmbConfig(version="int32", n_iters=192, batch=256,
                            dim=8, lr=1.0, frac_bits=12, seed=1,
                            flush_every=D, record_every=192)
            system = make_system("pim", n_cores=16)
            res = fit(system.put(X, y), cfg)
            out[D] = (system.stats.flush_bytes, res.history[-1][1])
        (eager_bytes, eager_loss), (lazy_bytes, lazy_loss) = out[1], out[8]
        assert eager_bytes / lazy_bytes >= 2.0, out
        assert abs(lazy_loss - eager_loss) <= 0.01 * eager_loss + 1e-9, out
