"""The int32 GD view in ``fx_matvec``'s blocked feature-major layout.

``PimDataset.gd_view`` stores the int32 family once as ``Xs (C, nb, F8,
bn)``, ``ys`` and an int32 mask ``(C, nb, bn)`` (DESIGN.md §3.2).  These
tests un-block it back to ``shard_rows``' rows, and hold the LIN and LOG
int32 fits on it, serial and fused, to a row-major reference built on
``fixed_point.fx_dot`` — bit for bit, on a row count that is no multiple
of cores x 1,024.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import linreg, logreg
from repro.core.fixed_point import (_shift_round, fx_dot, fx_sum,
                                    to_fixed)
from repro.core.lut import build_sigmoid_lut, taylor_sigmoid_fixed
from repro.core.pim import PimConfig, PimSystem
from repro.kernels.lut_activation.ref import lut_sigmoid_ref
from repro.systems.host import HostSystem

CORES = 4
N, F = 4 * 2500 - 3, 5          # 2,500 rows a core: 3 blocks, ragged
FRAC = 10


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (N, F)).astype(np.float32)
    y = (X @ rng.uniform(-1, 1, F) > 0.1).astype(np.float32)
    return X, y


def _unblock(a, n_pc):
    """(C, nb, *F8, bn) -> (C, n_pc, *F8): the rows back on axis 1."""
    a = np.moveaxis(np.asarray(a), -1, 2)
    return a.reshape(a.shape[0], -1, *a.shape[3:])[:, :n_pc]


@pytest.mark.parametrize("system,n", [
    (lambda: PimSystem(PimConfig(n_cores=CORES)), N),
    (lambda: PimSystem(PimConfig(n_cores=3)), 301),    # one small block
    (lambda: HostSystem(), N),
])
def test_int32_view_unblocks_to_shard_rows(data, system, n):
    X, y = data[0][:n], data[1][:n]
    sys_ = system()
    ds = sys_.put(X, y)
    Xs, ys, mask = ds.gd_view("int32_lut_wram")
    assert ds.gd_view("int32") is not None and ds.n_views == 1
    c, n_pc = sys_.n_shards, ds.n_pc
    bn = min(1024, -(-n_pc // 256) * 128)     # two blocks when it can
    nb = -(-n_pc // bn)
    assert Xs.shape == (c, nb, 8, bn) and ys.shape == mask.shape == (
        c, nb, bn)
    assert Xs.dtype == ys.dtype == mask.dtype == jnp.int32
    ref = PimSystem(PimConfig(n_cores=c))   # shard_rows' own partition
    rows_x = np.asarray(ref.shard_rows(np.asarray(to_fixed(X, FRAC))))
    rows_y = np.asarray(ref.shard_rows(np.asarray(to_fixed(y, FRAC))))
    ux = _unblock(Xs, n_pc)
    np.testing.assert_array_equal(ux[..., :F], rows_x)
    assert not ux[..., F:].any()                        # pad features
    np.testing.assert_array_equal(_unblock(ys, n_pc), rows_y)
    # the mask is false exactly on the pads, those of shard_rows and
    # those of the last block alike; pad rows are zero
    valid = np.zeros((c, nb * bn), bool)
    valid[:, :n_pc] = np.asarray(ref.row_validity_mask(n))
    np.testing.assert_array_equal(
        np.asarray(mask).reshape(c, -1), valid.astype(np.int32))
    assert not np.moveaxis(np.asarray(Xs), -1, 2).reshape(
        c, nb * bn, -1)[~valid].any()
    assert not np.asarray(ys).reshape(c, -1)[~valid].any()
    # the shared mask keeps its row-major shape
    assert ds.mask().shape == (c, n_pc) and ds.mask().dtype == jnp.bool_


def _reference(X, y, workload, version, n_iters, lr, minibatch=0, seed=0):
    """The int32 GD loop on row-major shards, per core, on ``fx_dot``:
    the trainers' numerics without the blocked view or the kernels."""
    n = X.shape[0]
    pim = PimSystem(PimConfig(n_cores=CORES))
    Xs = jnp.asarray(pim.shard_rows(np.asarray(to_fixed(X, FRAC))))
    ys = jnp.asarray(pim.shard_rows(np.asarray(to_fixed(y, FRAC))))
    ms = jnp.asarray(pim.row_validity_mask(n), jnp.int32)
    prepare, update = linreg.make_gd_step_fns(
        linreg.GdConfig(version="int32", frac_bits=FRAC))
    lut = build_sigmoid_lut(20, 10)
    n_pc = Xs.shape[1]
    rng = np.random.RandomState(seed)
    if workload == "lin":
        s = jnp.float32(lr * (2.0 / (minibatch * CORES if minibatch
                                     else n)))
    else:
        s = jnp.float32(lr * (1.0 / n))
    w, b = jnp.zeros(F, jnp.float32), jnp.float32(0.0)
    for _ in range(n_iters):
        wq, bq = prepare((w, b, s))
        Xc, yc, mc = Xs, ys, ms
        if minibatch:
            st = int(rng.randint(0, n_pc - minibatch + 1))
            Xc, yc, mc = (a[:, st:st + minibatch] for a in (Xs, ys, ms))
        z = fx_dot(Xc, wq, FRAC) + bq
        if version == "int32_lut_wram":
            p = _shift_round(lut_sigmoid_ref(z, lut.table, lut.value_frac),
                             lut.value_frac - FRAC)
        elif workload == "log":
            p = taylor_sigmoid_fixed(z, FRAC, terms=8)
        else:
            p = z
        err = (p - yc) * mc
        gw = fx_sum(_shift_round(err[..., None] * Xc, FRAC), 1)
        reduced = {"gw": jnp.sum(gw, 0), "gb": jnp.sum(fx_sum(err, 1), 0)}
        (w, b, s), _ = update((w, b, s), reduced)
    return np.asarray(w), float(b)


CASES = [("lin", "int32", {}), ("lin", "int32", {"minibatch": 700,
                                                  "seed": 3}),
         ("log", "int32", {}), ("log", "int32_lut_wram", {})]


@pytest.mark.parametrize("fuse", [1, 4])
@pytest.mark.parametrize("workload,version,kw", CASES,
                         ids=["lin", "lin_minibatch", "log_taylor",
                              "log_lut_wram"])
def test_int32_fits_equal_row_major_reference(data, workload, version, kw,
                                              fuse):
    X, y = data
    n_iters = 6
    ds = PimSystem(PimConfig(n_cores=CORES)).put(X, y)
    if workload == "lin":
        lr = 0.1
        r = linreg.fit(ds, linreg.GdConfig(version=version, n_iters=n_iters,
                                           lr=lr, fuse_steps=fuse, **kw))
    else:
        lr = 5.0
        r = logreg.fit(ds, logreg.LogRegConfig(version=version,
                                               n_iters=n_iters, lr=lr,
                                               fuse_steps=fuse))
    w, b = _reference(X, y, workload, version, n_iters, lr, **kw)
    np.testing.assert_array_equal(r.w, w)
    assert r.b == b
    assert np.abs(w).max() > 0.01          # the fit moved
