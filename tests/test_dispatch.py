"""Kernel backend-dispatch layer (repro/kernels/dispatch.py).

Three groups:
  * backend resolution (auto-selection, env override, error paths);
  * backend parity — ``jnp_ref`` vs ``pallas_interpret`` bit-exact for
    the integer kernels, tolerance-bounded for the float kernels,
    including ragged (non-multiple-of-block) shapes;
  * trainer routing — KMeans/DTree/LogReg fits actually go through the
    dispatch layer (asserted via the PimSystem kernel registry names
    AND the dispatch launch counters).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.fixed_point import fx_dot, to_fixed
from repro.core.lut import build_sigmoid_lut
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelBackend

BACKENDS = (KernelBackend.JNP_REF, KernelBackend.PALLAS_INTERPRET)


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------

def test_resolve_backend_accepts_strings_and_enums():
    assert dispatch.resolve_backend("jnp_ref") is KernelBackend.JNP_REF
    assert dispatch.resolve_backend("PALLAS_INTERPRET".lower()) \
        is KernelBackend.PALLAS_INTERPRET
    for be in KernelBackend:
        assert dispatch.resolve_backend(be) is be


def test_resolve_backend_rejects_unknown():
    with pytest.raises(ValueError, match="unknown kernel backend"):
        dispatch.resolve_backend("cuda")
    with pytest.raises(TypeError):
        dispatch.resolve_backend(7)


def test_default_backend_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.BACKEND_ENV_VAR, "pallas_interpret")
    assert dispatch.default_backend() is KernelBackend.PALLAS_INTERPRET
    monkeypatch.setenv(dispatch.BACKEND_ENV_VAR, "jnp_ref")
    assert dispatch.default_backend() is KernelBackend.JNP_REF


def test_default_backend_off_tpu_is_ref(monkeypatch):
    """Interpret mode must never be the silent default — off-TPU the
    fast path is the fused jnp oracle."""
    monkeypatch.delenv(dispatch.BACKEND_ENV_VAR, raising=False)
    import jax
    if jax.default_backend() != "tpu":
        assert dispatch.default_backend() is KernelBackend.JNP_REF


def test_all_families_registered():
    ops = dispatch.available_ops()
    for op in ("kmeans_assign", "gini_split", "lut_sigmoid",
               "quant_matmul", "int_matmul", "fx_matvec", "mha"):
        assert op in ops
    with pytest.raises(KeyError, match="unknown kernel op"):
        dispatch.get_op("nope")


# ---------------------------------------------------------------------------
# parity: integer kernels are bit-exact across backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,f,k", [(96, 8, 4), (1000, 16, 16), (33, 4, 2)])
def test_kmeans_assign_backend_parity(n, f, k):
    rng = np.random.RandomState(n)
    x = jnp.asarray(rng.randint(-2047, 2048, (n, f)), jnp.int16)
    c = jnp.asarray(rng.randint(-2047, 2048, (k, f)), jnp.int16)
    outs = [dispatch.launch("kmeans_assign", x, c, backend=be, block_n=64)
            for be in BACKENDS]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n,f,L,C", [(100, 3, 4, 2), (257, 8, 8, 3)])
def test_gini_split_backend_parity(n, f, L, C):
    rng = np.random.RandomState(n + L)
    x = jnp.asarray(rng.uniform(0, 1, (n, f)), jnp.float32)
    y = jnp.asarray(rng.randint(0, C, n), jnp.int32)
    leaf = jnp.asarray(rng.randint(0, L, n), jnp.int32)
    th = jnp.asarray(rng.uniform(0, 1, (L, f)), jnp.float32)
    outs = [dispatch.launch("gini_split", x, y, leaf, th, C, backend=be,
                            block_n=64) for be in BACKENDS]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shape", [(37,), (13, 5)])
def test_lut_sigmoid_backend_parity(shape):
    lut = build_sigmoid_lut()
    rng = np.random.RandomState(sum(shape))
    xq = to_fixed(jnp.asarray(rng.uniform(-25, 25, shape), jnp.float32), 10)
    a, b = [dispatch.launch("lut_sigmoid", xq, lut, backend=be)
            for be in BACKENDS]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_int_matmul_backend_parity():
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randint(-128, 128, (32, 64)), jnp.int8)
    b = jnp.asarray(rng.randint(-128, 128, (64, 32)), jnp.int8)
    o1, o2 = [dispatch.launch("int_matmul", a, b, backend=be,
                              bm=32, bn=32, bk=32) for be in BACKENDS]
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


@pytest.mark.parametrize("n,f", [(64, 16), (100, 7)])   # incl. ragged tail
def test_fx_matvec_backend_parity_and_oracle(n, f):
    rng = np.random.RandomState(n)
    xq = jnp.asarray(rng.randint(-1024, 1024, (n, f)), jnp.int32)
    wq = jnp.asarray(rng.randint(-1024, 1024, (f,)), jnp.int32)
    outs = [dispatch.launch("fx_matvec", xq, wq, 10, backend=be,
                            block_n=32) for be in BACKENDS]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    # the ref IS fixed_point.fx_dot — the trainers' pre-dispatch hot path
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(fx_dot(xq, wq, 10)))


def test_mha_backend_parity_tolerance():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.normal(0, 1, (1, 2, 64, 32)), jnp.float32)
    o1, o2 = [dispatch.launch("mha", q, q, q, backend=be, causal=True,
                              bq=32, bk=32) for be in BACKENDS]
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fx_matvec_public_wrapper_ragged(use_pallas):
    """The public ops wrapper must pad ragged N like the dispatch path
    (it once called the raw kernel and tripped its block assert)."""
    from repro.kernels.quant_matmul.ops import fx_matvec
    rng = np.random.RandomState(0)
    xq = jnp.asarray(rng.randint(-512, 512, (100, 5)), jnp.int32)
    wq = jnp.asarray(rng.randint(-512, 512, (5,)), jnp.int32)
    out = fx_matvec(xq, wq, 10, use_pallas=use_pallas, block_n=64)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(fx_dot(xq, wq, 10)))


def test_split_eval_kernel_masks_padding_totals():
    """Shard-padding rows must not inflate the spill slot's totals —
    leaf max_nodes-1 is allocatable as a real leaf (parity with the
    pre-dispatch in-line kernel, which masked totals to zero)."""
    from repro.core.dtree import make_split_eval_kernel
    max_nodes, n_classes = 4, 2
    kern = make_split_eval_kernel(max_nodes, n_classes)
    x = jnp.asarray([[0.1], [0.2], [0.3], [0.4], [9.9], [9.9]], jnp.float32)
    y = jnp.asarray([0, 1, 0, 1, 0, 0], jnp.int32)
    # two real points live in the spill leaf max_nodes-1
    leaf = jnp.asarray([0, 0, 3, 3, 0, 0], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 0, 0], bool)
    th = jnp.full((max_nodes, 1), 0.5, jnp.float32)
    out = kern(x, y, leaf, valid, th)
    np.testing.assert_array_equal(np.asarray(out["total"]),
                                  [[1, 1], [0, 0], [0, 0], [1, 1]])
    assert int(out["total"].sum()) == 4  # only the valid rows


def test_pallas_backend_degrades_to_ref_when_unavailable():
    """It never does: a requested Pallas backend is honoured as asked
    (and fails loudly where it cannot run), never swapped for jnp_ref."""
    assert dispatch.resolve_backend("pallas_tpu") is KernelBackend.PALLAS_TPU
    assert dispatch.resolve_backend("pallas_interpret") \
        is KernelBackend.PALLAS_INTERPRET
    assert not hasattr(dispatch, "HAS_PALLAS")


# ---------------------------------------------------------------------------
# trainer routing: fits go through the dispatch layer
# ---------------------------------------------------------------------------

def _count(op):
    return dispatch.launch_counts.get(op, 0)


def _toy(n=60, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(0, 1, (n, f)).astype(np.float32)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.int32)
    return X, y


def test_kmeans_fit_routes_through_dispatch():
    from repro.core import kmeans
    from repro.core.pim import PimConfig, PimSystem
    X, _ = _toy()
    pim = PimSystem(PimConfig(n_cores=2))
    before = _count("kmeans_assign")
    r = kmeans.fit(pim.put(X), kmeans.KMeansConfig(k=3, max_iters=4))
    assert _count("kmeans_assign") > before
    tag = dispatch.backend_tag(None)
    assert f"kme.assign/k3/{tag}" in pim.registered_kernels()
    assert r.labels is not None and r.labels.shape == (X.shape[0],)


def test_dtree_fit_routes_through_dispatch():
    from repro.core import dtree
    from repro.core.pim import PimConfig, PimSystem
    X, y = _toy()
    pim = PimSystem(PimConfig(n_cores=2))
    before = _count("gini_split")
    tree = dtree.fit(pim.put(X, y), dtree.TreeConfig(max_depth=3))
    assert _count("gini_split") > before
    tag = dispatch.backend_tag(None)
    assert any(k.startswith("dtr.eval/") and k.endswith(tag)
               for k in pim.registered_kernels())
    assert tree.n_nodes >= 1


def test_logreg_fit_routes_through_dispatch():
    from repro.core import logreg
    from repro.core.pim import PimConfig, PimSystem
    X, y = _toy()
    pim = PimSystem(PimConfig(n_cores=2))
    before_mv, before_lut = _count("fx_matvec"), _count("lut_sigmoid")
    logreg.fit(pim.put(X, y),
               logreg.LogRegConfig(version="int32_lut_wram", n_iters=3))
    assert _count("fx_matvec") > before_mv
    assert _count("lut_sigmoid") > before_lut


def test_trainer_results_backend_invariant():
    """jnp_ref and pallas_interpret produce identical fits (integer
    kernels are deterministic; the backend is a pure performance knob)."""
    from repro.core import dtree, kmeans
    from repro.core.pim import PimConfig, PimSystem
    X, y = _toy(n=48, f=5)
    results = {}
    for be in ("jnp_ref", "pallas_interpret"):
        pim = PimSystem(PimConfig(n_cores=2))
        km = kmeans.fit(pim.put(X), kmeans.KMeansConfig(
            k=3, max_iters=4, kernel_backend=be))
        tr = dtree.fit(pim.put(X, y), dtree.TreeConfig(
            max_depth=3, kernel_backend=be))
        results[be] = (km.inertia, km.labels, tr.feature.copy(),
                       tr.threshold.copy(), tr.n_nodes)
    a, b = results["jnp_ref"], results["pallas_interpret"]
    assert a[0] == b[0]
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2], b[2])
    np.testing.assert_array_equal(a[3], b[3])
    assert a[4] == b[4]


def test_estimator_exposes_kernel_backend():
    from repro.api import make_estimator
    from repro.core.pim import PimConfig, PimSystem
    X, _ = _toy()
    est = make_estimator("kmeans", n_clusters=3, max_iter=4,
                         kernel_backend="jnp_ref",
                         system=PimSystem(PimConfig(n_cores=2)))
    est.fit(X)
    assert est.get_params()["kernel_backend"] == "jnp_ref"
