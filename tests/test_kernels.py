"""Per-kernel validation: shape/dtype sweeps, interpret=True vs ref oracle.

(`hypothesis` is not installable offline; sweeps are seeded parameterized
grids + randomized draws per cell — see also tests/test_property.py.)
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fixed_point import to_fixed
from repro.core.lut import SigmoidLut, build_sigmoid_lut

# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------
from repro.kernels.quant_matmul.kernel import (fx_matvec, int_matmul,
                                               to_feature_blocks)
from repro.kernels.quant_matmul.ops import quant_dense, quant_matmul
from repro.kernels.quant_matmul.ref import int_matmul_ref, quant_matmul_ref


slow = pytest.mark.slow  # large-shape interpret-mode cases (tier-1 only)


@pytest.mark.parametrize("n,f,bn", [
    (1024, 8, 1024),                  # one whole block, no feature pad
    (2500, 18, 1024),                 # ragged rows, 18 -> 24 features
    (300, 5, 128),                    # rows past several small blocks
])
def test_fx_matvec_blocked_kernel_matches_fx_dot(n, f, bn):
    """The interpret-mode kernel over the blocked layout equals
    ``fixed_point.fx_dot`` row for row, with rounding ties of both signs
    and the Q10 range's extremes; pad rows and features add nothing."""
    from repro.core.fixed_point import fx_dot
    rng = np.random.RandomState(n + f)
    x = rng.randint(-2 ** 20, 2 ** 20, (n, f))
    x[:4] = [[2 ** 20 - 1], [-2 ** 20], [512], [-512]]   # extremes, ties
    w = rng.randint(-1024, 1024, f)
    w[0] = 1
    xq, wq = jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32)
    xb = to_feature_blocks(xq[None], bn)[0]
    assert xb.shape == (-(-n // bn), -(-f // 8) * 8, bn)
    out = fx_matvec(xb, wq, frac_bits=10, interpret=True)
    assert out.shape == (xb.shape[0], bn) and out.dtype == jnp.int32
    flat = np.asarray(out).reshape(-1)
    np.testing.assert_array_equal(flat[:n], np.asarray(fx_dot(xq, wq, 10)))
    assert not flat[n:].any()


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (128, 128, 128, 128, 128, 128),   # single block
    pytest.param(256, 384, 128, 128, 128, 128,
                 marks=slow),         # multi-block all dims
    (64, 64, 64, 32, 16, 64),         # small, odd block ratios
    (8, 256, 8, 8, 64, 8),            # skinny
])
def test_int_matmul_exact(m, k, n, bm, bk, bn):
    rng = np.random.RandomState(m + n + k)
    a = jnp.asarray(rng.randint(-128, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.randint(-128, 128, (k, n)), jnp.int8)
    out = int_matmul(a, b, bm=bm, bk=bk, bn=bn, interpret=True)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(int_matmul_ref(a, b)))


@pytest.mark.parametrize("scale_kind", ["scalar", "per_channel"])
def test_quant_matmul_dequant(scale_kind):
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randint(-128, 128, (64, 128)), jnp.int8)
    b = jnp.asarray(rng.randint(-128, 128, (128, 64)), jnp.int8)
    sa = jnp.float32(0.01)
    sb = (jnp.float32(0.02) if scale_kind == "scalar"
          else jnp.asarray(rng.uniform(0.01, 0.05, (1, 64)), jnp.float32))
    out = quant_matmul(a, b, sa, sb, use_pallas=True, interpret=True)
    ref = quant_matmul_ref(a, b, sa, sb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_dense_accuracy(dtype):
    """Quantized dense must track the float matmul within int8 error."""
    from repro.core.quantization import symmetric_quantize
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.normal(0, 1, (32, 256)), dtype)
    w = jnp.asarray(rng.normal(0, 0.05, (256, 128)), jnp.float32)
    wq, wp = symmetric_quantize(w, bits=8, axis=1)
    out = quant_dense(x, wq, wp.scale, use_pallas=True, interpret=True)
    ref = x.astype(jnp.float32) @ w
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref))
    rel = err.max() / max(float(np.abs(np.asarray(ref)).max()), 1e-9)
    assert rel < 0.05


# ---------------------------------------------------------------------------
# lut_activation
# ---------------------------------------------------------------------------
from repro.kernels.dispatch import KernelBackend
from repro.kernels.lut_activation.ops import lut_sigmoid
from repro.kernels.lut_activation.ref import lut_sigmoid_ref


@pytest.mark.parametrize("shape", [(7,), (100,), (33, 5), (256, 128)])
@pytest.mark.parametrize("frac_bits", [8, 10])
def test_lut_sigmoid_kernel_matches_ref(shape, frac_bits):
    lut = build_sigmoid_lut(boundary=20, frac_bits=frac_bits)
    rng = np.random.RandomState(sum(shape))
    x = jnp.asarray(rng.uniform(-25, 25, shape), jnp.float32)
    xq = to_fixed(x, frac_bits)
    out = lut_sigmoid(xq, lut, placement="vmem")
    ref = lut_sigmoid_ref(xq, lut.table, lut.value_frac)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("frac_bits", [8, 10])
def test_lut_sigmoid_every_index_and_past_boundary(frac_bits):
    """Bit for bit on every table index and its negation (each table
    row, each lane of the in-register gather), past the boundary, and
    at the largest magnitudes int32 holds."""
    lut = build_sigmoid_lut(boundary=20, frac_bits=frac_bits)
    n = lut.table.shape[0]
    past = np.array([n, n + 1, n + 127, 2 * n, 10 ** 6, 10 ** 9,
                     2 ** 31 - 2, 2 ** 31 - 1])
    mags = np.concatenate([np.arange(n), past])
    xq = jnp.asarray(np.concatenate([mags, -mags]), jnp.int32)
    out = lut_sigmoid(xq, lut, backend=KernelBackend.PALLAS_INTERPRET)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(lut_sigmoid_ref(xq, lut.table,
                                                    lut.value_frac)))


def test_lut_sigmoid_any_int16_table():
    """Two entries share a 32-bit word in the kernel's table: any int16
    entry, the extremes included, comes back sign-extended, and a table
    whose length is no multiple of 256 reads no padding."""
    rng = np.random.RandomState(7)
    table = rng.randint(-2 ** 15, 2 ** 15, 3 * 256 + 77).astype(np.int16)
    table[:4] = [-2 ** 15, 2 ** 15 - 1, -1, 0]
    lut = SigmoidLut(jnp.asarray(table), frac_bits=6, boundary=13,
                     value_frac=15)
    mags = np.arange(table.size + 200)
    xq = jnp.asarray(np.concatenate([mags, -mags]), jnp.int32)
    out = lut_sigmoid(xq, lut, backend=KernelBackend.PALLAS_INTERPRET)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(lut_sigmoid_ref(xq, lut.table,
                                                    lut.value_frac)))


def test_lut_sigmoid_ragged_per_core_vmapped():
    """The SUSY per-core length (78,125 logits: 611 rows of 128, padded
    to 616) under ``vmap`` over a cores axis, as ``System`` calls it."""
    lut = build_sigmoid_lut()
    rng = np.random.RandomState(78125)
    xq = jnp.asarray(rng.randint(-(25 << 10), 25 << 10, (2, 78_125)),
                     jnp.int32)
    out = jax.vmap(lambda z: lut_sigmoid(
        z, lut, backend=KernelBackend.PALLAS_INTERPRET))(xq)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(lut_sigmoid_ref(xq, lut.table,
                                                    lut.value_frac)))


def test_lut_sigmoid_placements_identical():
    """Paper §5.2.2: WRAM vs MRAM placement is performance-only."""
    lut = build_sigmoid_lut()
    xq = to_fixed(jnp.linspace(-20, 20, 999), 10)
    np.testing.assert_array_equal(
        np.asarray(lut_sigmoid(xq, lut, placement="vmem")),
        np.asarray(lut_sigmoid(xq, lut, placement="hbm")))


# ---------------------------------------------------------------------------
# kmeans_assign
# ---------------------------------------------------------------------------
from repro.kernels.kmeans_assign.ops import assign_and_accumulate
from repro.kernels.kmeans_assign.ref import kmeans_assign_ref


@pytest.mark.parametrize("n,f,k,bn", [
    pytest.param(1024, 16, 16, 256, marks=slow),
    (1000, 16, 16, 256),    # padding path
    (128, 8, 4, 128),
    pytest.param(512, 32, 64, 64, marks=slow),
])
def test_kmeans_assign_matches_ref(n, f, k, bn):
    rng = np.random.RandomState(n + k)
    x = jnp.asarray(rng.randint(-2047, 2048, (n, f)), jnp.int16)
    c = jnp.asarray(rng.randint(-2047, 2048, (k, f)), jnp.int16)
    l1, s1, n1 = assign_and_accumulate(x, c, use_pallas=True, block_n=bn)
    l2, s2, n2 = kmeans_assign_ref(x, c)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    assert int(n1.sum()) == n


@slow
def test_kmeans_assign_int32_exactness_bound():
    """The sums come back as exact int32 pairs (DESIGN.md §2): the
    high byte and the normalised low byte of the coordinate sum."""
    n, f, k = 4096, 16, 2
    x = jnp.full((n, f), 2047, jnp.int16)
    c = jnp.asarray(np.stack([np.full(f, 2047), np.full(f, -2047)]),
                    jnp.int16)
    _, sums, counts = assign_and_accumulate(x, c, use_pallas=True,
                                            block_n=1024)
    assert int(counts[0]) == n
    hi, lo = (int(v) for v in sums[0, 0])
    assert 0 <= lo < 256
    assert hi * 256 + lo == 2047 * n  # exact, no overflow


# ---------------------------------------------------------------------------
# gini_split
# ---------------------------------------------------------------------------
from repro.kernels.gini_split.ops import split_evaluate
from repro.kernels.gini_split.ref import gini_counts_ref


@pytest.mark.parametrize("n,f,L,C,bn", [
    pytest.param(1024, 16, 8, 2, 256, marks=slow),
    (1000, 16, 8, 2, 256),   # padding path
    pytest.param(512, 4, 32, 4, 128, marks=slow),    # multiclass
    (100, 1, 1, 2, 100),     # single feature/leaf
])
def test_gini_split_matches_ref(n, f, L, C, bn):
    rng = np.random.RandomState(n + L)
    x = jnp.asarray(rng.uniform(0, 1, (n, f)), jnp.float32)
    y = jnp.asarray(rng.randint(0, C, n), jnp.int32)
    leaf = jnp.asarray(rng.randint(0, L, n), jnp.int32)
    th = jnp.asarray(rng.uniform(0, 1, (L, f)), jnp.float32)
    b1, t1 = split_evaluate(x, y, leaf, th, C, use_pallas=True, block_n=bn)
    b2, t2 = gini_counts_ref(x, y, leaf, th, C)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    assert int(t1.sum()) == n


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
from repro.kernels.flash_attention.ops import mha
from repro.kernels.flash_attention.ref import attention_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,bq,bk", [(128, 64, 64),
                                     pytest.param(256, 128, 64, marks=slow),
                                     (64, 64, 64)])
def test_flash_causal_matches_ref(dtype, s, bq, bk):
    rng = np.random.RandomState(s)
    q = jnp.asarray(rng.normal(0, 1, (2, 4, s, 64)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (2, 4, s, 64)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (2, 4, s, 64)), dtype)
    out = mha(q, k, v, causal=True, use_pallas=True, bq=bq, bk=bk)
    ref = mha(q, k, v, causal=True, use_pallas=False)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_gqa_and_noncausal():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.normal(0, 1, (1, 8, 128, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 2, 128, 32)), jnp.float32)
    for causal in (True, False):
        out = mha(q, k, v, causal=causal, use_pallas=True, bq=64, bk=64)
        ref = mha(q, k, v, causal=causal, use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)


@slow
def test_flash_decode_one_token():
    """serve_step shape: 1 query against a long KV cache."""
    rng = np.random.RandomState(9)
    skv = 512
    q = jnp.asarray(rng.normal(0, 1, (2, 4, 1, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 4, skv, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 4, skv, 64)), jnp.float32)
    out = mha(q, k, v, causal=True, q_offset=skv - 1, use_pallas=True,
              bq=1, bk=128)
    ref = mha(q, k, v, causal=True, q_offset=skv - 1, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


@pytest.mark.parametrize("window,s,bq,bk", [
    pytest.param(32, 256, 64, 64, marks=slow),
    (64, 128, 64, 64), (1, 128, 64, 64),
    pytest.param(100, 256, 128, 64, marks=slow),
])
def test_flash_sliding_window_matches_ref(window, s, bq, bk):
    """SWA path (hymba): out-of-window kv blocks are skipped entirely."""
    rng = np.random.RandomState(window + s)
    q = jnp.asarray(rng.normal(0, 1, (1, 4, s, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 4, s, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 4, s, 32)), jnp.float32)
    out = mha(q, k, v, causal=True, window=window, use_pallas=True,
              bq=bq, bk=bk)
    ref = mha(q, k, v, causal=True, window=window, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_flash_window_decode():
    """Windowed single-token decode against a long cache."""
    rng = np.random.RandomState(3)
    skv = 256
    q = jnp.asarray(rng.normal(0, 1, (1, 2, 1, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (1, 2, skv, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (1, 2, skv, 32)), jnp.float32)
    out = mha(q, k, v, causal=True, q_offset=skv - 1, window=64,
              use_pallas=True, bq=1, bk=64)
    ref = mha(q, k, v, causal=True, q_offset=skv - 1, window=64,
              use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
