import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.fixed_point import (_shift_round, from_fixed,
                                    from_fixed_sum, fx_dot, fx_dot_hybrid,
                                    fx_mul, fx_recip, fx_sum, to_fixed)


def test_to_from_fixed_roundtrip():
    x = np.linspace(-4, 4, 101).astype(np.float32)
    q = to_fixed(x, 10)
    back = np.asarray(from_fixed(q, 10))
    assert np.abs(back - x).max() <= 2 ** -10


def test_to_fixed_saturates():
    q = to_fixed(np.array([300.0]), 7, dtype=jnp.int8)
    assert int(q[0]) == 127


def test_shift_round_rounds_to_nearest():
    # floor-shift of -1 >> 1 would give -1; round gives 0 or -1 consistently
    x = jnp.asarray([3, 5, -3, -5], jnp.int32)
    out = np.asarray(_shift_round(x, 1))
    assert list(out) == [2, 3, -1, -2]  # round-half-up behaviour


@pytest.mark.parametrize("frac", [8, 10, 12])
def test_fx_mul_matches_float(frac):
    rng = np.random.RandomState(0)
    a = rng.uniform(-4, 4, 256).astype(np.float32)
    b = rng.uniform(-4, 4, 256).astype(np.float32)
    out = from_fixed(fx_mul(to_fixed(a, frac), to_fixed(b, frac), frac), frac)
    assert np.abs(np.asarray(out) - a * b).max() < 40 * 2.0 ** -frac


def test_fx_dot_matches_float():
    rng = np.random.RandomState(1)
    X = rng.uniform(0, 1, (32, 16)).astype(np.float32)
    w = rng.uniform(-1, 1, 16).astype(np.float32)
    out = from_fixed(fx_dot(to_fixed(X, 10), to_fixed(w, 10), 10), 10)
    assert np.abs(np.asarray(out) - X @ w).max() < 16 * 2.0 ** -10 * 4


def test_fx_dot_hybrid_close_and_saturating():
    rng = np.random.RandomState(2)
    X = rng.uniform(0, 1, (8, 16)).astype(np.float32)
    w = rng.uniform(-1, 1, 16).astype(np.float32)
    out = from_fixed(
        fx_dot_hybrid(to_fixed(X, 7, dtype=jnp.int8),
                      to_fixed(w, 8, dtype=jnp.int16), 7, 8, 10), 10)
    assert np.abs(np.asarray(out) - X @ w).max() < 0.1
    # saturation: huge weights would overflow int16 accumulation
    w_big = np.full(16, 60.0, np.float32)
    out_sat = fx_dot_hybrid(to_fixed(X, 7, dtype=jnp.int8),
                            to_fixed(w_big, 8, dtype=jnp.int16), 7, 8, 10)
    assert int(np.max(np.asarray(out_sat))) <= 2 ** 15 - 1


def test_fx_recip():
    rng = np.random.RandomState(3)
    d = rng.uniform(0.5, 8.0, 64).astype(np.float32)
    r = from_fixed(fx_recip(to_fixed(d, 10), 10), 10)
    assert np.abs(np.asarray(r) - 1.0 / d).max() < 0.01


@pytest.mark.parametrize("term,n,shards", [
    (-1024, 2_500_000, 64),   # SUSY's first LIN bias gradient: -2.56e9
    (1023, 3_000_000, 1),     # one shard past int32
    (-7, 1000, 8),            # small sums stay exact as well
])
def test_fx_sum_pairs_exact_past_int32(term, n, shards):
    """Gradient sums outgrow int32 at the paper's dataset sizes; the
    (hi, lo) pairs of shard partials add to the exact total."""
    t = jnp.full((shards, n // shards), term, jnp.int32)
    pairs = fx_sum(t, axis=1)                        # one pair per shard
    assert pairs.shape == (shards, 2) and pairs.dtype == jnp.int32
    total = term * (n // shards) * shards
    got = float(from_fixed_sum(jnp.sum(pairs, axis=0), 0))
    assert got == float(np.float32(total))
    assert float(from_fixed_sum(jnp.sum(pairs, axis=0), 10)) \
        == float(np.float32(total) / np.float32(1024))
