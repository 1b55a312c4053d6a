"""Welford streaming statistics: exactness + merge associativity."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.reduction import (
    blocked_welford,
    finalize,
    init_welford,
    merge,
    tree_sum,
    update_batch,
)


def test_update_batch_matches_numpy(rng):
    x = rng.standard_normal((64, 3)).astype(np.float32) * 10
    acc = init_welford((3,))
    acc = update_batch(acc, jnp.asarray(x))
    stats = finalize(acc)
    np.testing.assert_allclose(np.asarray(stats.mean), x.mean(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(stats.var), x.var(0, ddof=1),
                               rtol=1e-4)


def test_windowed_merge_equals_batch(rng):
    x = rng.standard_normal((100, 2)).astype(np.float32)
    acc = init_welford((2,))
    for i in range(0, 100, 10):
        acc = update_batch(acc, jnp.asarray(x[i:i + 10]))
    s = finalize(acc)
    np.testing.assert_allclose(np.asarray(s.mean), x.mean(0), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s.var), x.var(0, ddof=1), rtol=1e-3,
                               atol=1e-5)


def test_masked_update(rng):
    x = rng.standard_normal((10, 2)).astype(np.float32)
    mask = np.array([1, 1, 0, 1, 0, 1, 1, 0, 1, 1], bool)
    acc = update_batch(init_welford((2,)), jnp.asarray(x), jnp.asarray(mask))
    s = finalize(acc)
    np.testing.assert_allclose(np.asarray(s.mean), x[mask].mean(0), rtol=1e-5)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-1e3, 1e3), min_size=2, max_size=40),
       st.integers(1, 10))
def test_merge_associative_split_invariant(xs, split):
    """Any split of the sample stream yields identical (n, mean, m2)."""
    x = np.asarray(xs, np.float32)[:, None]
    split = min(split, len(xs) - 1)
    a = update_batch(init_welford((1,)), jnp.asarray(x))
    b1 = update_batch(init_welford((1,)), jnp.asarray(x[:split]))
    b2 = update_batch(init_welford((1,)), jnp.asarray(x[split:]))
    b = merge(b1, b2)
    scale = max(1.0, np.abs(x).max()) ** 2
    assert abs(float(a.n[0] - b.n[0])) == 0
    assert abs(float(a.mean[0] - b.mean[0])) < 1e-3 * max(1.0, np.abs(x).max())
    assert abs(float(a.m2[0] - b.m2[0])) < 1e-2 * scale * len(xs)


def test_ci90_shrinks_with_n(rng):
    x = rng.standard_normal((1000, 1)).astype(np.float32)
    s_small = finalize(update_batch(init_welford((1,)), jnp.asarray(x[:10])))
    s_big = finalize(update_batch(init_welford((1,)), jnp.asarray(x)))
    assert float(s_big.ci90[0]) < float(s_small.ci90[0])


def _pairwise(x):
    """tree_sum's order in numpy float32: rows [0, h) + rows [h, 2h),
    an odd last row carried."""
    x = np.asarray(x, np.float32)
    if len(x) == 0:
        return np.zeros(x.shape[1:], np.float32)
    while len(x) > 1:
        h = len(x) // 2
        x = np.concatenate([x[:h] + x[h:2 * h], x[2 * h:]])
    return x[0]


@pytest.mark.parametrize("rows", [0, 1, 2, 3, 5, 8, 33, 1024])
def test_tree_sum_is_the_pairwise_tree(rows, rng):
    """The same bits eagerly, under jit and under vmap: the order is the
    row count's, not the program's."""
    x = (rng.standard_normal((rows, 3)) * 1e3).astype(np.float32)
    want = _pairwise(x)
    assert (np.asarray(tree_sum(jnp.asarray(x))) == want).all()
    assert (np.asarray(jax.jit(tree_sum)(jnp.asarray(x))) == want).all()
    pair = jnp.stack([jnp.asarray(x), -jnp.asarray(x)])
    got = np.asarray(jax.vmap(tree_sum)(pair))
    assert (got[0] == want).all() and (got[1] == -want).all()


def test_block_partials_bitwise_in_any_program(rng):
    """One block's Welford partial has the same bits folded alone, as a
    row of the blocked fold, and inside a jitted scan (each a compiled
    program): the sharded block and an unsharded fold meet in these
    bits."""
    obs = jnp.asarray(rng.integers(800, 1200, (4 * 300, 2)),
                      jnp.float32)
    blocks = blocked_welford(obs, 4)

    def scanned(o):
        def body(c, xb):
            return c, update_batch(init_welford(xb.shape[1:]), xb)
        return jax.lax.scan(body, 0, o.reshape(4, 300, 2))[1]

    in_scan = jax.jit(scanned)(obs)
    for b in range(4):
        alone = jax.jit(update_batch)(init_welford((2,)),
                                      obs[b * 300:(b + 1) * 300])
        for leaf_a, leaf_b, leaf_c in zip(alone, blocks, in_scan):
            assert (np.asarray(leaf_a) == np.asarray(leaf_b[b])).all()
            assert (np.asarray(leaf_a) == np.asarray(leaf_c[b])).all()
