"""On-line trajectory reduction (paper §5.2 schema iii).

Trajectories sampled at fixed sim-time grid points are reduced to
running (count, mean, M2) Welford accumulators per (grid point,
species) — mean / variance / 90% confidence exactly as the paper's
Fig. 1 — while the raw window is discarded (memory-bounded streaming).

`merge` is Chan's parallel merge: associative, so the reduction forms a
tree across lanes, shards and pods (the paper's single collector thread,
made hierarchical — DESIGN.md §7).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

Z90 = 1.6448536269514722  # two-sided 90% normal quantile


class Welford(NamedTuple):
    n: jax.Array  # (...,) float32 count
    mean: jax.Array
    m2: jax.Array


def init_welford(shape) -> Welford:
    z = jnp.zeros(shape, jnp.float32)
    return Welford(n=z, mean=jnp.zeros_like(z), m2=jnp.zeros_like(z))


def tree_sum(x):
    """Sum over axis 0 as a fixed pairwise tree of elementwise adds:
    rows [0, h) plus rows [h, 2h) at each level, an odd last row carried
    to the next. A float reduce leaves its order to the compiler, which
    picks it per program (on a TPU the fused sharded block and a
    one-device fold summed the same 65,536 lanes differently); this
    order depends on the row count alone, so every compiled program
    folds one block to the same bits. (Op-by-op eager execution may
    differ where a backend fuses a product into the first adds, as the
    CPU backend makes fused multiply-adds: the engine folds only in
    compiled calls.)"""
    if x.shape[0] == 0:
        return jnp.zeros(x.shape[1:], x.dtype)
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        pair = x[:h] + x[h:2 * h]
        x = pair if x.shape[0] % 2 == 0 else jnp.concatenate(
            [pair, x[2 * h:]])
    return x[0]


def update_batch(acc: Welford, x, mask=None) -> Welford:
    """Fold a batch of samples. x: (B, ...) folding over axis 0;
    mask: (B,) optional validity. The float sums are `tree_sum`s and
    the count an int32 sum, so in a compiled program the fold's bits
    depend on the values alone. (Compiled, a division by a count the
    compiler can fold is a product with its reciprocal; eagerly it is a
    division: they agree where B is a power of two.)"""
    if mask is None:
        mask = jnp.ones(x.shape[0], bool)
    m = mask.astype(jnp.float32)
    while m.ndim < x.ndim:
        m = m[..., None]
    xb = x.astype(jnp.float32) * m
    nb = jnp.broadcast_to(m.astype(jnp.int32), x.shape).sum(
        axis=0).astype(jnp.float32)
    mean_b = jnp.where(nb > 0, tree_sum(xb) / jnp.maximum(nb, 1), 0.0)
    m2_b = tree_sum(((x.astype(jnp.float32) - mean_b) * m) ** 2)
    return merge(acc, Welford(n=nb, mean=mean_b, m2=m2_b))


def merge(a: Welford, b: Welford) -> Welford:
    n = a.n + b.n
    safe = jnp.maximum(n, 1.0)
    d = b.mean - a.mean
    mean = a.mean + d * (b.n / safe)
    m2 = a.m2 + b.m2 + d * d * (a.n * b.n / safe)
    return Welford(n=n, mean=jnp.where(n > 0, mean, 0.0), m2=m2)


def merge_over_axis(acc: Welford, axis: str) -> Welford:
    """Tree-merge accumulators across a mesh axis inside shard_map.

    Exact merge of (n, mean, m2) via psum identities:
      N = Σn;  MEAN = Σ(n·mean)/N;  M2 = Σ m2 + Σ n·mean² − N·MEAN²
    (algebraically identical to pairwise Chan merges, but one psum.)
    """
    n = jax.lax.psum(acc.n, axis)
    s1 = jax.lax.psum(acc.n * acc.mean, axis)
    s2 = jax.lax.psum(acc.m2 + acc.n * acc.mean * acc.mean, axis)
    safe = jnp.maximum(n, 1.0)
    mean = s1 / safe
    m2 = s2 - n * mean * mean
    return Welford(n=n, mean=jnp.where(n > 0, mean, 0.0),
                   m2=jnp.maximum(m2, 0.0))


# ---------------------------------------------------------------- blocks
# The sharded farm pins the statistics merge tree to a fixed number of
# *virtual blocks* (Partitioning.stat_blocks) so the reduced records
# depend only on the block partition — never on how many physical
# shards computed them. Each block is a contiguous run of instances;
# a shard owns blocks/n_shards of them; the cross-shard wire format is
# ONE psum of the (blocks, ...) partial-accumulator stack (zeros are
# exact additive identities, so the gathered stack is bitwise identical
# to the unsharded one), and the final merge over the block axis is the
# same jitted fold everywhere. Every float sum is a `tree_sum`, whose
# order no compiler chooses: a block's partial has the same bits in the
# fused sharded program as in the jitted fold on one device.


@functools.partial(jax.jit, static_argnames="n_blocks")
def blocked_welford(obs, n_blocks: int) -> Welford:
    """Per-block Welford partials: obs (I, ...) -> leaves (V, ...).

    Block b covers the contiguous instance rows [b*I/V, (b+1)*I/V)."""
    xb = obs.reshape((n_blocks, obs.shape[0] // n_blocks) + obs.shape[1:])
    return jax.vmap(
        lambda x: update_batch(init_welford(x.shape[1:]), x))(xb)


@jax.jit
def merge_blocks(acc: Welford) -> Welford:
    """Canonical merge of a (V, ...) stack of block accumulators.

    Same psum identities as `merge_over_axis`, but as `tree_sum`s over
    the leading block axis, so every path (sharded or not) folds the
    identical stack with the identical tree. V == 1 returns
    the single block unchanged (bitwise — the unblocked legacy path)."""
    if acc.n.shape[0] == 1:
        return Welford(*(a[0] for a in acc))
    n = tree_sum(acc.n)
    s1 = tree_sum(acc.n * acc.mean)
    s2 = tree_sum(acc.m2 + acc.n * acc.mean * acc.mean)
    safe = jnp.maximum(n, 1.0)
    mean = s1 / safe
    m2 = s2 - n * mean * mean
    return Welford(n=n, mean=jnp.where(n > 0, mean, 0.0),
                   m2=jnp.maximum(m2, 0.0))


def gather_blocks_over_axis(acc: Welford, axis: str,
                            n_shards: int) -> Welford:
    """Assemble the full (V, ...) block stack across a mesh axis with a
    single psum — the sharded farm's wire format.

    Each shard scatters its local (V/K, ...) partials into its rows of
    a zeroed (3, V, ...) buffer; the psum tree then moves exactly
    O(V x n_obs) floats per window, and because every position sums one
    value plus K-1 exact zeros, the gathered stack is bit-identical to
    the stack an unsharded run computes directly."""
    v_loc = acc.n.shape[0]
    v_total = v_loc * n_shards
    k = jax.lax.axis_index(axis)
    stacked = jnp.stack([acc.n, acc.mean, acc.m2])  # (3, V/K, ...)
    buf = jnp.zeros((3, v_total) + acc.n.shape[1:], jnp.float32)
    buf = jax.lax.dynamic_update_slice_in_dim(buf, stacked, k * v_loc,
                                              axis=1)
    full = jax.lax.psum(buf, axis)
    return Welford(n=full[0], mean=full[1], m2=full[2])


class Stats(NamedTuple):
    n: jax.Array
    mean: jax.Array
    var: jax.Array
    ci90: jax.Array  # half-width of the 90% confidence interval


@jax.jit
def finalize(acc: Welford) -> Stats:
    var = acc.m2 / jnp.maximum(acc.n - 1.0, 1.0)
    sem = jnp.sqrt(var / jnp.maximum(acc.n, 1.0))
    return Stats(n=acc.n, mean=acc.mean, var=var, ci90=Z90 * sem)


def grouped_stats(obs, group_ids, n_groups: int) -> Stats:
    """Per-group statistics over the instance axis (sweep points).

    obs: (I, n_obs) one window's samples; group_ids: (I,) int32 group of
    each instance; n_groups static. Returns Stats with (n_groups, n_obs)
    leaves — the per-sweep-point reduction of paper §3.1.2, still one
    masked Welford fold per group so it composes with merge_over_axis.
    """
    def one(g):
        return update_batch(init_welford(obs.shape[1:]), obs,
                            mask=group_ids == g)

    return finalize(jax.vmap(one)(jnp.arange(n_groups)))


def blocked_stats(obs, n_blocks: int = 1) -> Stats:
    """Window statistics under the fixed `n_blocks` merge tree: the
    block partials, their merge and the finalisation, each one jitted
    call, so a sharded run folds its gathered partials with the same
    programs an unsharded run uses.

    n_blocks == 1 is the single update_batch fold (merge_blocks passes
    the one block through); n_blocks > 1 reduces per-block partials
    with `merge_blocks` — the form whose result is invariant to
    sharding over any shard count dividing n_blocks."""
    return finalize(merge_blocks(blocked_welford(obs, n_blocks)))


def blocked_grouped_welford(obs, group_ids, n_groups: int,
                            n_blocks: int) -> Welford:
    """Per-(block, group) masked partials: leaves (V, n_groups, ...)."""
    bs = obs.shape[0] // n_blocks
    xb = obs.reshape((n_blocks, bs) + obs.shape[1:])
    gb = group_ids.reshape(n_blocks, bs)

    def one_block(x, g):
        def one_group(gid):
            return update_batch(init_welford(x.shape[1:]), x,
                                mask=g == gid)

        return jax.vmap(one_group)(jnp.arange(n_groups))

    return jax.vmap(one_block)(xb, gb)
