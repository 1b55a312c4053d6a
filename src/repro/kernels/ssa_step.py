"""Pallas TPU kernel: fused multi-step SSA window, RNG in VREGs.

The flagship hardware adaptation (DESIGN.md §2/§4): the paper found the
single SSA step too fine-grained for any inter-core parallelism and
nearly SIMD-proof *within* one instance. Here the ENTIRE Monte Carlo
inner loop runs inside one kernel with the lane state (X, t) resident
in VMEM across `n_steps` iterations:

  per step (all in VMEM / VREGs):
    Match   — A = k · Π C(X[idx_m], coef_m)
    Resolve — u1, u2 = threefry2x32(key, ctr) (VREG counter-based draw)
              tau = -ln(u1)/a0;  reaction j from inverse-CDF on the
              shifted-add prefix sum (`gillespie.prefix_sum`)
    Update  — X += delta[j]

Layout: the dense exact kernel (`ssa_window_call`) has two bodies, and
`kernel_layout` picks one from the network's shape; both give the same
bits as the unfused step.
- "lanes", for networks of at most LANES_MAX species and reactions
  (the CWC models, `lv2` to `lv8`): replicas fill the 128-wide lane
  axis, so every per-lane value is a whole (rows, 128) tile and the
  network is unrolled in Python into scalar x tile VPU ops — the
  tables (reactant index, coefficients, delta) and shared rates are
  SMEM scalars, Match reads the population row idx[m, r] of a VMEM
  scratch at a dynamic index, Update is a select chain. No MXU, no
  padding of the network to 128 lanes.
- "columns", for larger networks (`ring8` up, whose unrolled body would
  take seconds to lower on every engine build): lane blocks of (BL, S)
  and (BL, R) tiles, per-lane scalars in (BL, 1) columns, Match as
  one-hot matmuls and Update as a one-hot x delta matmul on the MXU at
  Precision.HIGHEST (populations above 256 must not be rounded to
  bf16). Per-lane scalars are columns, never 1-D (BL,) vectors: Mosaic
  cannot broadcast a 1-D lane vector against the (BL, R) tiles.
The tau and sparse window kernels have the column body only.

HBM traffic per window: X/t/flags/key/ctr once each way — nothing that
scales with the step count. There is NO uniform-stream operand: the
uniforms are generated in-register from the per-lane (key, ctr) stream
(`core/stream.counter_uniforms`), which is the memory-wall guideline
(§3.2.3/3.1.2) applied to the HBM↔VMEM boundary.

Because the draw is a pure function of (lane key, event counter), the
kernel consumes the IDENTICAL stream as the unfused
`gillespie.ssa_step` — trajectories are bitwise equal for ANY chunk
size, across window boundaries, and across shard counts (tested).

Grid: lane blocks only (reactions stay whole in VMEM — CWC systems are
small-R; an R-tiled variant would add a cross-tile argmin, not needed
here). The call wrappers take and return (B,) per-lane arrays and
reshape them around the `pallas_call`: to (B, 1) columns, or to
(B/128, 128) tiles for the lane body.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gillespie import (
    LaneState,
    choose_reaction,
    prefix_sum,
    resolve_carry,
    sparse_ssa_step,
)
from repro.core.reactions import MAX_COEF, MAX_REACTANTS
from repro.core.stream import counter_uniforms, ctr_add
from repro.core.tau_leap import tau_step_core
from repro.kernels.propensity import _comb_factors, resolve_interpret

LANE_BLK = 256
#: the TPU's vector lane width: the lane body's minor axis
LANES = 128
#: most sublane rows of the lane body's tiles: up to 32 x 128 replicas
#: per grid step (`_lane_rows`)
LANE_ROWS = 32
#: largest species or reaction count the dense exact kernel runs in its
#: lane body (`kernel_layout`)
LANES_MAX = 16
_HIGHEST = jax.lax.Precision.HIGHEST
#: scoped VMEM of the tau kernel (a v5e core has 128 MiB). Its (M, S,
#: R) one-hots and (R, S) delta sit whole in VMEM, double-buffered,
#: beside the lane block's tiles: the default budget already overflows
#: on the 120-cell ring (S=480, R=840)
TAU_VMEM_LIMIT = 100 * 2**20
#: largest one-hot + delta tables the compiled tau kernel is given:
#: within TAU_VMEM_LIMIT the 240-cell ring's 32.3 MB (S=960, R=1680)
#: compile for a v5e, the 320-cell ring's 57.3 MB do not
TAU_TABLE_BYTES = 32 * 2**20


def _lane_block(b: int, width: int) -> int:
    """Lanes per grid step: LANE_BLK for rows up to 128 wide, fewer for
    wider (B, R) / (B, S) tiles so a block's working set stays that of
    a (LANE_BLK, 128) tile — the tau kernel keeps a few dozen such
    tiles live, which for the 80-cell ring (R=560) at 256 lanes
    overflows the v5e's default VMEM budget."""
    bl = LANE_BLK
    while bl > 8 and bl * max(width, 128) > LANE_BLK * 128:
        bl //= 2
    return min(bl, b)


def kernel_layout(s: int, r: int) -> str:
    """Body of the dense exact kernel for a network of S species and R
    reactions: "lanes" (replicas on the lane axis, the network unrolled
    into scalar x tile ops) up to LANES_MAX of each, else "columns"
    ((BL, S) and (BL, R) tiles, Match and Update on the MXU)."""
    return "lanes" if max(s, r) <= LANES_MAX else "columns"


def _lane_rows(b: int) -> int:
    """Sublane rows per grid step of the lane body for b lanes: the
    most, up to LANE_ROWS, whose blocks the lanes padded to a whole
    (8, 128) tile count fill, so that padding stays under 1,024 lanes
    (16,384 lanes run 4 blocks of 32 rows; 2,065 run 3 of 8)."""
    tiles = pl.cdiv(b, 8 * LANES)
    rows = LANE_ROWS
    while rows > 8 and tiles % (rows // 8):
        rows //= 2
    return rows


def _col(bl: int):
    """BlockSpec of a (B, 1) per-lane column."""
    return pl.BlockSpec((bl, 1), lambda i: (i, 0))


def _whole(shape):
    """BlockSpec of an operand every lane block reads whole."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _cols(*arrays):
    """(B,) per-lane arrays -> (B, 1) columns for the kernels."""
    return [a.reshape(-1, 1) for a in arrays]


def _horizon(horizon):
    return jnp.asarray(horizon, jnp.float32).reshape(1, 1)


def _window_kernel(x_ref, t_ref, dead_ref, key_ref, ctr_ref, ctrhi_ref,
                   e_ref, coef_ref, delta_ref, rates_ref, horizon_ref,
                   x_out, t_out, dead_out, steps_out, ctr_out, ctrhi_out,
                   n_steps: int):
    x = x_ref[...].astype(jnp.float32)  # (BL, S)
    t = t_ref[...]  # (BL, 1)
    k0 = key_ref[:, 0:1]  # (BL, 1) uint32 — stream key, read once
    k1 = key_ref[:, 1:2]
    horizon = horizon_ref[...]  # (1, 1)
    steps = jnp.zeros_like(t, jnp.int32)

    # `dead` rides the loop as int32: Mosaic cannot carry bool vectors
    def step(i, carry):
        x, t, dead, steps, ctr, ctr_hi = carry
        dead = dead > 0
        active = (t < horizon) & ~dead
        # --- Match (MXU) ---
        a = rates_ref[...]
        for m in range(MAX_REACTANTS):
            pops = jax.lax.dot(x, e_ref[m], precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
            a = a * _comb_factors(pops, coef_ref[m:m + 1, :])
        cum = prefix_sum(a)
        a0 = cum[:, -1:]
        now_dead = a0 <= 0.0
        # --- Resolve (counter-based draw, VREGs only) ---
        u1, u2 = counter_uniforms(k0, k1, ctr, ctr_hi)
        tau = -jnp.log(u1) / jnp.maximum(a0, 1e-30)
        t_next = t + tau
        fire = active & ~now_dead & (t_next <= horizon)
        lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        onehot = jnp.where(
            fire & (lane == choose_reaction(a, cum, u2 * a0)), 1.0, 0.0)
        # --- Update (MXU) ---
        x = x + jax.lax.dot(onehot, delta_ref[...], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
        t = jnp.where(fire, t_next, jnp.where(active, horizon, t))
        dead = (dead | (active & now_dead)).astype(jnp.int32)
        steps = steps + fire.astype(jnp.int32)
        ctr, ctr_hi = ctr_add(ctr, ctr_hi, active.astype(jnp.uint32))
        return x, t, dead, steps, ctr, ctr_hi

    x, t, dead, steps, ctr, ctr_hi = jax.lax.fori_loop(
        0, n_steps, step,
        (x, t, dead_ref[...], steps, ctr_ref[...], ctrhi_ref[...]))
    x_out[...] = x
    t_out[...] = t
    dead_out[...] = dead
    steps_out[...] = steps
    ctr_out[...] = ctr
    ctrhi_out[...] = ctr_hi


def tau_table_bytes(s: int, r: int) -> int:
    """Bytes of the tables the tau kernel holds whole in VMEM: the
    (M, S, R) reactant one-hots and the (R, S) delta, f32."""
    return 4 * (MAX_REACTANTS + 1) * s * r


def check_tau_tables_fit(s: int, r: int, backend: str | None = None):
    """Refuse (ValueError) a compiled tau kernel whose tables exceed
    TAU_TABLE_BYTES — Mosaic would fail at the first window. The
    interpreter has no such bound. `backend` as in `resolve_interpret`.
    """
    need = tau_table_bytes(s, r)
    if need > TAU_TABLE_BYTES and not resolve_interpret(None, backend):
        raise ValueError(
            "use_kernel=True with method=tau_leap holds the (M, S, R) "
            f"reactant one-hots and the (R, S) delta whole in VMEM: "
            f"{need} bytes for S={s}, R={r}, past the tau kernel's "
            f"{TAU_TABLE_BYTES}-byte limit; run this network with "
            "use_kernel=False, or as method=exact with sparse=True (the "
            "sparse exact kernel holds only the packed recipe rows)")


def _lane_outs(b: int, s: int, dtypes):
    """out_shape list: x (B, S) f32, then one (B, 1) column per dtype."""
    return ([jax.ShapeDtypeStruct((b, s), jnp.float32)]
            + [jax.ShapeDtypeStruct((b, 1), dt) for dt in dtypes])


def ssa_window_columns(x, t, dead, key, ctr, ctr_hi, e, coef, delta,
                       rates, horizon, *, n_steps: int, interpret: bool):
    """The column body of `ssa_window_call`: lane blocks of (BL, S) and
    (BL, R) tiles, per-lane scalars in (BL, 1) columns, Match and
    Update on the MXU. Arguments and results as `ssa_window_call`."""
    b, s = x.shape
    r = delta.shape[0]
    if rates.ndim == 1:
        rates = jnp.broadcast_to(rates, (b, r))
    bl = _lane_block(b, max(r, s))
    col = _col(bl)
    outs = pl.pallas_call(
        partial(_window_kernel, n_steps=n_steps),
        grid=(pl.cdiv(b, bl),),
        in_specs=[
            pl.BlockSpec((bl, s), lambda i: (i, 0)),
            col, col,
            pl.BlockSpec((bl, 2), lambda i: (i, 0)),
            col, col,
            _whole((MAX_REACTANTS, s, r)),
            _whole((MAX_REACTANTS, r)),
            _whole((r, s)),
            pl.BlockSpec((bl, r), lambda i: (i, 0)),
            _whole((1, 1)),
        ],
        out_specs=[pl.BlockSpec((bl, s), lambda i: (i, 0))] + [col] * 5,
        out_shape=_lane_outs(b, s, (jnp.float32, jnp.int32, jnp.int32,
                                    jnp.uint32, jnp.uint32)),
        interpret=interpret,
    )(x, *_cols(t, dead), key, *_cols(ctr, ctr_hi), e, coef, delta, rates,
      _horizon(horizon))
    return (outs[0],) + tuple(o[:, 0] for o in outs[1:])


def _comb_scalar(pops, c, fact, zero):
    """`comb_factors` of a (rows, 128) population tile for one reaction
    slot whose coefficient `c` (int32, the float one rounded up) and
    `fact` = c! are scalars: the same multiplies and the same division
    as the (BL, R) form, so the same bits. At i = 0 the running product
    1 · max(pops - 0, 0) is max(pops, 0) exactly. `zero` is a zero tile;
    `lax.select` on a scalar predicate keeps the traced body small."""
    ff = lax.select(c > 0, lax.max(pops, zero), zero + 1.0)
    for i in range(1, MAX_COEF):
        ff = lax.select(c > i, ff * lax.max(pops - float(i), zero), ff)
    return ff / fact


def _lanes_kernel(x_ref, t_ref, dead_ref, key_ref, ctr_ref, ctrhi_ref,
                  rates_ref, idx_ref, coef_ref, fact_ref, delta_ref,
                  horizon_ref, x_out, t_out, dead_out, steps_out, ctr_out,
                  ctrhi_out, pop_ref, *, n_steps: int, s: int, r: int):
    """The lane body: replicas on the 128-wide lane axis, so every
    per-lane value is a full (rows, 128) tile. Populations live in the
    VMEM scratch `pop_ref` (S + 1, rows, 128), row S held at zero for
    empty reactant slots; Match reads row idx[m, r] at a dynamic
    leading index from the SMEM table. The network's tables and shared
    rates are SMEM scalars; rates_ref is (R, rows, 128) for per-lane
    rates, else (R,) in SMEM, and `rates_ref[j]` serves both. Every
    loop over reactions, species and reactant slots is unrolled."""
    shape = t_ref.shape
    zero = jnp.zeros(shape, jnp.float32)
    horizon = horizon_ref[0]
    pop_ref[s] = zero
    for sp in range(s):
        pop_ref[sp] = x_ref[sp].astype(jnp.float32)

    def step(i, carry):
        t, dead, steps, ctr, ctr_hi = carry
        active = (t < horizon) & (dead == 0)
        # --- Match: a_j = k_j · Π_m C(x[idx[m, j]], coef[m, j]) ---
        a = []
        for j in range(r):
            aj = rates_ref[j]
            for m in range(MAX_REACTANTS):
                k = m * r + j
                aj = aj * _comb_scalar(pop_ref[idx_ref[k]], coef_ref[k],
                                       fact_ref[k], zero)
            a.append(aj)
        # `prefix_sum`'s Hillis-Steele pairs, level by level
        cum, k = a, 1
        while k < r:
            cum = [c + cum[n - k] if n >= k else c
                   for n, c in enumerate(cum)]
            k *= 2
        a0 = cum[-1]
        now_dead = a0 <= 0.0
        # --- Resolve ---
        u1, u2 = counter_uniforms(key_ref[0], key_ref[1], ctr, ctr_hi)
        tau = -jnp.log(u1) / jnp.maximum(a0, 1e-30)
        t_next = t + tau
        fire = active & ~now_dead & (t_next <= horizon)
        # `choose_reaction`: the first live reaction whose sum reaches
        # u2 · a0, else the last live one (the first met going down);
        # R where none fires
        thresh = u2 * a0
        none = first = last = jnp.full(shape, r, jnp.int32)
        for j in reversed(range(r)):
            live = a[j] > 0.0
            jj = jnp.full(shape, j, jnp.int32)
            first = lax.select(live & (cum[j] >= thresh), jj, first)
            last = lax.select(live & (last == r), jj, last)
        pick = lax.select(fire, lax.select(first < r, first, last), none)
        # --- Update: x_s += delta[pick, s] ---
        hit = [pick == j for j in range(r)]
        for sp in range(s):
            dx = zero
            for j in range(r):
                dx = lax.select(hit[j], zero + delta_ref[j * s + sp], dx)
            pop_ref[sp] = pop_ref[sp] + dx
        t = lax.select(fire, t_next, lax.select(active, zero + horizon, t))
        dead = ((dead > 0) | (active & now_dead)).astype(jnp.int32)
        steps = steps + fire.astype(jnp.int32)
        ctr, ctr_hi = ctr_add(ctr, ctr_hi, active.astype(jnp.uint32))
        return t, dead, steps, ctr, ctr_hi

    t, dead, steps, ctr, ctr_hi = jax.lax.fori_loop(
        0, n_steps, step,
        (t_ref[...], dead_ref[...], jnp.zeros(shape, jnp.int32),
         ctr_ref[...], ctrhi_ref[...]))
    for sp in range(s):
        x_out[sp] = pop_ref[sp]
    t_out[...] = t
    dead_out[...] = dead
    steps_out[...] = steps
    ctr_out[...] = ctr
    ctrhi_out[...] = ctr_hi


def ssa_window_lanes(x, t, dead, key, ctr, ctr_hi, e, coef, delta, rates,
                     horizon, *, n_steps: int, interpret: bool):
    """The lane body of `ssa_window_call`: (B,) per-lane arrays become
    (B/128, 128) tiles, x (S, B/128, 128), key (2, ...) and per-lane
    rates (R, ...); `_lane_rows` x 128 replicas per grid step. Lanes
    past a whole block are padded as lanes already at the horizon and
    cut off again. Arguments and results as `ssa_window_call`; `e` is
    a one-hot (at most one 1 per reactant slot), read as a species
    index."""
    b, s = x.shape
    r = delta.shape[0]
    rows = _lane_rows(b)
    blk = rows * LANES
    nb = pl.cdiv(b, blk) * blk
    horizon = jnp.asarray(horizon, jnp.float32)

    def tiles(a, fill=0):
        """(B, ...) -> (..., nb/128, 128), padded with `fill`."""
        if nb > b:
            a = jnp.concatenate(
                [a, jnp.full((nb - b,) + a.shape[1:], fill, a.dtype)])
        return jnp.moveaxis(a, 0, -1).reshape(a.shape[1:] + (-1, LANES))

    hit = e > 0  # (M, S, R)
    idx = jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1), s)
    fact = jnp.ones_like(coef)
    for i in range(MAX_COEF):
        fact = jnp.where(coef > i, fact * (i + 1), fact)
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def stack(n):
        return pl.BlockSpec((n, rows, LANES), lambda i: (0, i, 0))

    per_lane = rates.ndim == 2
    outs = pl.pallas_call(
        partial(_lanes_kernel, n_steps=n_steps, s=s, r=r),
        grid=(nb // blk,),
        in_specs=[stack(s), tile, tile, stack(2), tile, tile,
                  stack(r) if per_lane else smem,
                  smem, smem, smem, smem, smem],
        out_specs=[stack(s)] + [tile] * 5,
        out_shape=[jax.ShapeDtypeStruct((s, nb // LANES, LANES),
                                        jnp.float32)]
        + [jax.ShapeDtypeStruct((nb // LANES, LANES), dt)
           for dt in (jnp.float32, jnp.int32, jnp.int32, jnp.uint32,
                      jnp.uint32)],
        scratch_shapes=[pltpu.VMEM((s + 1, rows, LANES), jnp.float32)],
        interpret=interpret,
    )(tiles(x), tiles(t, horizon), tiles(dead), tiles(key), tiles(ctr),
      tiles(ctr_hi), tiles(rates) if per_lane else rates,
      idx.astype(jnp.int32).reshape(-1),
      jnp.ceil(coef).astype(jnp.int32).reshape(-1), fact.reshape(-1),
      delta.reshape(-1), horizon.reshape(1))
    x = outs[0].reshape(s, nb)[:, :b].T
    return (x,) + tuple(o.reshape(nb)[:b] for o in outs[1:])


@partial(jax.jit, static_argnames=("n_steps", "interpret"))
def ssa_window_call(x, t, dead, key, ctr, ctr_hi, e, coef, delta, rates,
                    horizon, *, n_steps: int,
                    interpret: bool | None = None):
    """Run up to n_steps fused SSA events per lane toward `horizon`.

    x: (B,S) f32; t: (B,) f32; dead: (B,) int32; key: (B,2) uint32;
    ctr/ctr_hi: (B,) uint32; e: (M,S,R); coef: (M,R) f32;
    delta: (R,S) f32; rates: (B,R) or (R,).
    `interpret=None` auto-selects the compiled kernel on TPU/GPU.
    The body is the one `kernel_layout` names for the network's shape;
    both give the same bits.
    Returns (x, t, dead, steps_taken, ctr, ctr_hi), per-lane as (B,).
    """
    body = (ssa_window_lanes
            if kernel_layout(x.shape[1], delta.shape[0]) == "lanes"
            else ssa_window_columns)
    return body(x, t, dead, key, ctr, ctr_hi, e, coef, delta, rates,
                horizon, n_steps=n_steps,
                interpret=resolve_interpret(interpret))


def _tau_window_kernel(x_ref, t_ref, dead_ref, noleap_ref, key_ref,
                       ctr_ref, ctrhi_ref, e_ref, coef_ref, delta_ref,
                       rates_ref, gi_ref, rmask_ref, horizon_ref,
                       x_out, t_out, dead_out, steps_out, leaps_out,
                       ctr_out, ctrhi_out,
                       n_steps: int, eps: float, fallback: float,
                       max_c: int):
    """Fused multi-step tau-leap window: the SAME `tau_step_core` the
    host paths trace, iterated with the lane state resident in VMEM —
    propensity/moment/update matmuls on the MXU, Poisson
    inverse-transform and counter-based draws in VREGs.

    noleap_ref: (BL, 1) int32 — nonzero lanes take exact SSA steps only
    (steering's per-lane exact<->tau switch): their effective fallback
    threshold is +inf, computed once in VREGs; the static scalar
    `fallback` stays a jit-time constant for everyone else."""
    fb = jnp.where(noleap_ref[...] > 0, jnp.float32(jnp.inf),
                   jnp.float32(fallback))
    k0 = key_ref[:, 0:1]
    k1 = key_ref[:, 1:2]
    horizon = horizon_ref[...]
    t = t_ref[...]
    zeros = jnp.zeros_like(t, jnp.int32)

    def step(i, carry):
        x, t, dead, ctr, ctr_hi, steps, leaps = carry
        x, t, dead, ctr, ctr_hi, steps, leaps = tau_step_core(
            x, t, dead > 0, k0, k1, ctr, ctr_hi, steps, leaps,
            e_ref[...], coef_ref[...], delta_ref[...], rates_ref[...],
            gi_ref[...], rmask_ref[...], horizon,
            eps=eps, fallback=fb, max_c=max_c)
        return x, t, dead.astype(jnp.int32), ctr, ctr_hi, steps, leaps

    x, t, dead, ctr, ctr_hi, steps, leaps = jax.lax.fori_loop(
        0, n_steps, step,
        (x_ref[...].astype(jnp.float32), t, dead_ref[...], ctr_ref[...],
         ctrhi_ref[...], zeros, zeros))
    x_out[...] = x
    t_out[...] = t
    dead_out[...] = dead
    steps_out[...] = steps
    leaps_out[...] = leaps
    ctr_out[...] = ctr
    ctrhi_out[...] = ctr_hi


@partial(jax.jit, static_argnames=("n_steps", "interpret", "eps",
                                   "fallback", "max_c"))
def tau_window_call(x, t, dead, no_leap, key, ctr, ctr_hi, e, coef,
                    delta, rates, gi, rmask, horizon, *, n_steps: int,
                    eps: float, fallback: float, max_c: int = MAX_COEF,
                    interpret: bool | None = None):
    """Run up to n_steps fused tau-leap iterations per lane toward
    `horizon`. Shapes as `ssa_window_call` plus no_leap (B,) int32
    (nonzero = lane forced to exact SSA — steering's per-lane method
    switch), gi (>=MAX_COEF,S) and rmask (S,) from
    `core.tau_leap.gi_tables`/`reactant_mask`. `max_c` is the comb
    unroll bound: MAX_COEF for dense systems, the system's max
    coefficient on the sparse seam (whose host path Matches by gather;
    the one-hot Match here gives the same bits).
    `interpret=None` auto-selects the compiled kernel on TPU/GPU.
    Returns (x, t, dead, steps_delta, leaps_delta, ctr, ctr_hi)."""
    interpret = resolve_interpret(interpret)
    b, s = x.shape
    r = delta.shape[0]
    if rates.ndim == 1:
        rates = jnp.broadcast_to(rates, (b, r))
    bl = _lane_block(b, max(r, s))
    col = _col(bl)
    m = e.shape[0]
    outs = pl.pallas_call(
        partial(_tau_window_kernel, n_steps=n_steps, eps=eps,
                fallback=fallback, max_c=max_c),
        grid=(pl.cdiv(b, bl),),
        in_specs=[
            pl.BlockSpec((bl, s), lambda i: (i, 0)),
            col, col, col,
            pl.BlockSpec((bl, 2), lambda i: (i, 0)),
            col, col,
            _whole((m, s, r)),
            _whole((m, r)),
            _whole((r, s)),
            pl.BlockSpec((bl, r), lambda i: (i, 0)),
            _whole(gi.shape),
            _whole((1, s)),
            _whole((1, 1)),
        ],
        out_specs=[pl.BlockSpec((bl, s), lambda i: (i, 0))] + [col] * 6,
        out_shape=_lane_outs(b, s, (jnp.float32, jnp.int32, jnp.int32,
                                    jnp.int32, jnp.uint32, jnp.uint32)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=TAU_VMEM_LIMIT),
        interpret=interpret,
    )(x, *_cols(t, dead, no_leap), key, *_cols(ctr, ctr_hi), e, coef,
      delta, rates, gi, rmask.reshape(1, s), _horizon(horizon))
    return (outs[0],) + tuple(o[:, 0] for o in outs[1:])


def _sparse_window_kernel(*refs, n_steps: int, max_c: int, d: int, k: int,
                          m: int, per_lane_rates: bool):
    """Fused multi-step SPARSE exact-SSA window.

    VMEM holds only the O(R·(M+K+D)) packed recipe rows
    (`gillespie.bind_sparse_step`) — no (M, S, R) one-hots and no
    (R, S) dense delta. The per-step body is the SAME
    `gillespie.sparse_ssa_step` the host paths trace, in its
    `kernel_form` (one-hot-matmul row gathers, masked population
    gathers/scatters). The carried (BL, R) propensity vector is seeded
    per launch from `a_ref`, the dense evaluation at the launch's x —
    a pure function of x, so chunk boundaries cannot change bits.
    `per_lane_rates`: sweep rates arrive as a (BL, R+1) operand and are
    selected per event; otherwise they live inside the float rows."""
    if per_lane_rates:
        (x_ref, t_ref, dead_ref, key_ref, ctr_ref, ctrhi_ref, a_ref,
         itab_ref, ftab_ref, rates_ref, horizon_ref, *outs) = refs
        rates2d = rates_ref[...]
    else:
        (x_ref, t_ref, dead_ref, key_ref, ctr_ref, ctrhi_ref, a_ref,
         itab_ref, ftab_ref, horizon_ref, *outs) = refs
        rates2d = None
    x_out, t_out, dead_out, steps_out, ctr_out, ctrhi_out = outs
    t = t_ref[...]
    zeros = jnp.zeros_like(t, jnp.int32)
    # dead/no_leap ride the loop as int32: Mosaic cannot carry bool
    # vectors
    state = LaneState(x=x_ref[...].astype(jnp.float32), t=t,
                      key=key_ref[...], ctr=ctr_ref[...],
                      ctr_hi=ctrhi_ref[...], steps=zeros, leaps=zeros,
                      dead=dead_ref[...], no_leap=zeros)
    bound = (itab_ref[...], ftab_ref[...], rates2d, max_c, d, k, m)
    horizon = horizon_ref[...]

    def step(i, carry):
        st, aci = carry
        st, aci = sparse_ssa_step(
            st._replace(dead=st.dead > 0, no_leap=st.no_leap > 0), aci,
            bound, horizon, kernel_form=True)
        return st._replace(dead=st.dead.astype(jnp.int32),
                           no_leap=zeros), aci

    state, _ = jax.lax.fori_loop(0, n_steps, step,
                                 (state, resolve_carry(a_ref[...])))
    x_out[...] = state.x
    t_out[...] = state.t
    dead_out[...] = state.dead
    steps_out[...] = state.steps  # started at 0: already the delta
    ctr_out[...] = state.ctr
    ctrhi_out[...] = state.ctr_hi


@partial(jax.jit, static_argnames=("n_steps", "max_c", "d", "k", "m",
                                   "interpret"))
def sparse_window_call(x, t, dead, key, ctr, ctr_hi, a, int_tab, flt_tab,
                       rates2d, horizon, *, n_steps: int, max_c: int,
                       d: int, k: int, m: int,
                       interpret: bool | None = None):
    """Run up to n_steps sparse SSA events per lane toward `horizon`.

    a: (B, R) propensities at x (`gillespie.initial_propensities`);
    int_tab (R+1, D+K+K·M) and flt_tab (R+1, D+K·M[+K]) with
    (rates2d, max_c, d, k, m) from `gillespie.bind_sparse_step`;
    rates2d: (B, R+1) sweep rates, or None when the rates are packed
    into flt_tab. Returns (x, t, dead, steps_delta, ctr, ctr_hi) —
    bitwise identical to iterating the host `sparse_ssa_step`, which is
    itself bitwise identical to the dense path.
    """
    interpret = resolve_interpret(interpret)
    b, s = x.shape
    r = a.shape[1]
    bl = _lane_block(b, max(r, s))
    col = _col(bl)
    in_specs = [
        pl.BlockSpec((bl, s), lambda i: (i, 0)),
        col, col,
        pl.BlockSpec((bl, 2), lambda i: (i, 0)),
        col, col,
        pl.BlockSpec((bl, r), lambda i: (i, 0)),
        _whole(int_tab.shape),
        _whole(flt_tab.shape),
    ]
    extra = []
    if rates2d is not None:
        in_specs.append(pl.BlockSpec((bl, r + 1), lambda i: (i, 0)))
        extra.append(rates2d)
    in_specs.append(_whole((1, 1)))
    outs = pl.pallas_call(
        partial(_sparse_window_kernel, n_steps=n_steps, max_c=max_c, d=d,
                k=k, m=m, per_lane_rates=rates2d is not None),
        grid=(pl.cdiv(b, bl),),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bl, s), lambda i: (i, 0))] + [col] * 5,
        out_shape=_lane_outs(b, s, (jnp.float32, jnp.int32, jnp.int32,
                                    jnp.uint32, jnp.uint32)),
        interpret=interpret,
    )(x, *_cols(t, dead), key, *_cols(ctr, ctr_hi), a,
      int_tab.astype(jnp.float32), flt_tab, *extra, _horizon(horizon))
    return (outs[0],) + tuple(o[:, 0] for o in outs[1:])
