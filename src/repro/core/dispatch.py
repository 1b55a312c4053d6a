"""Window-dispatch strategies — the engine's execution seam.

Three ways to advance the instance pool one window, selected by
`select_dispatch` (SimulationEngine no longer branches on booleans
inside its window loop):

  host_loop : legacy per-group gather -> advance -> scatter round trips
              (the benchmark baseline);
  fused     : one jitted, donated `window_step` over the whole pool
              (device-side permutation + lax.scan over lane slices);
  sharded   : the same window body wrapped in `jax.shard_map` over a
              mesh data axis — each shard advances its contiguous slice
              of the pool locally, and per-window Welford accumulators
              (plain and grouped) are assembled device-side with ONE
              psum per window (`reduction.gather_blocks_over_axis`),
              so only O(stat_blocks x n_obs) floats ever cross shards;
              the tiny final fold is `reduction.merge_blocks`.

`use_kernel=True` composes with ALL three: the Pallas fused-window
chunk loop is a device-side `lax.while_loop` (kernels/ops.py), so the
fused strategy runs it as its one-dispatch-per-window step, the
sharded strategy runs it per shard under shard_map (the paper's two
families — single-simulation speedup × simulation farm — composed),
and the host loop keeps it per group as the baseline.

The per-lane ALGORITHM is a second, orthogonal seam
(`SimConfig.method` × `SimConfig.sparse`): the unfused bodies take the
engine-built `advance_fn(lane_slice, rates, horizon)`
(`gillespie.make_advance_fn` — exact `gillespie.ssa_step`,
`tau_leap.make_tau_step`, dense or sparse dependency-graph), the
kernel bodies take the engine-built chunk loop (exact or tau, dense or
sparse — `engine._make_chunk_loop`); every strategy × method ×
encoding pairing stays bit-identical per lane.

A third, orthogonal seam is the SUPERSTEP width
(`SimConfig.window_block`): the fused and sharded strategies expose
`advance_block(horizons)`, a `lax.scan` over W window horizons inside
ONE jitted (donated) dispatch — both the unfused window body and the
Pallas kernel chunk loop nest under the scan — accumulating per-window
observables, Welford partials, steps/leaps telemetry, and (kernel
paths) truncation flags into an on-device `(W, ...)` record ring
(`BlockResult`). The engine's async collector pulls a whole ring with
one blocking sync, so dispatches AND host syncs amortise to 1/W per
window (DESIGN.md §3e). The host loop stays per-window by design (it
is the round-trip baseline the superstep is measured against).

All paths are bit-identical per lane (counter-based per-lane RNG,
`core/stream.counter_uniforms`; identical per-lane ops — including
kernel vs unfused, see DESIGN.md §3c). The sharded path additionally
pins the statistics merge tree to `Partitioning.blocks` virtual
blocks, so its StatsRecords are bit-identical for ANY shard count
dividing the block count — including the unsharded fused path
configured with the same `stat_blocks` — which is what makes
checkpoints mesh-shape-agnostic. Supersteps preserve all of it:
the scan body IS the per-window body, and the per-window statistics
are computed by the same ops on the same values, so records are
bitwise identical for ANY `window_block` (window_block=1 runs the
unchanged legacy per-window path).
"""
from __future__ import annotations

import numbers
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import reduction
from repro.core.gillespie import LaneState
from repro.stats.sketch import window_sketch


@dataclass(frozen=True)
class Partitioning:
    """How the instance pool is distributed over a device mesh.

    n_shards: devices along the farm's data axis; each owns the
    contiguous instance block [k*I/K, (k+1)*I/K).
    axis: mesh axis name (the psum/shard_map axis).
    stat_blocks: virtual blocks the per-window statistics reduce over
    (defaults to n_shards). Records depend on this number — never on
    the physical shard count — so pin it when comparing runs across
    mesh shapes or resuming a checkpoint on a different device count.
    """

    n_shards: int = 1
    axis: str = "data"
    stat_blocks: Optional[int] = None

    @property
    def blocks(self) -> int:
        return (self.stat_blocks if self.stat_blocks is not None
                else max(self.n_shards, 1))

    def validate(self, n_instances: int) -> None:
        for name in ("n_shards", "stat_blocks"):
            v = getattr(self, name)
            if v is None and name == "stat_blocks":
                continue
            if isinstance(v, bool) or not isinstance(v, numbers.Integral):
                raise ValueError(
                    f"Partitioning.{name} must be an int, got {v!r}")
        if self.n_shards < 1:
            raise ValueError(
                f"Partitioning.n_shards must be >= 1, got {self.n_shards}")
        if not self.axis or not isinstance(self.axis, str):
            raise ValueError(
                f"Partitioning.axis must be a mesh axis name, "
                f"got {self.axis!r}")
        if n_instances % self.n_shards:
            raise ValueError(
                f"n_instances ({n_instances}) must divide evenly over "
                f"Partitioning.n_shards ({self.n_shards})")
        v = self.blocks
        if v < 1:
            raise ValueError(
                f"Partitioning.stat_blocks must be >= 1, got {v}")
        if v % self.n_shards:
            raise ValueError(
                f"Partitioning.stat_blocks ({v}) must be a multiple of "
                f"n_shards ({self.n_shards}) so each shard owns whole "
                "blocks")
        if n_instances % v:
            raise ValueError(
                f"n_instances ({n_instances}) must divide evenly into "
                f"Partitioning.stat_blocks ({v}) blocks")

    def degrade(self, n_instances: int, n_lost: int = 1) -> "Partitioning":
        """Elastic shard-loss degradation: the largest valid shard
        count d <= n_shards - n_lost — d must divide both n_instances
        and the stat-block count (d=1 always qualifies, so any loss
        short of every device degrades rather than dies).

        stat_blocks is PINNED to the ORIGINAL partitioning's block
        count: records depend on stat_blocks, never on the physical
        shard count, so a run resumed on the survivors stays bitwise
        identical to the uninterrupted one (the PR 2
        reshard-on-restore contract, now exercised by fault recovery).
        """
        if n_lost < 1:
            raise ValueError(f"n_lost must be >= 1, got {n_lost}")
        if n_lost >= self.n_shards:
            raise ValueError(
                f"cannot degrade: lost {n_lost} of {self.n_shards} "
                "shards (no survivors)")
        blocks = self.blocks
        target = self.n_shards - n_lost
        d = next(d for d in range(target, 0, -1)
                 if n_instances % d == 0 and blocks % d == 0)
        out = Partitioning(n_shards=d, axis=self.axis, stat_blocks=blocks)
        out.validate(n_instances)
        return out


class WindowResult(NamedTuple):
    """What one dispatched window hands back to the engine.

    obs: (I, n_obs) window samples (device array; sharded under the
    sharded strategy — only pulled when trajectories are buffered).
    steps_delta: per-instance events this window (None on the host path
    unless the predictive policy asked for it).
    stats / grouped: per-window Stats already reduced device-side
    (sharded strategy), or None when the engine should compute them
    from `obs`.
    truncated: device bool/int scalar on the kernel path — nonzero iff
    the fused window's chunk budget ran out with live lanes below the
    horizon (the engine raises FusedWindowTruncated); None on the
    unfused paths, whose while_loop has no chunk budget.
    sketch: (hist, rare) int32 device sketches (repro/stats) when the
    engine configured a SketchSpec — reduced device-side with one psum
    under the sharded strategy (integer sums: shard partials are
    bitwise the full-pool counts), or None when the engine should
    compute them eagerly from `obs` (fused strategy).
    n_chunks: int32 device scalar on the kernel paths — the chunk-loop
    iterations the window ran (the most of any group or shard); None on
    the unfused paths.
    shard_steps: (n_shards,) int32 cumulative step counts of each
    shard's lanes (sharded strategy); None elsewhere.
    """

    obs: Any
    steps_delta: Any
    stats: Optional[reduction.Stats]
    grouped: Optional[reduction.Stats]
    truncated: Any = None
    sketch: Any = None
    n_chunks: Any = None
    shard_steps: Any = None


class BlockResult(NamedTuple):
    """One superstep's on-device record ring: W windows advanced in ONE
    dispatch, every per-window product stacked over a leading (W,) axis
    and left on device until the engine's collector pulls the block.

    obs: (W, I, n_obs) per-window samples (sharded over I under the
    sharded strategy).
    steps_end / leaps_end: (W,) int32 pool-total step/leap counts at
    each window's end (cumulative — the collector takes mod-2^32
    deltas exactly like the per-window path).
    stats / grouped: length-W lists of per-window Stats already reduced
    device-side (sharded strategy — the same psum-gather + eager fold
    the per-window path uses), or None when the engine should compute
    them from `obs` rows (fused strategy, mirroring its per-window
    eager reduction).
    steps_delta: (W, I) per-instance events per window — only produced
    under the predictive policy (the scheduler's EMA costs are updated
    window-by-window at collect time; regrouping happens at block
    boundaries, which never changes a trajectory — lane groups are
    execution packaging, not semantics).
    truncated: (W,) int32 on the kernel paths — nonzero entries mark
    windows whose chunk budget ran out (the collector raises
    FusedWindowTruncated naming the first one); None on unfused paths.
    sketch: (hist, rare) stacked (W, ...) int32 sketches riding the
    ring (sharded strategy, one psum each — exact integer sums), or
    None when the engine computes them eagerly from `obs` rows (fused
    strategy).
    n_chunks: (W,) int32 on the kernel paths — each window's chunk-loop
    iterations (the most of any shard); None on unfused paths.
    shard_steps: (W, n_shards) int32 cumulative step counts of each
    shard's lanes at each window's end (sharded strategy), summed on
    each shard beside the psum of the pool total; None elsewhere.
    """

    obs: Any
    steps_end: Any
    leaps_end: Any
    stats: Optional[list] = None
    grouped: Optional[list] = None
    steps_delta: Any = None
    truncated: Any = None
    sketch: Any = None
    n_chunks: Any = None
    shard_steps: Any = None


def _obs_extractor(obs_idx):
    """Normalised device-side observable extraction, shared by BOTH
    window-body factories — a single definition is what keeps the
    kernel and unfused paths' records bitwise comparable."""
    obs_idx = tuple(tuple(int(i) for i in ii) for ii in obs_idx)

    def extract(x):
        cols = [x[:, list(ii)].sum(axis=1) for ii in obs_idx]
        return jnp.stack(cols, axis=1)

    return extract


def make_window_body(advance_fn, n_lanes: int, obs_idx):
    """The shared whole-pool window advance: permutation gather,
    lax.scan over fixed-size lane slices (each running the masked
    per-lane advance to the horizon), inverse scatter, device-side
    observables.

    `advance_fn(lane_slice, rates, horizon) -> LaneState` is the
    engine-built per-slice loop (`gillespie.make_advance_fn` — dense
    exact, tau-leap, or the sparse dependency-graph step with its
    carried propensity vector); the window machinery is method- and
    encoding-agnostic.

    Used verbatim by BOTH the fused and the sharded strategies (the
    sharded one applies it per shard with shard-local indices), which
    is what keeps their per-lane trajectories bit-identical.
    """
    extract_obs = _obs_extractor(obs_idx)

    def window_body(pool: LaneState, rates, perm, horizon):
        n_groups = perm.shape[0] // n_lanes

        def take(a):
            return a[perm].reshape((n_groups, n_lanes) + a.shape[1:])

        lanes = LaneState(*(take(a) for a in pool))
        rates_g = take(rates)

        def advance_group(carry, grp):
            sl, r = grp
            return carry, advance_fn(sl, r, horizon)

        _, advanced = jax.lax.scan(advance_group, 0, (lanes, rates_g))
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((n_groups * n_lanes,) + a.shape[2:]),
            advanced)
        # duplicate padding indices write identical data — safe
        new_pool = LaneState(*(
            p.at[perm].set(v) for p, v in zip(pool, flat)))
        return new_pool, extract_obs(new_pool.x), \
            new_pool.steps - pool.steps

    return window_body


class _Dispatch:
    """Base strategy: holds a back-reference to the engine, advances
    the pool one window, and accounts its own telemetry."""

    name = "?"

    def __init__(self, engine):
        self.eng = engine

    def place(self, tree):
        """Device placement for pool-shaped pytrees (leading instance
        axis). Identity except under sharding."""
        return tree

    def advance(self, horizon) -> WindowResult:
        raise NotImplementedError

    def advance_block(self, horizons) -> BlockResult:
        """Advance the pool over a whole block of window horizons in
        one dispatch (superstep). Only the fused and sharded strategies
        implement it; the host loop is the per-window baseline
        (SimConfig rejects window_block > 1 with host_loop)."""
        raise NotImplementedError(
            f"dispatch strategy {self.name!r} has no superstep path; "
            "window_block > 1 needs the fused or sharded strategy")


class HostLoopDispatch(_Dispatch):
    """Legacy baseline: per-group gather -> advance -> scatter, one
    dispatch per (group x window) — with or without the fused kernel
    inside each group's launch."""

    name = "host_loop"

    def __init__(self, engine):
        super().__init__(engine)
        self._advance_fn = self._make_advance()

    def _make_advance(self):
        eng = self.eng
        cfg = eng.cfg

        if cfg.use_kernel:
            idx_t, coef_t, delta_t, _ = eng._tensors_base
            # the chunk loop is one jitted launch (device-side
            # while_loop): one dispatch per group, no mid-window host
            # syncs — exact or tau-leap, dense or sparse, per the
            # engine's method/encoding
            chunk_loop = eng._make_chunk_loop()

            def advance(pool_slice, rates, horizon):
                return chunk_loop(
                    pool_slice, (idx_t, coef_t, delta_t, rates), horizon)

            return jax.jit(advance, donate_argnums=(0,))

        # the engine-built per-slice loop — the SAME advance the fused/
        # sharded window bodies scan, jitted per group here
        return jax.jit(eng._make_advance_fn(), donate_argnums=(0,))

    def _gather(self, idx) -> tuple[LaneState, jax.Array]:
        p = self.eng._pool
        sl = LaneState(*(a[idx] for a in p))
        # index the cached device rates — no per-window host re-upload
        return sl, self.eng._rates_dev[idx]

    def _scatter(self, idx, sl: LaneState) -> None:
        p = self.eng._pool
        # guard duplicate padding indices: later writes win (same data)
        self.eng._pool = LaneState(
            *(a.at[idx].set(v) for a, v in zip(p, sl)))

    def advance(self, horizon) -> WindowResult:
        eng = self.eng
        use_kernel = eng.cfg.use_kernel
        predictive = eng.scheduler.policy == "predictive"
        steps_before = None
        truncated = n_chunks = None
        if predictive:
            steps_before = np.asarray(eng._pool.steps)
            eng.n_host_syncs += 1
        for idx in eng.scheduler.groups():
            sl, rates = self._gather(idx)
            out = self._advance_fn(sl, rates, horizon)
            eng.n_dispatches += 1
            if use_kernel:
                # device-scalar truncation flags OR together lazily —
                # no per-group (or per-chunk) host pull
                truncated = (out.truncated if truncated is None
                             else truncated | out.truncated)
                n_chunks = (out.n_chunks if n_chunks is None
                            else jnp.maximum(n_chunks, out.n_chunks))
                sl = out.state
            else:
                sl = out
            self._scatter(idx, sl)
        steps_delta = None
        if predictive:
            steps_delta = np.asarray(eng._pool.steps) - steps_before
            eng.n_host_syncs += 1
        return WindowResult(eng._observe(), steps_delta, None, None,
                            truncated, n_chunks=n_chunks)


def make_kernel_window_body(tensors3, obs_idx, chunk_loop_fn):
    """Whole-pool window advance through a Pallas fused kernel chunk
    loop: one device-side while_loop + observable extraction, traceable
    under jit (fused strategy) and shard_map (sharded strategy).

    `chunk_loop_fn(pool, (idx, coef, delta, rates), horizon) ->
    FusedWindowOut` is the engine-built loop (`_make_chunk_loop`) —
    exact SSA (`ops.window_chunk_loop`) or tau-leap
    (`ops.tau_window_chunk_loop`) with the chunk budget bound in.

    No permutation/group scan: the kernel's lane-block grid IS the
    SIMD grouping, and every per-lane op is independent, so scheduler
    groups would not change a single trajectory.

    Returns (new_pool, obs, steps_delta, truncated, n_chunks)."""
    idx_t, coef_t, delta_t = tensors3
    extract_obs = _obs_extractor(obs_idx)

    def window_body(pool: LaneState, rates, horizon):
        out = chunk_loop_fn(pool, (idx_t, coef_t, delta_t, rates),
                            horizon)
        new_pool = out.state
        return new_pool, extract_obs(new_pool.x), \
            new_pool.steps - pool.steps, out.truncated, out.n_chunks

    return window_body


class FusedDispatch(_Dispatch):
    """One jitted, donated window_step for the whole pool — one device
    dispatch per window (DESIGN.md §3). With `use_kernel=True` the
    step is the Pallas fused-window chunk loop instead of the
    permutation + lax.scan body — still one dispatch per window, now
    with the SSA inner loop resident in VMEM."""

    name = "fused"

    def __init__(self, engine):
        super().__init__(engine)
        cfg = engine.cfg
        idx_t, coef_t, delta_t, _ = engine._tensors_base
        self._kernel = cfg.use_kernel
        if self._kernel:
            body = make_kernel_window_body(
                (idx_t, coef_t, delta_t), engine.obs_idx,
                engine._make_chunk_loop())
        else:
            body = make_window_body(engine._make_advance_fn(),
                                    engine.scheduler.n_lanes,
                                    engine.obs_idx)
        self._body = body
        self._step = jax.jit(body, donate_argnums=(0,))
        self._block_step = None  # built lazily on first superstep

    def advance(self, horizon) -> WindowResult:
        eng = self.eng
        if self._kernel:
            eng._pool, obs, steps_delta, truncated, n_chunks = self._step(
                eng._pool, eng._rates_dev, horizon)
            eng.n_dispatches += 1
            return WindowResult(obs, steps_delta, None, None, truncated,
                                n_chunks=n_chunks)
        eng._pool, obs, steps_delta = self._step(
            eng._pool, eng._rates_dev, eng._permutation(), horizon)
        eng.n_dispatches += 1
        return WindowResult(obs, steps_delta, None, None)

    def _build_block(self):
        """ONE jitted, donated superstep: lax.scan of the window body
        over a (W,) horizon vector, stacking per-window obs + telemetry
        into the record ring. The scan body is the SAME window body the
        per-window step jits, so per-lane trajectories (and therefore
        records) are bitwise independent of window_block."""
        body = self._body
        kernel = self._kernel
        predictive = self.eng.scheduler.policy == "predictive"
        # in-scan predictive regroup: the cost EMA rides the scan carry
        # (float32 device copy of the scheduler's EMA) and every window
        # re-sorts groups from it — per-window regrouping with ZERO
        # host round trips, where the host-perm form could only regroup
        # at block boundaries. Grouping is execution packaging, never
        # semantics (per-lane ops are independent, padding duplicates
        # write identical data), so records stay bitwise identical to
        # the host-perm path; steps_d still rides the ring so the host
        # EMA (the canonical float64 copy) updates at collect time
        # exactly as before. The kernel body has no grouping at all.
        in_scan = predictive and not kernel
        if in_scan:
            take_pos = jnp.asarray(self.eng.scheduler.take_positions())
            alpha = self.eng.scheduler.ema_alpha

            def cost_block_body(pool, rates, cost, horizons):
                def step(carry, h):
                    p, c = carry
                    perm = jnp.argsort(c, stable=True)[take_pos]
                    new_pool, obs, steps_d = body(p, rates, perm, h)
                    new_c = (1 - alpha) * c + \
                        alpha * steps_d.astype(c.dtype)
                    ring = (obs, new_pool.steps.sum(),
                            new_pool.leaps.sum(), jnp.int32(0), steps_d)
                    return (new_pool, new_c), ring

                return jax.lax.scan(step, (pool, cost), horizons)

            return jax.jit(cost_block_body, donate_argnums=(0, 2))

        def block_body(pool, rates, perm, horizons):
            def step(p, h):
                if kernel:  # truncation flag and chunk count
                    new_pool, obs, steps_d, trunc, chunks = body(
                        p, rates, h)
                    kring = (trunc.astype(jnp.int32), chunks)
                else:
                    new_pool, obs, steps_d = body(p, rates, perm, h)
                    kring = (jnp.int32(0),)
                ring = (obs, new_pool.steps.sum(), new_pool.leaps.sum()
                        ) + kring + ((steps_d,) if predictive else ())
                return new_pool, ring

            return jax.lax.scan(step, pool, horizons)

        return jax.jit(block_body, donate_argnums=(0,))

    def advance_block(self, horizons) -> BlockResult:
        eng = self.eng
        if self._block_step is None:
            self._block_step = self._build_block()
        predictive = eng.scheduler.policy == "predictive"
        in_scan = predictive and not self._kernel
        hvec = jnp.asarray(horizons, jnp.float32)
        if in_scan:
            (eng._pool, eng._cost_dev), ring = self._block_step(
                eng._pool, eng._rates_dev, eng._cost_device(), hvec)
        else:
            perm = None if self._kernel else eng._permutation()
            eng._pool, ring = self._block_step(
                eng._pool, eng._rates_dev, perm, hvec)
        eng.n_dispatches += 1
        obs, steps_end, leaps_end, trunc = ring[:4]
        chunks = ring[4] if self._kernel else None
        return BlockResult(
            obs=obs, steps_end=steps_end, leaps_end=leaps_end,
            steps_delta=(ring[-1] if predictive else None),
            truncated=(trunc if self._kernel else None),
            n_chunks=chunks)


class ShardedDispatch(_Dispatch):
    """The fused window body sharded over a mesh data axis.

    Pool, rates, and the scheduler permutation are sharded per device
    (in_specs P(axis)); each shard advances its own lane slices with
    shard-local indices; per-window statistic partials cross shards
    through `reduction.gather_blocks_over_axis` (one psum) and come
    back replicated, so the host sees one dispatch and O(1) pulls per
    window regardless of shard count.
    """

    name = "sharded"

    def __init__(self, engine, mesh, partitioning: Partitioning):
        super().__init__(engine)
        part = partitioning
        if engine.cfg.n_instances % part.n_shards:
            raise ValueError(
                f"n_instances={engine.cfg.n_instances} not divisible by "
                f"n_shards={part.n_shards}")
        if part.axis not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {part.axis!r} (axes: "
                f"{tuple(mesh.axis_names)})")
        if mesh.shape[part.axis] != part.n_shards:
            raise ValueError(
                f"mesh axis {part.axis!r} has size "
                f"{mesh.shape[part.axis]}, but Partitioning.n_shards is "
                f"{part.n_shards}")
        self.mesh = mesh
        self.part = part
        self._sharding = NamedSharding(mesh, P(part.axis))
        self._step = None
        # cache key: (grouped?, n_groups) — the jitted step closes over
        # both, so a set_groups() with a new group count must rebuild
        self._step_key: Optional[tuple] = None
        self._block_step = None
        self._block_key: Optional[tuple] = None

    def place(self, tree):
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._sharding), tree)

    def _build(self, grouped: bool):
        eng = self.eng
        part = self.part
        axis, n_shards = part.axis, part.n_shards
        per_shard = eng.cfg.n_instances // n_shards
        v_loc = part.blocks // n_shards
        n_groups = eng._n_groups if grouped else 0
        use_kernel = eng.cfg.use_kernel
        sk = eng._sketch  # SketchParams or None (frozen per engine)
        idx_t, coef_t, delta_t, _ = eng._tensors_base
        if use_kernel:
            # per-shard Pallas fused window: the paper's two families
            # (single-simulation speedup x simulation farm) composed
            kbody = make_kernel_window_body(
                (idx_t, coef_t, delta_t), eng.obs_idx,
                eng._make_chunk_loop())
        else:
            body = make_window_body(eng._make_advance_fn(),
                                    eng.scheduler.n_lanes, eng.obs_idx)

        def local(pool, rates, perm, gids, horizon):
            if use_kernel:
                new_pool, obs, steps_delta, trunc, chunks = kbody(
                    pool, rates, horizon)
                # any-shard truncation and the most chunks any shard
                # ran, replicated so one device scalar answers for the
                # whole farm
                trunc = jax.lax.psum(trunc.astype(jnp.int32), axis)
                chunks = jax.lax.pmax(chunks, axis)
            else:
                k = jax.lax.axis_index(axis)
                perm_loc = perm - k * per_shard  # global -> shard-local
                new_pool, obs, steps_delta = body(pool, rates, perm_loc,
                                                  horizon)
                # a constant is already replicated — no collective
                trunc = jnp.int32(0)
            # psum-gather the per-block partial accumulators; the final
            # O(V) fold runs eagerly host-side (advance() below) with
            # the exact op sequence the unsharded path uses, so records
            # stay bitwise independent of the mesh shape
            acc = reduction.blocked_welford(obs, v_loc)
            stack = reduction.gather_blocks_over_axis(acc, axis,
                                                      n_shards)
            outs = (new_pool, obs, steps_delta, trunc, stack)
            if grouped:
                gacc = reduction.blocked_grouped_welford(
                    obs, gids, n_groups, v_loc)
                gstack = reduction.gather_blocks_over_axis(gacc, axis,
                                                           n_shards)
                outs = outs + (gstack,)
            if sk is not None:
                # int32 counts: shard-partial psum is bitwise the
                # full-pool sum (integer addition is associative with
                # exact identity), so sketches are mesh-shape-agnostic
                g = gids if grouped else jnp.zeros((obs.shape[0],),
                                                   jnp.int32)
                hist, rare = window_sketch(
                    obs, g, n_groups if grouped else 1, sk.lo, sk.width,
                    sk.n_bins, sk.thresholds if sk.n_thr else None)
                outs = outs + (jax.lax.psum(hist, axis),)
                if rare is not None:
                    outs = outs + (jax.lax.psum(rare, axis),)
            if use_kernel:
                outs = outs + (chunks,)
            # this shard's cumulative steps, one entry per shard
            return outs + (new_pool.steps.sum()[None],)

        sh = P(axis)
        out_specs = (sh, sh, sh, P(), P()) + ((P(),) if grouped else ())
        if sk is not None:
            out_specs = out_specs + (P(),) * (1 + (1 if sk.n_thr else 0))
        if use_kernel:
            out_specs = out_specs + (P(),)
        out_specs = out_specs + (sh,)
        # the kernel body never reads the scheduler permutation (its
        # lane-block grid IS the grouping) — drop the operand so the
        # host neither assembles nor ships it each window
        if use_kernel and grouped:
            def wrapped(pool, rates, gids, horizon):
                return local(pool, rates, None, gids, horizon)

            in_specs = (sh, sh, sh, P())
        elif use_kernel:
            def wrapped(pool, rates, horizon):
                return local(pool, rates, None, None, horizon)

            in_specs = (sh, sh, P())
        elif grouped:
            wrapped = local
            in_specs = (sh, sh, sh, sh, P())
        else:
            def wrapped(pool, rates, perm, horizon):
                return local(pool, rates, perm, None, horizon)

            in_specs = (sh, sh, sh, P())
        fn = jax.shard_map(wrapped, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=(0,))

    def _build_block(self, grouped: bool):
        """The sharded superstep: the SAME per-window local body
        (window advance + per-block Welford partials + one psum gather)
        wrapped in a lax.scan over the horizon vector, all inside one
        shard_map'd, jitted, donated dispatch. Per-window gathered
        stat stacks come back replicated with a leading (W,) axis; the
        eager merge_blocks/finalize fold stays OUTSIDE the jit
        (advance_block below), exactly like the per-window path, so
        records are bitwise independent of both the mesh shape and
        window_block."""
        eng = self.eng
        part = self.part
        axis, n_shards = part.axis, part.n_shards
        per_shard = eng.cfg.n_instances // n_shards
        v_loc = part.blocks // n_shards
        n_groups = eng._n_groups if grouped else 0
        use_kernel = eng.cfg.use_kernel
        predictive = eng.scheduler.policy == "predictive"
        # in-scan predictive regroup (see FusedDispatch._build_block):
        # the shard-LOCAL cost slice rides the scan carry and each
        # window re-sorts within the shard — the same shard-locality
        # the host groups() enforces, with zero host round trips.
        # take_positions() replicates the host padding rule, and its
        # positions are shard-local, so argsort output needs no
        # global->local shift
        in_scan = predictive and not use_kernel
        sk = eng._sketch
        idx_t, coef_t, delta_t, _ = eng._tensors_base
        if use_kernel:
            kbody = make_kernel_window_body(
                (idx_t, coef_t, delta_t), eng.obs_idx,
                eng._make_chunk_loop())
        else:
            body = make_window_body(eng._make_advance_fn(),
                                    eng.scheduler.n_lanes, eng.obs_idx)
        if in_scan:
            take_pos = jnp.asarray(eng.scheduler.take_positions())
            alpha = eng.scheduler.ema_alpha

        def local(pool, rates, pc, gids, horizons):
            # `pc` is the third operand: the global permutation for the
            # host-perm form, the shard-local cost slice when in_scan
            def step(carry, h):
                p, c = carry if in_scan else (carry, None)
                if use_kernel:
                    new_pool, obs, steps_d, trunc, chunks = kbody(
                        p, rates, h)
                    trunc = jax.lax.psum(trunc.astype(jnp.int32), axis)
                else:
                    if in_scan:
                        perm_loc = jnp.argsort(c, stable=True)[take_pos]
                    else:
                        k = jax.lax.axis_index(axis)
                        perm_loc = pc - k * per_shard
                    new_pool, obs, steps_d = body(p, rates, perm_loc, h)
                    trunc = jnp.int32(0)
                acc = reduction.blocked_welford(obs, v_loc)
                stack = reduction.gather_blocks_over_axis(acc, axis,
                                                          n_shards)
                # int32 pool-total counters are exact mod 2^32, so the
                # psum equals the eager global sum the per-window path
                # pulls
                ring = (obs, trunc, stack,
                        jax.lax.psum(new_pool.steps.sum(), axis),
                        jax.lax.psum(new_pool.leaps.sum(), axis))
                if grouped:
                    gacc = reduction.blocked_grouped_welford(
                        obs, gids, n_groups, v_loc)
                    ring = ring + (reduction.gather_blocks_over_axis(
                        gacc, axis, n_shards),)
                if sk is not None:
                    g = gids if grouped else jnp.zeros(
                        (obs.shape[0],), jnp.int32)
                    hist, rare = window_sketch(
                        obs, g, n_groups if grouped else 1, sk.lo,
                        sk.width, sk.n_bins,
                        sk.thresholds if sk.n_thr else None)
                    ring = ring + (jax.lax.psum(hist, axis),)
                    if rare is not None:
                        ring = ring + (jax.lax.psum(rare, axis),)
                if predictive:
                    ring = ring + (steps_d,)
                if use_kernel:
                    ring = ring + (jax.lax.pmax(chunks, axis),)
                # this shard's cumulative steps: (W, 1) per shard,
                # (W, n_shards) assembled
                ring = ring + (new_pool.steps.sum()[None],)
                if in_scan:
                    new_c = (1 - alpha) * c + \
                        alpha * steps_d.astype(c.dtype)
                    return (new_pool, new_c), ring
                return new_pool, ring

            init = (pool, pc) if in_scan else pool
            return jax.lax.scan(step, init, horizons)

        sh = P(axis)
        rsh = P(None, axis)  # (W, I_loc, ...) rings: windows leading
        ring_specs = (rsh, P(), P(), P(), P())
        if grouped:
            ring_specs = ring_specs + (P(),)
        if sk is not None:
            ring_specs = ring_specs + (P(),) * (1 + (1 if sk.n_thr
                                                     else 0))
        if predictive:
            ring_specs = ring_specs + (rsh,)
        if use_kernel:
            ring_specs = ring_specs + (P(),)
        ring_specs = ring_specs + (rsh,)
        out_specs = (((sh, sh) if in_scan else sh), ring_specs)
        if use_kernel and grouped:
            def wrapped(pool, rates, gids, horizons):
                return local(pool, rates, None, gids, horizons)

            in_specs = (sh, sh, sh, P())
        elif use_kernel:
            def wrapped(pool, rates, horizons):
                return local(pool, rates, None, None, horizons)

            in_specs = (sh, sh, P())
        elif grouped:
            wrapped = local
            in_specs = (sh, sh, sh, sh, P())
        else:
            def wrapped(pool, rates, pc, horizons):
                return local(pool, rates, pc, None, horizons)

            in_specs = (sh, sh, sh, P())
        fn = jax.shard_map(wrapped, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=((0, 2) if in_scan else (0,)))

    def advance_block(self, horizons) -> BlockResult:
        eng = self.eng
        grouped = eng._group_ids_dev is not None
        predictive = eng.scheduler.policy == "predictive"
        in_scan = predictive and not eng.cfg.use_kernel
        key = (grouped, eng._n_groups if grouped else 0)
        if self._block_step is None or self._block_key != key:
            self._block_step = self._build_block(grouped)
            self._block_key = key
        step_args = [eng._pool, eng._rates_dev]
        if not eng.cfg.use_kernel:
            # third operand: shard-local cost carry (in-scan regroup)
            # or the host-assembled global permutation
            step_args.append(eng._cost_device() if in_scan
                             else eng._permutation())
        if grouped:
            step_args.append(eng._group_ids_dev)
        carry, ring = self._block_step(
            *step_args, jnp.asarray(horizons, jnp.float32))
        if in_scan:
            eng._pool, eng._cost_dev = carry
        else:
            eng._pool = carry
        eng.n_dispatches += 1
        obs, trunc, stack, steps_end, leaps_end = ring[:5]
        i = 5
        gstack = None
        if grouped:
            gstack = ring[i]
            i += 1
        sketch = None
        if eng._sketch is not None:
            hist = ring[i]
            i += 1
            rare = None
            if eng._sketch.n_thr:
                rare = ring[i]
                i += 1
            sketch = (hist, rare)
        steps_delta = ring[i] if predictive else None
        chunks = ring[-2] if eng.cfg.use_kernel else None
        n_windows = len(horizons)
        # per-window eager fold — the exact op sequence the per-window
        # sharded advance() (and the unsharded path) uses
        stats = [reduction.finalize(reduction.merge_blocks(
            reduction.Welford(*(a[w] for a in stack))))
            for w in range(n_windows)]
        gstats = None
        if grouped:
            gstats = [reduction.finalize(reduction.merge_blocks(
                reduction.Welford(*(a[w] for a in gstack))))
                for w in range(n_windows)]
        return BlockResult(
            obs=obs, steps_end=steps_end, leaps_end=leaps_end,
            stats=stats, grouped=gstats, steps_delta=steps_delta,
            truncated=(trunc if eng.cfg.use_kernel else None),
            sketch=sketch, n_chunks=chunks, shard_steps=ring[-1])

    def advance(self, horizon) -> WindowResult:
        eng = self.eng
        grouped = eng._group_ids_dev is not None
        key = (grouped, eng._n_groups if grouped else 0)
        if self._step is None or self._step_key != key:
            self._step = self._build(grouped)
            self._step_key = key
        step_args = [eng._pool, eng._rates_dev]
        if not eng.cfg.use_kernel:
            step_args.append(eng._permutation())
        if grouped:
            step_args.append(eng._group_ids_dev)
        outs = self._step(*step_args, horizon)
        eng._pool, obs, steps_delta, trunc, stack = outs[:5]
        i = 5
        gstats = None
        if grouped:
            gstats = reduction.finalize(reduction.merge_blocks(outs[i]))
            i += 1
        sketch = None
        if eng._sketch is not None:
            hist = outs[i]
            i += 1
            rare = None
            if eng._sketch.n_thr:
                rare = outs[i]
                i += 1
            sketch = (hist, rare)
        chunks = outs[i] if eng.cfg.use_kernel else None
        stats = reduction.finalize(reduction.merge_blocks(stack))
        eng.n_dispatches += 1
        truncated = trunc if eng.cfg.use_kernel else None
        return WindowResult(obs, steps_delta, stats, gstats, truncated,
                            sketch, chunks, shard_steps=outs[-1])


def select_dispatch(engine, mesh):
    """Resolve the engine's (cfg, partitioning, mesh) to one strategy.

    Returns (dispatch, mesh): the mesh is built here (via
    `jax.make_mesh`) when a multi-shard Partitioning arrives without
    one.
    """
    cfg = engine.cfg
    part = engine.partitioning
    if part is not None and part.n_shards > 1:
        if cfg.host_loop:
            raise ValueError(
                "sharded dispatch is incompatible with host_loop=True; "
                "the host loop is a single-device baseline")
        part.validate(cfg.n_instances)
        if mesh is None:
            n_dev = len(jax.devices())
            if part.n_shards > n_dev:
                raise ValueError(
                    f"Partitioning.n_shards={part.n_shards} but only "
                    f"{n_dev} device(s) are visible (set XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N to farm "
                    "over forced host devices)")
            mesh = jax.make_mesh((part.n_shards,), (part.axis,),
                                 axis_types=(AxisType.Auto,))
        return ShardedDispatch(engine, mesh, part), mesh
    if cfg.host_loop:
        return HostLoopDispatch(engine), mesh
    return FusedDispatch(engine), mesh
