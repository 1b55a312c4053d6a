"""SimulationEngine — the multicore-aware simulator, TPU-pod native.

Runs an ensemble of stochastic CWC simulations (replicas and/or a
parameter sweep) under one of the paper's three schemas:

  schema "i"   static farm, post-hoc reduction (baseline)
  schema "ii"  time-sliced self-balancing farm, post-hoc reduction
  schema "iii" time-sliced farm + ON-LINE windowed reduction (the
               paper's best variant; memory-bounded)

Hot path: the whole instance pool advances one window as ONE pytree
through a single jitted, donated `window_step` — the scheduler's groups
become a device-side permutation plus a `lax.scan` over fixed-size lane
slices, so a window costs one dispatch instead of one gather/advance/
scatter round trip per group. With `SimConfig.use_kernel` the step is
instead the Pallas fused SSA window (kernels/): a device-side chunk
while_loop with in-VREG counter-based RNG — still one dispatch per
window, zero mid-window host syncs, and bitwise identical to the
unfused path (DESIGN.md §3c). The legacy host-driven per-group loop is
kept behind `SimConfig.host_loop` as the benchmark baseline; all paths
are bit-identical because every per-lane operation is unchanged.
With `SimConfig.window_block=W` whole runs go device-resident:
W windows fuse into ONE dispatch (a lax.scan inside the strategy) whose
per-window products land in an on-device record ring, and the engine's
depth-K pipelined collector (`SimConfig.pipeline_depth`, "auto" to
profile) keeps up to K blocks in flight before blocking on the oldest
ring pull — so dispatches AND host syncs amortise to 1/W per window,
the collector's host work hides behind K blocks of device compute, and
records stay bitwise identical for any W and any K (DESIGN.md §3e).
Ring snapshots (`enable_snapshots`) let checkpoint() save the collected
frontier while blocks stay in flight instead of flushing the pipeline.

Distribution: with a `Partitioning` (or a mesh), the instance pool is
sharded over the mesh's data axis (each shard = a farm worker); the
same window body runs per shard under `jax.shard_map`, and
per-window statistics are reduced with a single psum tree
(`reduction.gather_blocks_over_axis` + `merge_blocks`) so only
O(stat_blocks x species) floats ever cross pods. Dispatch-path selection (host loop / fused /
sharded) lives in `core/dispatch.py` as one explicit strategy seam.
Fault tolerance: `checkpoint()`/`restore()` serialise the pool +
scheduler + accumulators + emitted records (gather-on-save); restore
re-places the pool on the current mesh (reshard-on-restore), and
trajectories are deterministic per-instance (keyed RNG), so a restart —
even with a different mesh shape — resumes bit-identically.

NOTE: constructing `SimulationEngine` directly is deprecated — use the
declarative front-end, `repro.api.simulate(Experiment(...))` (see
DESIGN.md for the migration table). The old surface is kept as a thin
shim over the same engine.
"""
from __future__ import annotations

import collections
import math
import warnings
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import reduction
from repro.core.cwc.compile import compile_model
from repro.core.cwc.rules import CWCModel
from repro.core.dispatch import Partitioning, select_dispatch
from repro.core.gillespie import (
    LaneState,
    init_lanes,
    make_advance_fn,
    sparse_system_tensors,
    ssa_step,
    system_tensors,
)
from repro.core.reactions import MAX_COEF, ReactionSystem, sparse_tables
from repro.core.scheduler import Scheduler
from repro.core.spans import span
from repro.core.stream import StatsRecord, StatsStream
from repro.ckpt import store as ckpt_store
from repro.runtime.fault import InvariantViolation
from repro.runtime.straggler import WindowWatchdog
from repro.stats.sketch import SketchSpec, WindowSketch, window_sketch
from repro.steer.policy import Steering, SteeringActions, SteeringPolicy


@dataclass(frozen=True)
class SimConfig:
    n_instances: int = 128
    t_end: float = 10.0
    n_windows: int = 50
    n_lanes: int = 128  # SIMD width per slice group
    schema: str = "iii"  # i | ii | iii
    policy: str = "on_demand"  # static_rr | on_demand | predictive
    seed: int = 0
    max_steps_per_window: Optional[int] = None
    use_kernel: bool = False  # fused Pallas window (see kernels/)
    host_loop: bool = False  # legacy per-group gather/scatter dispatch
    # kernel-path chunking: each window is ONE dispatch running up to
    # kernel_max_chunks kernel launches of kernel_chunk_steps fused
    # events in a device-side while_loop; a window needing more raises
    # FusedWindowTruncated (never silently truncates)
    kernel_chunk_steps: int = 256
    kernel_max_chunks: int = 64
    # simulation algorithm: "exact" (Gillespie direct SSA) or
    # "tau_leap" (adaptive Cao tau selection + Poisson reaction counts,
    # per-lane exact fallback — core/tau_leap.py). Composes with every
    # dispatch path (host_loop | fused | sharded, x use_kernel).
    method: str = "exact"
    tau_eps: float = 0.03  # Cao bound: max relative propensity drift
    tau_fallback: float = 10.0  # leap only when tau covers >= this
    #   many expected SSA events (else per-lane exact SSA step)
    # superstep width: fuse this many windows into ONE device dispatch
    # (a lax.scan over window horizons inside the fused/sharded
    # strategies) with the per-window records accumulated in an
    # on-device ring and pulled per block by the engine's pipelined
    # collector — dispatches and host syncs amortise to 1/window_block
    # per window. 1 (default) is the unchanged per-window path;
    # records are bitwise identical for any value (DESIGN.md §3e).
    window_block: int = 1
    # superstep pipeline depth (DESIGN.md §3e): how many dispatched
    # window blocks may sit in flight (ring pull outstanding) after
    # each collector turn before the oldest is collected. 1 is the
    # PR 5 double-buffer; K > 1 keeps K rings queued so the collector's
    # host-side reduce/emit work is hidden behind K blocks of device
    # compute; "auto" measures the first collected block's blocking
    # pull vs host-reduce walls and picks a depth from that profile
    # (resolve_auto_depth). Depth only changes WHEN rings are pulled,
    # never what was computed — records/sketches/grouped stats/
    # trajectories/steering are bitwise identical for any depth. Each
    # in-flight block holds a full record ring (plus a pool snapshot
    # when ring-snapshot checkpointing is enabled), and
    # peak_buffered_bytes accounts for all of them. Irrelevant (depth
    # is effectively 1) when window_block == 1 or under host_loop.
    pipeline_depth: Union[int, str] = 1
    # sparse large-network encoding (DESIGN.md §3g): CSR-style padded
    # reactant tables + a precomputed reaction dependency graph so a
    # firing recomputes only the affected propensities (O(out-degree)
    # per event instead of O(R·M)), and the kernels hold only
    # O(R·(M+K+D)) sparse tables instead of O(S·R) one-hots. Composes
    # with every strategy × method × window_block; trajectories and
    # records are BITWISE identical to the dense path. Also lifts the
    # dense MAX_COEF ceiling (table-free comb unroll to the system's
    # actual max coefficient).
    sparse: bool = False
    # engine invariant guards (DESIGN.md §3h): host-side checks on the
    # per-window statistics the collect path has ALREADY pulled
    # (non-finite moments, negative populations, ring/record count
    # disagreement) — zero extra device syncs. A trip raises a typed
    # InvariantViolation; the in-memory pool is untrusted from that
    # point and a supervisor recovers from the last durable checkpoint.
    guards: bool = True

    def __post_init__(self):
        if self.window_block < 1:
            raise ValueError(
                f"SimConfig.window_block must be >= 1, got "
                f"{self.window_block}")
        if self.window_block > 1 and self.host_loop:
            raise ValueError(
                "window_block > 1 needs the fused or sharded dispatch "
                "strategy; host_loop is the per-window round-trip "
                "baseline (set window_block=1)")
        if self.kernel_chunk_steps < 1:
            raise ValueError(
                f"SimConfig.kernel_chunk_steps must be >= 1, got "
                f"{self.kernel_chunk_steps}")
        if self.kernel_max_chunks < 1:
            raise ValueError(
                f"SimConfig.kernel_max_chunks must be >= 1, got "
                f"{self.kernel_max_chunks}")
        if isinstance(self.pipeline_depth, str):
            if self.pipeline_depth != "auto":
                raise ValueError(
                    f"SimConfig.pipeline_depth must be an int >= 1 or "
                    f"'auto', got {self.pipeline_depth!r}")
        elif self.pipeline_depth < 1:
            raise ValueError(
                f"SimConfig.pipeline_depth must be >= 1, got "
                f"{self.pipeline_depth}")
        if self.method not in ("exact", "tau_leap"):
            raise ValueError(
                f"SimConfig.method must be 'exact' or 'tau_leap', got "
                f"{self.method!r}")
        if not self.tau_eps > 0:
            raise ValueError(
                f"SimConfig.tau_eps must be > 0, got {self.tau_eps}")
        if self.tau_fallback < 0:
            raise ValueError(
                f"SimConfig.tau_fallback must be >= 0, got "
                f"{self.tau_fallback}")


# pipeline_depth="auto" bounds: floor keeps at least the PR 5 depth-1
# overlap plus one queued block; cap bounds memory (each in-flight
# block holds a full record ring)
AUTO_DEPTH_MIN = 2
AUTO_DEPTH_MAX = 8


def resolve_auto_depth(pull_s: float, host_s: float) -> int:
    """Pick a pipeline depth from the first collected block's profile.

    `pull_s` is the blocking ring-pull wall with a cold pipeline (a
    proxy for one block's remaining device+transfer time when the
    collector asks) and `host_s` the collector's host-side
    reduce/emit wall for that block. Queueing K blocks gives the
    device ~K blocks of runway while the host works, so the depth
    that hides the host work is 1 + ceil(host_s / pull_s), clamped to
    [AUTO_DEPTH_MIN, AUTO_DEPTH_MAX]. The probe only tunes WHEN rings
    are pulled — results are bitwise identical for any outcome.
    """
    if pull_s <= 0:
        return AUTO_DEPTH_MIN
    return max(AUTO_DEPTH_MIN,
               min(AUTO_DEPTH_MAX, 1 + math.ceil(host_s / pull_s)))


class _InFlight(NamedTuple):
    """One dispatched-but-uncollected superstep in the pipeline."""
    w0: int  # first window of the block
    n_win: int  # windows in the block
    pull: dict  # device record ring + queued eager folds
    dispatch_wall: float  # enqueue + fold span seconds
    obs_row_bytes: int  # one window's obs footprint (schema-iii acct)
    ring_bytes: int  # whole queued ring (+ snapshot) device footprint
    snapshot: Optional[LaneState]  # pool copy taken BEFORE dispatch
    #   (the dispatch donates the pool) — lets checkpoint() save this
    #   block's entry boundary while it is still in flight


def resolve_observables(model: CWCModel | ReactionSystem):
    """(system, obs_names, obs_idx) for a model — the single source of
    the observable-column derivation (engine and repro.api share it)."""
    if isinstance(model, CWCModel):
        system, meta = compile_model(model)
        names = list(meta["observables"]) or list(meta["species"])
        idx = [v for v in meta["observables"].values()] or [
            [i] for i in range(system.n_species)]
    else:
        system = model
        names = list(model.species_names)
        idx = [[i] for i in range(model.n_species)]
    return system, names, idx


class SimulationEngine:
    def __init__(self, model: CWCModel | ReactionSystem, cfg: SimConfig,
                 rates=None, mesh=None, observables: Optional[list] = None,
                 group_ids=None, record_trajectories: bool = False,
                 partitioning: Optional[Partitioning] = None,
                 sketch: Optional[SketchSpec] = None,
                 steering: Optional[Steering] = None,
                 _deprecated: bool = True):
        if _deprecated:
            warnings.warn(
                "constructing SimulationEngine directly is deprecated; "
                "use repro.api.simulate(Experiment(...))",
                DeprecationWarning, stacklevel=2)
        self.system, self.obs_names, self.obs_idx = resolve_observables(
            model)
        self.cfg = cfg
        # a bare mesh (the historically inert `mesh=` kwarg) now means
        # "shard the pool over the mesh's first axis" — see DESIGN.md's
        # migration note; pass `partitioning=` for explicit control
        if partitioning is None and mesh is not None:
            axis = mesh.axis_names[0]
            partitioning = Partitioning(n_shards=mesh.shape[axis],
                                        axis=axis)
        self.partitioning = partitioning
        n_shards = partitioning.n_shards if partitioning else 1
        self.n_shards = n_shards
        if partitioning is not None:
            partitioning.validate(cfg.n_instances)
        self._stats_blocks = partitioning.blocks if partitioning else 1
        # per-instance rates (parameter sweep) or shared
        if rates is None:
            self.rates = np.broadcast_to(
                self.system.rates, (cfg.n_instances, self.system.n_reactions))
        else:
            self.rates = np.asarray(rates, np.float32)
            assert self.rates.shape == (cfg.n_instances,
                                        self.system.n_reactions)
        self.grid = np.linspace(cfg.t_end / cfg.n_windows, cfg.t_end,
                                cfg.n_windows)
        self.stream = StatsStream()
        self.scheduler = Scheduler(
            cfg.n_instances,
            min(cfg.n_lanes, cfg.n_instances // n_shards),
            policy=("static_rr" if cfg.schema == "i" else cfg.policy),
            n_shards=n_shards)
        # dense gather-form tensors are always built (the sparse exact
        # path still seeds its carried propensity vector with the dense
        # evaluation, and sparse tau keeps the dense delta matmuls);
        # the MAX_COEF ceiling only binds when the dense comb unroll
        # would actually be used
        self._tensors_base = system_tensors(self.system,
                                            require_dense=not cfg.sparse)
        self._sparse_tensors = (sparse_system_tensors(
            sparse_tables(self.system)) if cfg.sparse else None)
        self._window = 0
        # superstep pipeline (window_block > 1): windows DISPATCHED to
        # the device run ahead of windows COLLECTED (records emitted);
        # each in-flight block's record ring waits here until its
        # blocking pull — which the collector hides behind the next
        # block's device compute (run_block)
        self._dispatched = 0
        self._pending: collections.deque = collections.deque()
        # depth-K pipeline: resolved in-flight block budget (None while
        # pipeline_depth="auto" awaits its first-collect probe), the
        # probe's measurements, and per-ring accounting/telemetry
        self._depth: Optional[int] = (
            None if cfg.pipeline_depth == "auto"
            else int(cfg.pipeline_depth))
        self.depth_probe: Optional[dict] = None
        self.peak_inflight_blocks = 0
        # ring-snapshot checkpointing (off until a caller that intends
        # to checkpoint mid-run opts in): when enabled, every dispatch
        # first copies the pool so checkpoint() can save the oldest
        # in-flight block's entry boundary instead of flushing
        self._snap_enabled = False
        self.n_snapshot_saves = 0
        self.n_ckpt_flushes = 0
        # the most recent aligned-ENTRY snapshot of a block that ended
        # mid-grid (a max_windows cut): (w0, pool) — checkpoint() rolls
        # a mid-block save back to this boundary so the file always
        # restores under the run's own window_block
        self._aligned_snap: Optional[tuple] = None
        # opt-in export of the per-window blocked Welford PARTIAL
        # stacks (n, mean, m2) feeding each pooled record — the farm
        # worker's seam: a coordinator concatenates worker stacks in
        # global block order and re-runs the same merge_blocks +
        # finalize, reproducing the single-process records bitwise
        self._export_partials = False
        self._block_partials: list = []
        # grouped analogue (per-window (V, G, n_obs) masked partial
        # stacks): the reference grouped fold merges per-(block, group)
        # partials — zero partials included — so worker-local FINALIZED
        # rows are NOT bit-identical to it; the coordinator must embed
        # worker partial stacks into the global (V, G) layout and rerun
        # the same merge
        self._grouped_partials: list = []
        self._gp_fn = None  # lazily-jitted blocked_grouped_welford
        # farm worker seam: (v_total, v0, g_total, g0) — when set, the
        # grouped fold runs over the GLOBAL zero-extended (V, G) stack
        # (zero partials are exact terms in the Sigma-form merge), so a
        # worker's per-point stats — the steering policy's inputs —
        # carry the single-process reference bits even with one local
        # block, where merge_blocks would otherwise short-circuit
        self._stats_layout: Optional[tuple] = None
        # block-level wall attribution: (w0, n_win, dispatch_s,
        # collect_s) per collected unit, from the spans — dispatch_s is
        # enqueue + fold, collect_s is pull + reduce (see
        # Telemetry.block_walls)
        self.block_walls: list[tuple] = []
        # device-side predictive cost carry (in-scan regroup seam);
        # seeded lazily from scheduler._cost, invalidated whenever the
        # host rewrites cost out-of-band (restore, steering)
        self._cost_dev: Optional[jax.Array] = None
        # per-lane algorithm (the method seam): exact SSA or tau-leap —
        # the dispatch strategies consume `_lane_step` (unfused bodies)
        # and `_make_chunk_loop` (Pallas kernel bodies)
        if cfg.method == "tau_leap":
            from repro.core import tau_leap

            self._gi_tab = jnp.asarray(tau_leap.gi_tables(self.system))
            self._rmask = jnp.asarray(tau_leap.reactant_mask(self.system))
            # the sparse seam keeps tau-leap Match in gather form
            # (bitwise equal to the one-hot form, no MAX_COEF ceiling)
            self._lane_step = tau_leap.make_tau_step(
                self._gi_tab, self._rmask, cfg.tau_eps, cfg.tau_fallback,
                gather_max_c=(max(self.system.max_coef, 1)
                              if cfg.sparse else None))
        else:
            self._lane_step = ssa_step
        # schemas i/ii always buffer raw per-window samples; schema iii
        # only on explicit opt-in (it forfeits the memory bound)
        self._record_trajectories = record_trajectories
        self._samples: list = []
        self._peak_buffered = 0
        # wall of the build that made this engine (repro.engine.build;
        # set by api.run.build_engine)
        self.build_s = 0.0
        # body of the dense exact kernel the chunk loop runs: "lanes" or
        # "columns" (`kernels.ssa_step.kernel_layout`), "" on every
        # other path
        self.kernel_layout = ""
        if cfg.use_kernel and cfg.method == "exact" and not cfg.sparse:
            from repro.kernels.ssa_step import kernel_layout

            self.kernel_layout = kernel_layout(self.system.n_species,
                                               self.system.n_reactions)
        # telemetry: device dispatches and blocking device->host pulls
        self.n_dispatches = 0
        self.n_host_syncs = 0
        # per-window method telemetry (from the same single record
        # pull): solver iterations and accepted tau-leaps — their
        # difference is the exact-fallback share
        self.window_steps: list[int] = []
        self.window_leaps: list[int] = []
        # kernel paths: chunk-loop iterations per window (the most any
        # one chunk loop of the window ran: over host-loop groups and
        # shards); empty on the unfused paths
        self.window_chunks: list[int] = []
        # events each shard's lanes fired per window (one entry per
        # shard; one shard off the sharded strategy)
        self.window_shard_steps: list[tuple] = []
        self._cum_steps = 0
        self._cum_shard_steps = np.zeros(n_shards, np.int64)
        self._cum_leaps = 0
        # optional grouped (per-sweep-point) reduction
        self._group_ids = None
        self._group_ids_dev = None
        self._grouped_fn = None
        self._n_groups = 0
        self._grouped: list[reduction.Stats] = []
        # streaming sketches (DESIGN.md §3f): resolved bin geometry +
        # the per-window pulled WindowSketch list; the sharded dispatch
        # reads `_sketch` at build time (sketch counts ride its ring
        # with one psum), the fused/host paths compute eagerly from obs
        self._sketch_spec = sketch
        self._sketch = None
        if sketch is not None:
            x0 = np.asarray(self.system.x0, np.float64)
            obs0 = np.asarray(
                [x0[list(ii)].sum() for ii in self.obs_idx], np.float64)
            self._sketch = sketch.resolve(obs0)
        self._sketch_fn_cache = None
        self._sketches: list[WindowSketch] = []
        if group_ids is not None:
            self.set_groups(group_ids)
        # adaptive steering (DESIGN.md §3f): a between-block controller
        # consulted by run_block at superstep boundaries
        self._steer: Optional[SteeringPolicy] = None
        if steering is not None and steering.enabled:
            steering.validate()
            if cfg.host_loop:
                raise ValueError(
                    "steering is driven from the superstep collector; "
                    "the host_loop baseline has no block boundary to "
                    "steer at (use the fused or sharded strategy)")
            if steering.bimodality and sketch is None:
                raise ValueError(
                    "Steering.bimodality reads window histograms — "
                    "configure a SketchSpec as well")
            if steering.tau_switch and cfg.method != "tau_leap":
                raise ValueError(
                    "Steering.tau_switch only applies to "
                    "method='tau_leap' runs")
            if (isinstance(cfg.pipeline_depth, int)
                    and cfg.pipeline_depth > 1):
                raise ValueError(
                    "steering is incompatible with an explicit "
                    f"pipeline_depth={cfg.pipeline_depth}: decisions "
                    "must see block k before block k+1 dispatches "
                    "(lock-step). Use pipeline_depth=1 or 'auto' "
                    "(which resolves to 1 under steering)")
            # steered runs are lock-step BY CONSTRUCTION: resolve
            # "auto" to 1 here (no probe) so the forcing is visible in
            # pipeline_depth_effective rather than silent in run_block
            self._depth = 1
            self._steer = SteeringPolicy(
                steering, cfg.n_instances,
                n_points=(self._n_groups or 1),
                n_windows=cfg.n_windows,
                tau_leap=(cfg.method == "tau_leap"))
        # straggler watchdog: observes per-window wall clock on every
        # collect path; flagged windows surface in result telemetry
        self.watchdog = WindowWatchdog()
        # dispatch-path selection: one explicit strategy seam
        # (core/dispatch.py) — host loop / fused / sharded
        self._perm_cache: Optional[jax.Array] = None
        pool = init_lanes(self.system, cfg.n_instances, cfg.seed)
        with span("dispatch.place", n_shards=n_shards):
            self._dispatch, self.mesh = select_dispatch(self, mesh)
            self._pool = self._dispatch.place(pool)
            self._rates_dev = self._dispatch.place(jnp.asarray(self.rates))
            if self._group_ids_dev is not None:
                self._group_ids_dev = self._dispatch.place(
                    self._group_ids_dev)

    # -------------------------------------------------------- re-spec
    def set_rates(self, rates) -> None:
        """Install a per-instance (I, R) rate matrix (parameter sweep).
        Must happen before the first window runs."""
        assert self._window == 0, "rates must be set before running"
        rates = np.asarray(rates, np.float32)
        assert rates.shape == (self.cfg.n_instances, self.system.n_reactions)
        self.rates = rates
        with span("dispatch.place", n_shards=self.n_shards):
            self._rates_dev = self._dispatch.place(jnp.asarray(rates))

    def set_groups(self, group_ids) -> None:
        """Enable grouped reduction: group_ids (I,) maps each instance
        to a reduction group (e.g. its sweep point)."""
        ids = np.asarray(group_ids, np.int32)
        assert ids.shape == (self.cfg.n_instances,)
        self._group_ids = ids
        self._group_ids_dev = jnp.asarray(ids)
        self._n_groups = int(ids.max()) + 1
        self._sketch_fn_cache = None  # closes over the group map
        if self._stats_blocks == 1:
            # legacy single-fold form (bit-identical historical records)
            self._grouped_fn = jax.jit(partial(
                reduction.grouped_stats, n_groups=self._n_groups))
        else:
            # jit the per-block partials; fold the (V, G, n_obs) stack
            # eagerly — the same op sequence the sharded dispatch uses,
            # so grouped stats stay bitwise mesh-shape-independent
            stack_fn = jax.jit(partial(
                reduction.blocked_grouped_welford,
                n_groups=self._n_groups, n_blocks=self._stats_blocks))

            def grouped_fn(obs, gids):
                return reduction.finalize(
                    reduction.merge_blocks(stack_fn(obs, gids)))

            self._grouped_fn = grouped_fn
        if self._stats_layout is not None:
            # farm worker: embed the local (V_loc, G_loc) partial stack
            # into the global layout at (v0, g0), run the reference
            # Sigma-form fold (the zero rows force past merge_blocks'
            # V == 1 shortcut and contribute exact-zero terms), then
            # slice this shard's rows back out — bit-identical to the
            # single-process grouped stats the steering thresholds saw
            v_tot, v0, g_tot, g0 = self._stats_layout
            g_loc = self._n_groups
            layout_stack_fn = jax.jit(partial(
                reduction.blocked_grouped_welford,
                n_groups=g_loc, n_blocks=self._stats_blocks))

            def grouped_global_fn(obs, gids):
                local = layout_stack_fn(obs, gids)

                def embed(leaf):
                    full = jnp.zeros(
                        (v_tot, g_tot) + leaf.shape[2:], leaf.dtype)
                    return full.at[v0:v0 + leaf.shape[0],
                                   g0:g0 + g_loc].set(leaf)

                st = reduction.finalize(reduction.merge_blocks(
                    reduction.Welford(*(embed(l) for l in local))))
                return reduction.Stats(
                    *(l[g0:g0 + g_loc] for l in st))

            self._grouped_fn = grouped_global_fn

    def set_global_stats_layout(self, v_total: int, v0: int,
                                g_total: int, g0: int) -> None:
        """Farm worker seam: declare where this shard's stat blocks and
        groups sit in the GLOBAL (V, G) layout so grouped per-point
        stats are computed through the zero-extended reference fold
        (see set_groups). Must run before the first window."""
        assert self._window == 0, "layout must be set before running"
        self._stats_layout = (int(v_total), int(v0),
                              int(g_total), int(g0))
        if self._group_ids is not None:
            self.set_groups(self._group_ids)

    # ------------------------------------------------------------------
    def _make_chunk_loop(self):
        """Pallas chunk loop for the kernel paths, method-resolved and
        chunk-budget-bound: (pool, tensors4, horizon) -> FusedWindowOut.
        Built lazily so kernel modules only import when use_kernel. A
        compiled tau kernel whose tables would not fit VMEM is refused
        here (ValueError; ExperimentError at the API)."""
        from repro.kernels import ops

        cfg = self.cfg
        if cfg.method == "tau_leap":
            from repro.kernels.ssa_step import check_tau_tables_fit

            check_tau_tables_fit(self.system.n_species,
                                 self.system.n_reactions)
            return partial(ops.tau_window_chunk_loop,
                           gi=self._gi_tab, rmask=self._rmask,
                           eps=cfg.tau_eps, fallback=cfg.tau_fallback,
                           max_c=(max(self.system.max_coef, 1)
                                  if cfg.sparse else MAX_COEF),
                           chunk_steps=cfg.kernel_chunk_steps,
                           max_chunks=cfg.kernel_max_chunks)
        if cfg.sparse:
            return partial(ops.sparse_window_chunk_loop,
                           sp=self._sparse_tensors,
                           chunk_steps=cfg.kernel_chunk_steps,
                           max_chunks=cfg.kernel_max_chunks)
        return partial(ops.window_chunk_loop,
                       chunk_steps=cfg.kernel_chunk_steps,
                       max_chunks=cfg.kernel_max_chunks)

    # ------------------------------------------------------------------
    def _make_advance_fn(self):
        """Per-lane-slice advance for the UNFUSED bodies (the encoding
        × method seam in one place): `advance(lane_slice, rates,
        horizon) -> LaneState`. Dense exact/tau iterate `_lane_step`;
        sparse exact runs the dependency-graph step with its carried
        propensity vector; sparse tau is `_lane_step` built with the
        gather-form Match. All bitwise identical to dense."""
        cfg = self.cfg
        idx_t, coef_t, delta_t, _ = self._tensors_base
        if cfg.sparse and cfg.method != "tau_leap":
            return make_advance_fn(None, None, cfg.max_steps_per_window,
                                   sparse=self._sparse_tensors)
        return make_advance_fn(self._lane_step,
                               (idx_t, coef_t, delta_t),
                               cfg.max_steps_per_window)

    # ------------------------------------------------------------------
    def _sketch_eval(self):
        """Jitted eager sketch for the paths whose dispatch does not
        produce one device-side (fused/host loop): obs (I, n_obs) ->
        (hist, rare). Same ops as the sharded in-body sketch, and
        integer counts, so the results are bitwise identical."""
        if self._sketch_fn_cache is None:
            sk = self._sketch
            gids = self._group_ids_dev
            n_g = self._n_groups if gids is not None else 1
            thr = sk.thresholds if sk.n_thr else None

            def fn(obs):
                g = (gids if gids is not None
                     else jnp.zeros((obs.shape[0],), jnp.int32))
                return window_sketch(obs, g, n_g, sk.lo, sk.width,
                                     sk.n_bins, thr)

            self._sketch_fn_cache = jax.jit(fn)
        return self._sketch_fn_cache

    # ------------------------------------------------------------------
    def _permutation(self) -> jax.Array:
        """Concatenated, padded scheduler groups as a device index map."""
        if self.scheduler.policy != "predictive" and \
                self._perm_cache is not None:
            return self._perm_cache
        perm = jnp.asarray(
            np.concatenate(self.scheduler.groups()).astype(np.int32))
        if self.scheduler.policy != "predictive":
            self._perm_cache = perm
        return perm

    # ------------------------------------------------------------------
    @property
    def pipeline_depth(self) -> int:
        """Resolved in-flight block budget. For pipeline_depth="auto"
        this is 1 until the first collected block's probe resolves it
        (resolve_auto_depth)."""
        return self._depth if self._depth is not None else 1

    @property
    def pipeline_depth_effective(self) -> int:
        """The depth the collector actually runs at. Steering forces
        lock-step (depth 1) regardless of the requested depth; this is
        the visible record of that forcing (Telemetry,
        recovery_report)."""
        return 1 if self._steer is not None else self.pipeline_depth

    def enable_block_partials(self) -> None:
        """Opt in to exporting the per-window blocked Welford PARTIALS
        (n, mean, m2 per stat block) alongside each pooled record. The
        multi-process farm worker needs them: its local records cover
        only its own instance rows, so the coordinator re-merges the
        partial stacks of all workers (in global block order) with the
        same merge_blocks + finalize fold to reproduce the
        single-process records bitwise. They ride the existing combined
        pull and the engine checkpoint (bp_* keys)."""
        self._export_partials = True

    def _grouped_partials_fn(self):
        """Jitted per-(block, group) masked partial stack over THIS
        engine's instance rows — exported so the farm coordinator can
        embed it into the global (V, G) partial layout and rerun the
        reference grouped merge bitwise."""
        if self._gp_fn is None:
            self._gp_fn = jax.jit(partial(
                reduction.blocked_grouped_welford,
                n_groups=self._n_groups,
                n_blocks=self._stats_blocks))
        return self._gp_fn

    def enable_snapshots(self) -> None:
        """Opt in to ring-snapshot checkpointing: every subsequent
        block dispatch first copies the pool (the dispatch donates its
        operand), so checkpoint() can save the oldest in-flight
        block's entry boundary — which IS the collected frontier —
        without flushing the pipeline. Costs one pool copy per
        dispatch; callers that never checkpoint mid-run leave it off."""
        self._snap_enabled = True

    def _cost_device(self) -> jax.Array:
        """Device-resident predictive cost carry for the in-scan
        regroup (float32, sharded like the pool). The host float64 EMA
        (scheduler._cost, updated at collect time) stays the canonical
        copy for checkpoints and parity; the device carry only decides
        grouping, which is execution packaging — any divergence in the
        low bits can reorder groups but never change a record."""
        if self._cost_dev is None:
            self._cost_dev = self._dispatch.place(
                jnp.asarray(self.scheduler._cost, jnp.float32))
        return self._cost_dev

    # ------------------------------------------------------------------
    def run_window(self) -> StatsRecord:
        """Advance every instance to the next grid point. All three
        schemas share this window loop — they differ in grouping policy
        (schema i: static_rr) and in what is buffered (i/ii: raw
        samples for post-hoc use; iii: nothing beyond the running
        accumulator). HOW the pool advances (host loop / fused /
        sharded) is the dispatch strategy's concern."""
        if self._pending:  # mixing with supersteps: drain them first
            self.flush()
        cfg = self.cfg
        win = self._window
        horizon = float(self.grid[win])
        with span("dispatch.enqueue", w0=win, n_win=1) as enqueue:
            res = self._dispatch.advance(horizon)
            if self.scheduler.policy == "predictive":
                steps_delta = res.steps_delta
                if steps_delta is not None and not isinstance(
                        steps_delta, np.ndarray):
                    steps_delta = np.asarray(steps_delta)
                    self.n_host_syncs += 1
                self.scheduler.record_costs(
                    np.arange(cfg.n_instances), steps_delta)
        self.watchdog.observe(win, enqueue.seconds)
        obs = res.obs
        with span("dispatch.fold", w0=win, n_win=1) as fold:
            stats = (res.stats if res.stats is not None
                     else reduction.blocked_stats(obs, self._stats_blocks))
            sk_dev = None
            if self._sketch is not None:
                sk_dev = (res.sketch if res.sketch is not None
                          else self._sketch_eval()(obs))
            bw_dev = (reduction.blocked_welford(obs, self._stats_blocks)
                      if self._export_partials else None)
            gp_dev = (self._grouped_partials_fn()(obs, self._group_ids_dev)
                      if self._export_partials
                      and self._group_ids is not None else None)
            # ONE combined blocking pull per window: record stats +
            # per-method step/leap telemetry + (on the kernel path) the
            # truncation scalar and chunk count — a separate pull of
            # the flag would cost the kernel path a second host sync
            # per window
            fetch = dict(
                mean=stats.mean, var=stats.var, ci90=stats.ci90,
                n=stats.n, steps=self._pool.steps.sum(),
                leaps=self._pool.leaps.sum(),
                **({} if res.truncated is None
                   else {"truncated": res.truncated}),
                **({} if res.n_chunks is None
                   else {"n_chunks": res.n_chunks}),
                **({} if res.shard_steps is None
                   else {"shard_steps": res.shard_steps}),
                **({} if sk_dev is None else {"sk_hist": sk_dev[0]}),
                **({} if sk_dev is None or sk_dev[1] is None
                   else {"sk_rare": sk_dev[1]}),
                **({} if bw_dev is None
                   else {"bw_n": bw_dev.n, "bw_mean": bw_dev.mean,
                         "bw_m2": bw_dev.m2}),
                **({} if gp_dev is None
                   else {"gp_n": gp_dev.n, "gp_mean": gp_dev.mean,
                         "gp_m2": gp_dev.m2}))
        with span("collect.pull", w0=win, n_win=1) as pull_span:
            pulled = jax.device_get(fetch)
        self.n_host_syncs += 1
        with span("collect.reduce", w0=win, n_win=1) as reduce_span:
            rec = self._emit_window(pulled, res, obs, horizon)
            self._annotate_reduce(reduce_span, 1, "n_chunks" in pulled)
        # window-level walls ARE measurable here: one block_walls row
        # per window (Telemetry.block_walls)
        self.block_walls.append(
            (win, 1, enqueue.seconds + fold.seconds,
             pull_span.seconds + reduce_span.seconds))
        self._window += 1
        self._dispatched = self._window
        return rec

    def _annotate_reduce(self, reduce_span, n_win: int,
                         chunks: bool) -> None:
        """Put the collected windows' events (and, on the kernel paths,
        chunk counts) on the reduce span: host values already pulled."""
        attrs = dict(events=self.window_steps[-n_win:])
        if chunks:
            attrs["chunks"] = self.window_chunks[-n_win:]
        reduce_span.annotate(**attrs)

    def _count_steps(self, steps, leaps, shard_steps) -> None:
        """One window's step, leap and per-shard step counts from the
        cumulative device sums pulled with its record."""
        # the device sums are int32 and wrap once pool-wide cumulative
        # counts pass 2^31; tracking residues mod 2^32 and taking
        # modular deltas keeps every per-window value exact (a single
        # window's work is far below 2^31)
        steps_cum = int(steps) & 0xFFFFFFFF
        leaps_cum = int(leaps) & 0xFFFFFFFF
        self.window_steps.append(
            (steps_cum - self._cum_steps) & 0xFFFFFFFF)
        self.window_leaps.append(
            (leaps_cum - self._cum_leaps) & 0xFFFFFFFF)
        self._cum_steps, self._cum_leaps = steps_cum, leaps_cum
        if shard_steps is None:
            self.window_shard_steps.append((self.window_steps[-1],))
            return
        cum = np.asarray(shard_steps, np.int64) & 0xFFFFFFFF
        self.window_shard_steps.append(tuple(
            int(v) for v in (cum - self._cum_shard_steps) & 0xFFFFFFFF))
        self._cum_shard_steps = cum

    def _emit_window(self, pulled: dict, res, obs,
                     horizon: float) -> StatsRecord:
        """Host-side reduction of one pulled window (run_window): the
        guards, telemetry and optional products, then the record."""
        cfg = self.cfg
        if bool(pulled.get("truncated", False)):
            # a silently partial window must never become a record
            self._raise_truncated(self._window, horizon)
        if cfg.guards:
            self._guard_stats(self._window, pulled["mean"], pulled["var"])
        self._count_steps(pulled["steps"], pulled["leaps"],
                          pulled.get("shard_steps"))
        if "n_chunks" in pulled:
            self.window_chunks.append(int(pulled["n_chunks"]))
        if "sk_hist" in pulled:
            self._sketches.append(WindowSketch(
                hist=np.asarray(pulled["sk_hist"]),
                rare=(np.asarray(pulled["sk_rare"])
                      if "sk_rare" in pulled else None)))
        if "bw_n" in pulled:
            self._block_partials.append(reduction.Welford(
                n=np.asarray(pulled["bw_n"]),
                mean=np.asarray(pulled["bw_mean"]),
                m2=np.asarray(pulled["bw_m2"])))
        if "gp_n" in pulled:
            self._grouped_partials.append(reduction.Welford(
                n=np.asarray(pulled["gp_n"]),
                mean=np.asarray(pulled["gp_mean"]),
                m2=np.asarray(pulled["gp_m2"])))
        if cfg.schema in ("i", "ii") or self._record_trajectories:
            self._samples.append(np.asarray(obs))
            self.n_host_syncs += 1
            self._peak_buffered = max(
                self._peak_buffered,
                sum(s.nbytes for s in self._samples))
        else:  # schema iii: on-line reduction, window dropped immediately
            self._peak_buffered = max(self._peak_buffered, obs.nbytes)
        if self._grouped_fn is not None:
            g = (res.grouped if res.grouped is not None
                 else self._grouped_fn(obs, self._group_ids_dev))
            self._grouped.append(
                reduction.Stats(*(np.asarray(v) for v in g)))
            self.n_host_syncs += 1
        rec = StatsRecord(
            t=horizon, window=self._window,
            mean=pulled["mean"], var=pulled["var"],
            ci90=pulled["ci90"], n=float(pulled["n"].max()))
        self.stream.emit(rec)
        return rec

    # -------------------------------------------------- supersteps
    def _raise_truncated(self, window: int, horizon: float):
        """The one FusedWindowTruncated raise for both the per-window
        and the superstep collect path. Everything still in flight was
        dispatched from the truncated (partial-window) pool, so the
        pipeline is dropped first — no later accessor's flush() may
        re-raise from a getter or turn the invalid state into records —
        and the dispatch cursor rewinds to the collected frontier so a
        caller that catches the error and drives on re-runs from the
        failed window instead of silently skipping the dropped ones
        (the per-window path has this property for free)."""
        from repro.kernels.ops import FusedWindowTruncated

        self._pending.clear()
        self._dispatched = self._window
        self._cost_dev = None  # advanced past the dropped blocks
        cfg = self.cfg
        raise FusedWindowTruncated(
            f"window {window} (horizon {horizon:g}) exhausted "
            f"kernel_max_chunks={cfg.kernel_max_chunks} x "
            f"kernel_chunk_steps={cfg.kernel_chunk_steps} events with "
            "live lanes still below the horizon; raise those limits "
            "or use more windows")

    def _raise_invariant(self, window: int, check: str, detail: str):
        """Typed invariant-guard raise, shared by the per-window and
        superstep collect paths. Same in-flight hygiene as
        _raise_truncated: the pipeline was dispatched from the
        now-untrusted pool, so it is dropped, and the dispatch cursor
        rewinds to the collected frontier — a supervisor that catches
        the error restores a checkpoint and replays from there."""
        self._pending.clear()
        self._dispatched = self._window
        self._cost_dev = None  # advanced past the dropped blocks
        raise InvariantViolation(
            f"engine invariant {check!r} violated at window {window}: "
            f"{detail} — the pool state is untrusted; recover from the "
            "last checkpoint (set SimConfig.guards=False to disable)",
            window=window, check=check)

    def _guard_stats(self, window: int, mean, var) -> None:
        """Cheap host-side invariant checks on the per-window moments
        the collect path already pulled (no extra device syncs).
        Observables are sums of species counts, so a finite simulation
        can only produce finite, non-negative means; NaN/inf means a
        poisoned pool (propensity overflow, bad rates, fault
        injection), a negative mean means population underflow."""
        mean = np.asarray(mean)
        var = np.asarray(var)
        if not (np.isfinite(mean).all() and np.isfinite(var).all()):
            self._raise_invariant(
                window, "non_finite_stats",
                "window statistics contain NaN/inf (non-finite "
                "propensities or poisoned lane state)")
        if (mean < 0.0).any():
            self._raise_invariant(
                window, "negative_population",
                f"window mean dipped below zero (min {mean.min():g}); "
                "species counts can never be negative")

    def _next_block_windows(self, limit: int) -> int:
        """Size of the next superstep: realigned to the absolute
        window_block grid (so an in-process mid-grid start — a
        max_windows cut, or a restore from a window_block boundary —
        converges back onto block boundaries; restore() itself rejects
        MID-block checkpoints), capped by the grid end and the caller's
        dispatch limit."""
        w0 = self._dispatched
        wb = self.cfg.window_block
        return min(wb - (w0 % wb), len(self.grid) - w0, limit - w0)

    def _dispatch_block(self, limit: int) -> None:
        """Launch the next W windows as ONE device dispatch and queue
        the resulting record ring for a later (pipelined) pull. The
        per-window statistics folds run here EAGERLY on device arrays —
        the same op sequence the per-window path uses — and are queued
        with the ring; `copy_to_host_async` starts their device->host
        movement so the blocking `device_get` in _collect_block mostly
        finds the bytes already on host. The two phases are the spans
        `repro.dispatch.enqueue` (the jitted block call) and
        `repro.dispatch.fold` (the folds and copies)."""
        w0 = self._dispatched
        n_win = self._next_block_windows(limit)
        horizons = self.grid[w0:w0 + n_win]
        snapshot = None
        if self._snap_enabled:
            # the dispatch donates the pool, so the copy of this
            # block's ENTRY boundary must happen before it; outside
            # the dispatch spans — it is checkpoint overhead, not
            # enqueue wall
            snapshot = jax.tree_util.tree_map(jnp.copy, self._pool)
        with span("dispatch.enqueue", w0=w0, n_win=n_win) as enqueue:
            res = self._dispatch.advance_block(horizons)
        with span("dispatch.fold", w0=w0, n_win=n_win) as fold:
            pull = self._fold_block(res, n_win)
        # per-ring memory accounting: EVERY queued ring (and snapshot)
        # is live simultaneously at depth K, so peak_buffered must see
        # their sum, not one block's footprint
        ring_bytes = sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree_util.tree_leaves((pull, snapshot)))
        self._pending.append(_InFlight(
            w0, n_win, pull, enqueue.seconds + fold.seconds,
            res.obs.nbytes // n_win, ring_bytes, snapshot))
        self._dispatched = w0 + n_win
        self.peak_inflight_blocks = max(
            self.peak_inflight_blocks, len(self._pending))
        self._peak_buffered = max(
            self._peak_buffered,
            sum(e.ring_bytes for e in self._pending)
            + sum(s.nbytes for s in self._samples))

    def _fold_block(self, res, n_win: int) -> dict:
        """The block's per-window statistic folds, run eagerly on the
        ring's device arrays (the per-window path's op sequence), and
        the async device-to-host copies of everything the collector
        will pull: the pull dict _collect_block reads."""
        cfg = self.cfg
        stats = (res.stats if res.stats is not None else [
            reduction.blocked_stats(res.obs[w], self._stats_blocks)
            for w in range(n_win)])
        pull = dict(stats=stats, steps=res.steps_end,
                    leaps=res.leaps_end)
        if self._grouped_fn is not None:
            pull["grouped"] = (res.grouped if res.grouped is not None
                               else [self._grouped_fn(
                                   res.obs[w], self._group_ids_dev)
                                   for w in range(n_win)])
        if self._sketch is not None:
            if res.sketch is not None:  # sharded: rode the ring (psum)
                pull["sk_hist"] = res.sketch[0]
                if res.sketch[1] is not None:
                    pull["sk_rare"] = res.sketch[1]
            else:  # fused: eager per-window sketch from the obs ring
                per = [self._sketch_eval()(res.obs[w])
                       for w in range(n_win)]
                pull["sk_hist"] = [p[0] for p in per]
                if per and per[0][1] is not None:
                    pull["sk_rare"] = [p[1] for p in per]
        if self._export_partials:
            bw = [reduction.blocked_welford(res.obs[w], self._stats_blocks)
                  for w in range(n_win)]
            pull["bw_n"] = [b.n for b in bw]
            pull["bw_mean"] = [b.mean for b in bw]
            pull["bw_m2"] = [b.m2 for b in bw]
            if self._group_ids is not None:
                gp = [self._grouped_partials_fn()(
                    res.obs[w], self._group_ids_dev)
                    for w in range(n_win)]
                pull["gp_n"] = [g.n for g in gp]
                pull["gp_mean"] = [g.mean for g in gp]
                pull["gp_m2"] = [g.m2 for g in gp]
        if res.truncated is not None:
            pull["truncated"] = res.truncated
        if cfg.schema in ("i", "ii") or self._record_trajectories:
            pull["obs"] = res.obs
        if res.steps_delta is not None:
            pull["steps_delta"] = res.steps_delta
        if res.n_chunks is not None:
            pull["n_chunks"] = res.n_chunks
        if res.shard_steps is not None:
            pull["shard_steps"] = res.shard_steps
        for leaf in jax.tree_util.tree_leaves(pull):
            copy = getattr(leaf, "copy_to_host_async", None)
            if callable(copy):
                copy()
        return pull

    def _collect_block(self) -> None:
        """Blocking pull + host-side reduction of the OLDEST in-flight
        superstep: ONE combined device_get for the whole ring (stats,
        telemetry, truncation, optional samples/grouped), then the
        exact per-window record emission the per-window path performs:
        the spans `repro.collect.pull` and `repro.collect.reduce`."""
        cfg = self.cfg
        ent = self._pending.popleft()
        w0, n_win = ent.w0, ent.n_win
        if (ent.snapshot is not None and cfg.window_block > 1
                and w0 % cfg.window_block == 0
                and (w0 + n_win) % cfg.window_block):
            # this block was cut short (a max_windows dispatch limit):
            # keep its aligned ENTRY snapshot so a later checkpoint()
            # at the mid-block frontier can serve a boundary-aligned
            # save instead of a file restore() would reject
            self._aligned_snap = (w0, ent.snapshot)
        with span("collect.pull", w0=w0, n_win=n_win) as pull_span:
            pulled = jax.device_get(ent.pull)
        self.n_host_syncs += 1
        with span("collect.reduce", w0=w0, n_win=n_win) as reduce_span:
            # per-window walls are NOT measurable under block dispatch
            # (one enqueue + one ring pull covers the whole block): feed
            # the watchdog ONE block-level sample at per-window scale
            # instead of n_win identical slices that would poison its
            # median
            self.watchdog.observe_block(
                w0, n_win, ent.dispatch_wall + pull_span.seconds)
            self._emit_block(pulled, w0, n_win, ent.obs_row_bytes)
            self._annotate_reduce(reduce_span, n_win, "n_chunks" in pulled)
        pull_s, host_s = pull_span.seconds, reduce_span.seconds
        self.block_walls.append((w0, n_win, ent.dispatch_wall,
                                 pull_s + host_s))
        if self._depth is None:
            # pipeline_depth="auto": the first collect ran at depth 1
            # (cold pipeline), so pull_s approximates one block's
            # remaining device+transfer time and host_s the collector
            # work to hide behind it
            self._depth = resolve_auto_depth(pull_s, host_s)
            self.depth_probe = dict(
                dispatch_s=ent.dispatch_wall, pull_s=pull_s,
                host_s=host_s,
                collect_dispatch_ratio=(
                    (pull_s + host_s) / max(ent.dispatch_wall, 1e-9)),
                depth=self._depth)

    def _emit_block(self, pulled: dict, w0: int, n_win: int,
                    obs_row_bytes: int) -> None:
        """The collector's host-side reduction of one pulled ring: the
        exact per-window record emission the per-window path performs,
        with the guards, telemetry and optional products of each
        window."""
        cfg = self.cfg
        trunc = pulled.get("truncated")
        if cfg.guards and (len(pulled["stats"]) != n_win
                           or len(pulled["steps"]) != n_win):
            # ring/record disagreement: the device ring and the queued
            # block descriptor no longer agree on the window count
            self._raise_invariant(
                w0, "ring_record_mismatch",
                f"superstep ring holds {len(pulled['stats'])} stat "
                f"rows / {len(pulled['steps'])} telemetry rows for a "
                f"{n_win}-window block at window {w0}")
        for w in range(n_win):
            if trunc is not None and trunc[w]:
                self._raise_truncated(w0 + w, float(self.grid[w0 + w]))
            if cfg.guards:
                s_w = pulled["stats"][w]
                self._guard_stats(w0 + w, s_w.mean, s_w.var)
            self._count_steps(
                pulled["steps"][w], pulled["leaps"][w],
                pulled["shard_steps"][w] if "shard_steps" in pulled
                else None)
            if "n_chunks" in pulled:
                self.window_chunks.append(int(pulled["n_chunks"][w]))
            if "obs" in pulled:
                self._samples.append(np.asarray(pulled["obs"][w]))
                self._peak_buffered = max(
                    self._peak_buffered,
                    sum(s.nbytes for s in self._samples))
            else:
                self._peak_buffered = max(self._peak_buffered,
                                          obs_row_bytes)
            if "grouped" in pulled:
                self._grouped.append(reduction.Stats(
                    *(np.asarray(v) for v in pulled["grouped"][w])))
            if "sk_hist" in pulled:
                self._sketches.append(WindowSketch(
                    hist=np.asarray(pulled["sk_hist"][w]),
                    rare=(np.asarray(pulled["sk_rare"][w])
                          if "sk_rare" in pulled else None)))
            if "bw_n" in pulled:
                self._block_partials.append(reduction.Welford(
                    n=np.asarray(pulled["bw_n"][w]),
                    mean=np.asarray(pulled["bw_mean"][w]),
                    m2=np.asarray(pulled["bw_m2"][w])))
            if "gp_n" in pulled:
                self._grouped_partials.append(reduction.Welford(
                    n=np.asarray(pulled["gp_n"][w]),
                    mean=np.asarray(pulled["gp_mean"][w]),
                    m2=np.asarray(pulled["gp_m2"][w])))
            if "steps_delta" in pulled:
                # per-window EMA updates in window order — the cost
                # state at every block boundary matches the per-window
                # path's; regrouping itself waits for the next block
                self.scheduler.record_costs(
                    np.arange(cfg.n_instances),
                    np.asarray(pulled["steps_delta"][w]))
            s = pulled["stats"][w]
            rec = StatsRecord(
                t=float(self.grid[w0 + w]), window=w0 + w,
                mean=s.mean, var=s.var, ci90=s.ci90,
                n=float(s.n.max()))
            self.stream.emit(rec)
            self._window += 1

    def run_block(self, dispatch_limit: Optional[int] = None,
                  pipeline: bool = True) -> int:
        """One turn of the pipelined superstep loop (window_block > 1):
        dispatch the next window block if any remains below
        `dispatch_limit` (an absolute window index), then collect the
        oldest in-flight block once more than `pipeline_depth` blocks
        are queued behind it — or once dispatching is done — so
        host-side reduction and sinks for block k run while the device
        simulates blocks k+1..k+K. Depth only changes WHEN rings are
        pulled, never what was computed: records are bitwise identical
        for any depth. With `pipeline=False` the freshly dispatched
        block is collected immediately (no dispatch-ahead) — the
        strict lock-step mode steering relies on. Callers that
        checkpoint per block no longer need it: enable_snapshots() +
        checkpoint() saves the collected frontier while blocks stay in
        flight. Returns the number of windows collected this call.

        With steering active the pipeline is forced off: the policy's
        decision point must see block k's records BEFORE block k+1 is
        dispatched (a dispatch-ahead block would run on pre-decision
        state), and the decision is applied here, at the collected
        boundary."""
        if self._steer is not None:
            pipeline = False
        limit = len(self.grid)
        if dispatch_limit is not None:
            limit = min(limit, dispatch_limit)
        if self._dispatched < limit:
            self._dispatch_block(limit)
        before = self._window
        depth = self.pipeline_depth  # "auto" acts as 1 until resolved
        if self._pending and (not pipeline
                              or len(self._pending) > depth
                              or self._dispatched >= limit):
            self._collect_block()
        collected = self._window - before
        if (self._steer is not None and collected and not self._pending
                and self._dispatched == self._window
                and self._window < len(self.grid)):
            self._steer_boundary()
        return collected

    def flush(self) -> None:
        """Collect every in-flight superstep so the emitted records
        catch up with the dispatched pool state (checkpoint() forces
        this — saves always land on a window boundary)."""
        while self._pending:
            self._collect_block()

    # --------------------------------------------------------- steering
    def _steer_boundary(self) -> None:
        """One decision point: hand the policy the freshest per-point
        stats, the latest window sketch, and the exact per-lane
        step/leap counters, then apply whatever it decides. Every input
        is bitwise path-invariant, so the decision sequence is too."""
        pulled = jax.device_get(dict(steps=self._pool.steps,
                                     leaps=self._pool.leaps))
        self.n_host_syncs += 1
        # int32 device counters wrap at 2^31; keep the unsigned residue
        # so the policy's deltas stay exact mod 2^32
        steps = np.asarray(pulled["steps"]).astype(np.int64) & 0xFFFFFFFF
        leaps = np.asarray(pulled["leaps"]).astype(np.int64) & 0xFFFFFFFF
        if self._grouped:
            g = self._grouped[-1]
            point_stats = {"mean": np.asarray(g.mean),
                           "ci90": np.asarray(g.ci90)}
        elif self.stream.records():
            r = self.stream.records()[-1]
            point_stats = {"mean": np.asarray(r.mean),
                           "ci90": np.asarray(r.ci90)}
        else:
            point_stats = None
        hist = self._sketches[-1].hist if self._sketches else None
        gids = (self._group_ids if self._group_ids is not None
                else np.zeros(self.cfg.n_instances, np.int32))
        actions = self._steer.decide(self._window, point_stats, hist,
                                     gids, steps, leaps)
        if actions.any:
            self._apply_steering(actions)

    def _apply_steering(self, a: SteeringActions) -> None:
        """Apply a decision to the device pool. Pull-edit-replace: the
        pool is tiny next to a window's compute and decision points are
        rare, so one gather + one re-place (resharding under the
        sharded strategy) beats a bespoke jitted scatter here."""
        arrs = {f: np.array(getattr(self._pool, f))  # writable copies
                for f in LaneState._fields}
        self.n_host_syncs += 1
        if np.asarray(a.stop_lanes).any():
            arrs["dead"] = arrs["dead"] | np.asarray(a.stop_lanes)
        moves = np.asarray(a.moves)
        if moves.size:
            dst, src = moves[:, 0], moves[:, 1]
            # trajectory splitting: clone the donor's state, keep the
            # moved lane's OWN RNG stream (key/ctr) — it diverges from
            # the donor immediately, an extra replica from here on
            for f in ("x", "t", "dead"):
                arrs[f][dst] = arrs[f][src]
            self.rates = np.array(self.rates)
            self.rates[dst] = self.rates[src]
            self._rates_dev = self._dispatch.place(
                jnp.asarray(self.rates))
            self.scheduler._cost[dst] = self.scheduler._cost[src]
            self._cost_dev = None  # host rewrote cost out-of-band
        if a.no_leap is not None:
            arrs["no_leap"] = np.asarray(a.no_leap, bool)
        self._pool = self._dispatch.place(LaneState(
            **{f: jnp.asarray(v) for f, v in arrs.items()}))
        if a.new_group_ids is not None:
            # every point keeps >= 1 lane, so n_groups is unchanged and
            # the sharded dispatch cache stays valid; only the operand
            # content changes
            self.set_groups(np.asarray(a.new_group_ids))
            self._group_ids_dev = self._dispatch.place(
                self._group_ids_dev)

    def sketches(self) -> list[WindowSketch]:
        """Per-window WindowSketch list (empty without a SketchSpec)."""
        self.flush()
        return list(self._sketches)

    def steering_report(self) -> Optional[dict]:
        """The policy's savings + decision summary (None when no
        steering is active)."""
        if self._steer is None:
            return None
        self.flush()
        return self._steer.report()

    def _observe(self) -> jax.Array:
        cols = [self._pool.x[:, idx].sum(axis=1) for idx in self.obs_idx]
        return jnp.stack(cols, axis=1)

    def run(self) -> list[StatsRecord]:
        # steered runs go through the block loop even at window_block=1
        # (steering decisions live at the collected block boundary)
        if self.cfg.window_block == 1 and self._steer is None:
            while self._window < len(self.grid):
                self.run_window()
        else:
            while self._window < len(self.grid):
                self.run_block()
        return self.stream.records()

    # ------------------------------------------------------------ fault
    def checkpoint(self, path: str) -> None:
        """One-file snapshot: pool + scheduler + emitted records (+ any
        buffered samples/grouped stats). Cost is O(pool + buffered
        state): constant per call under schema iii (nothing is
        buffered), but grows with the sample buffer under schemas
        i/ii — prefer schema iii for per-window checkpointing.

        Gather-on-save: `np.asarray` on a sharded pool gathers the
        global arrays, so the file never depends on the mesh shape —
        any engine (any shard count) can restore it.

        Supersteps: with ring snapshots enabled (enable_snapshots),
        the oldest in-flight block's ENTRY snapshot is the pool at the
        collected frontier — exactly the boundary every already-emitted
        record agrees on — so the save happens WITHOUT flushing the
        pipeline and later blocks keep computing underneath it.
        Without snapshots (or with nothing in flight) saving flushes
        first, as before: every in-flight block is collected so the
        saved pool and the saved records agree on one boundary.

        A `max_windows` cut landing MID-block (frontier not on a
        window_block boundary) rolls the save back to the cut block's
        aligned ENTRY snapshot (kept by _collect_block): the file then
        sits on a block boundary and always restores under the run's
        own window_block, and the save still never flushes
        (ckpt_flushes stays 0 for mid-block cuts too). Histories are
        truncated to the rolled-back window; resume re-runs the tail."""
        p = None
        win = self._window
        if self._pending:
            snap = self._pending[0].snapshot
            if snap is not None:
                # invariant: blocks collect in order, so the oldest
                # pending block's first window IS self._window
                assert self._pending[0].w0 == self._window
                p = snap
                self.n_snapshot_saves += 1
            else:
                self.n_ckpt_flushes += 1
        if p is None:
            self.flush()
            p = self._pool
            win = self._window
            wb = self.cfg.window_block
            if wb > 1 and win % wb and win != len(self.grid):
                aligned = getattr(self, "_aligned_snap", None)
                if aligned is not None and aligned[0] == win - win % wb:
                    win, p = aligned
                    self.n_snapshot_saves += 1
        extra = {}
        recs = [r for r in self.stream.records() if r.window < win]
        if recs:
            extra = dict(
                rec_t=np.asarray([r.t for r in recs], np.float64),
                rec_window=np.asarray([r.window for r in recs], np.int64),
                rec_mean=np.stack([r.mean for r in recs]),
                rec_var=np.stack([r.var for r in recs]),
                rec_ci90=np.stack([r.ci90 for r in recs]),
                rec_n=np.asarray([r.n for r in recs], np.float64))
        if self._samples[:win]:
            extra["samples"] = np.stack(self._samples[:win], axis=1)
        if self._grouped[:win]:
            for name in ("n", "mean", "var", "ci90"):
                extra[f"grouped_{name}"] = np.stack(
                    [getattr(g, name) for g in self._grouped[:win]])
        if self._sketches[:win]:
            extra["sketch_hist"] = np.stack(
                [s.hist for s in self._sketches[:win]])
            if self._sketches[0].rare is not None:
                extra["sketch_rare"] = np.stack(
                    [s.rare for s in self._sketches[:win]])
        if self._block_partials[:win]:
            for name in ("n", "mean", "m2"):
                extra[f"bp_{name}"] = np.stack(
                    [getattr(b, name)
                     for b in self._block_partials[:win]])
        if self._grouped_partials[:win]:
            for name in ("n", "mean", "m2"):
                extra[f"gpp_{name}"] = np.stack(
                    [getattr(b, name)
                     for b in self._grouped_partials[:win]])
        if self._group_ids is not None:
            # steering reallocation rewrites the lane->point map, so it
            # is run state, not just construction input
            extra["group_ids"] = self._group_ids
        if self._steer is not None:
            for k, v in self._steer.state_dict().items():
                extra[f"steer_{k}"] = v
        # atomic + checksummed (ckpt.store.save_atomic): a crash
        # mid-save never clobbers the previous snapshot, and restore
        # detects truncation/corruption instead of loading garbage
        ckpt_store.save_atomic(path, dict(
            x=np.asarray(p.x), t=np.asarray(p.t),
            key=np.asarray(p.key), ctr=np.asarray(p.ctr),
            ctr_hi=np.asarray(p.ctr_hi),
            steps=np.asarray(p.steps), leaps=np.asarray(p.leaps),
            dead=np.asarray(p.dead), no_leap=np.asarray(p.no_leap),
            window=win,
            cost=self.scheduler._cost, rates=self.rates, **extra))

    def restore(self, path: str) -> None:
        # integrity-checked load (ckpt.store.verify): truncated or
        # garbage files raise a typed CheckpointCorrupt naming the path
        # and the failure instead of surfacing a raw numpy/KeyError;
        # pre-hardening magic-less snapshots still load
        z = ckpt_store.verify(
            path, required=("x", "t", "key", "steps", "dead",
                            "window", "cost"))
        # supersteps advance window_block windows per dispatch, so a
        # resume must start on a block boundary of THIS engine's grid;
        # a checkpoint cut mid-block (e.g. by a max_windows stop under
        # a different window_block) is rejected up front, before any
        # state is touched
        saved_window = int(z["window"])
        wb = self.cfg.window_block
        if wb > 1 and saved_window % wb and saved_window != len(self.grid):
            raise ValueError(
                f"checkpoint at window {saved_window} is mid-block for "
                f"window_block={wb}: supersteps advance {wb} windows "
                "per dispatch, so resume needs a checkpoint on a "
                "window_block boundary — resume with window_block=1 "
                f"(or a divisor of {saved_window}), or re-save the "
                "checkpoint at a multiple of window_block")
        self._pending.clear()  # in-flight rings predate the restore
        self._aligned_snap = None
        self._cost_dev = None  # reseed the in-scan carry from `cost`
        # reshard-on-restore: checkpoints hold the gathered global pool
        # (mesh-shape-agnostic); the current dispatch re-places it on
        # whatever mesh THIS engine runs on
        # pre-counter-RNG checkpoints carry no `ctr`: restart those
        # streams at draw 0 (still exact SSA by memorylessness, but not
        # bitwise vs an uninterrupted pre-upgrade run); pre-widening
        # checkpoints carry no `ctr_hi`/`leaps`: restore with the high
        # word (and leap count) 0 — bitwise, since every stream below
        # 2^32 draws has hi = 0 by construction
        n = z["t"].shape[0]
        ctr = z["ctr"] if "ctr" in z else np.zeros((n,), np.uint32)
        ctr_hi = z["ctr_hi"] if "ctr_hi" in z else np.zeros((n,), np.uint32)
        leaps = z["leaps"] if "leaps" in z else np.zeros((n,), np.int32)
        # pre-steering checkpoints carry no `no_leap`: no lane was
        # pinned, so all-False restores bitwise
        no_leap = z["no_leap"] if "no_leap" in z else np.zeros((n,), bool)
        self._pool = self._dispatch.place(LaneState(
            x=jnp.asarray(z["x"]), t=jnp.asarray(z["t"]),
            key=jnp.asarray(z["key"]), ctr=jnp.asarray(ctr),
            ctr_hi=jnp.asarray(ctr_hi),
            steps=jnp.asarray(z["steps"]), leaps=jnp.asarray(leaps),
            dead=jnp.asarray(z["dead"]),
            no_leap=jnp.asarray(no_leap, bool)))
        self._window = saved_window
        self._dispatched = saved_window
        # per-window telemetry restarts from the restored cumulative
        # counts (deltas stay per-window, not since-process-start);
        # same mod-2^32 residue the wrapping device int32 sums produce
        self.window_steps, self.window_leaps = [], []
        self.window_chunks = []
        self.window_shard_steps = []
        lane_steps = np.asarray(z["steps"], np.int64)
        self._cum_steps = int(lane_steps.sum()) & 0xFFFFFFFF
        self._cum_shard_steps = lane_steps.reshape(
            self.n_shards, -1).sum(axis=1) & 0xFFFFFFFF
        self._cum_leaps = int(
            np.asarray(leaps, np.int64).sum()) & 0xFFFFFFFF
        self.scheduler._cost = z["cost"]
        if "rates" in z:
            self.rates = np.asarray(z["rates"], np.float32)
            self._rates_dev = self._dispatch.place(jnp.asarray(self.rates))
        if "group_ids" in z:
            # the saved map reflects any steering reallocations
            self.set_groups(np.asarray(z["group_ids"], np.int32))
            self._group_ids_dev = self._dispatch.place(
                self._group_ids_dev)
        if self._steer is not None:
            st = {k[len("steer_"):]: z[k] for k in z
                  if k.startswith("steer_")}
            if st:
                self._steer.load_state(st)
        # re-populate already-emitted records (buffer only — sinks are
        # not replayed so a resumed CSV does not double-write)
        self.stream.buffer.clear()
        if "rec_t" in z:
            for i in range(len(z["rec_t"])):
                self.stream.buffer.append(StatsRecord(
                    t=float(z["rec_t"][i]), window=int(z["rec_window"][i]),
                    mean=z["rec_mean"][i], var=z["rec_var"][i],
                    ci90=z["rec_ci90"][i], n=float(z["rec_n"][i])))
        if "samples" in z:
            s = z["samples"]
            self._samples = [s[:, w] for w in range(s.shape[1])]
        else:
            self._samples = []
        if "grouped_n" in z:
            self._grouped = [
                reduction.Stats(n=z["grouped_n"][w], mean=z["grouped_mean"][w],
                                var=z["grouped_var"][w],
                                ci90=z["grouped_ci90"][w])
                for w in range(len(z["grouped_n"]))]
        else:
            self._grouped = []
        if "sketch_hist" in z:
            sh = z["sketch_hist"]
            sr = z["sketch_rare"] if "sketch_rare" in z else None
            self._sketches = [WindowSketch(
                hist=sh[w], rare=(sr[w] if sr is not None else None))
                for w in range(len(sh))]
        else:
            self._sketches = []
        if "bp_n" in z:
            self._block_partials = [
                reduction.Welford(n=z["bp_n"][w], mean=z["bp_mean"][w],
                                  m2=z["bp_m2"][w])
                for w in range(len(z["bp_n"]))]
        else:
            self._block_partials = []
        if "gpp_n" in z:
            self._grouped_partials = [
                reduction.Welford(n=z["gpp_n"][w], mean=z["gpp_mean"][w],
                                  m2=z["gpp_m2"][w])
                for w in range(len(z["gpp_n"]))]
        else:
            self._grouped_partials = []

    @property
    def peak_buffered_bytes(self) -> int:
        return self._peak_buffered

    def trajectories(self) -> Optional[np.ndarray]:
        """(I, T, n_obs) raw samples. Buffered for schemas i/ii; for
        schema iii only when record_trajectories was requested."""
        self.flush()
        if not self._samples:
            return None
        return np.stack(self._samples, axis=1)

    def grouped_stats(self) -> list[reduction.Stats]:
        """Per-window grouped Stats ((n_groups, n_obs) leaves) when a
        grouped reduction is enabled via set_groups()."""
        self.flush()
        return list(self._grouped)
