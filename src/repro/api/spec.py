"""Typed experiment specification (the declarative front-end).

Everything the old imperative surface expressed through `SimConfig`
kwargs and stringly-typed `"i"/"ii"/"iii"` schemas is a validated,
composable spec here (see DESIGN.md for the migration table). A spec is
pure data: building one performs no compilation and touches no device —
`simulate()` does that. The one stateful exception is `sinks`: those
are live callables (a CsvSink opens its file when constructed and is
closed when the run completes), so build fresh sinks per simulate()
call rather than reusing one spec's sinks across runs.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from dataclasses import dataclass
from enum import Enum
from typing import Optional, Sequence, Union

import numpy as np

from repro.core.cwc.rules import CWCModel
from repro.core.dispatch import Partitioning
from repro.core.reactions import ReactionSystem
from repro.core.sweep import SweepSpec
from repro.runtime.supervisor import Recovery
from repro.stats.sketch import SketchSpec
from repro.steer.policy import Steering

__all__ = [
    "Ensemble", "Experiment", "ExperimentError", "Method",
    "Partitioning", "Policy", "Recovery", "Reduction", "Schedule",
    "Schema", "SketchSpec", "Steering",
]


class ExperimentError(ValueError):
    """A spec failed validation; the message names the offending field."""


class Schema(Enum):
    """The paper's three parallelisation schemas (Fig. 5)."""

    STATIC_FARM = "i"       # static farm, post-hoc reduction
    TIME_SLICED = "ii"      # self-balancing farm, post-hoc reduction
    ONLINE = "iii"          # time-sliced farm + on-line windowed reduction

    @classmethod
    def coerce(cls, v: Union["Schema", str]) -> "Schema":
        if isinstance(v, cls):
            return v
        for member in cls:
            if v in (member.value, member.name, member.name.lower()):
                return member
        raise ExperimentError(
            f"unknown schema {v!r}; expected one of "
            f"{[m.value for m in cls]} or {[m.name for m in cls]}")


class Policy(Enum):
    """Lane-grouping policy for the scheduler."""

    STATIC_RR = "static_rr"
    ON_DEMAND = "on_demand"
    PREDICTIVE = "predictive"  # EMA-cost-sorted groups (§5.2 heuristics)

    @classmethod
    def coerce(cls, v: Union["Policy", str]) -> "Policy":
        if isinstance(v, cls):
            return v
        for member in cls:
            if v in (member.value, member.name, member.name.lower()):
                return member
        raise ExperimentError(
            f"unknown policy {v!r}; expected one of "
            f"{[m.value for m in cls]}")


class Method(Enum):
    """The per-lane simulation algorithm (DESIGN.md §3d).

    EXACT is Gillespie's direct SSA — one Resolve/Update per reaction
    event. TAU_LEAP fires Poisson bundles of events over an adaptive
    Cao-bounded leap, falling back per lane to exact SSA wherever a
    leap would cover fewer than `tau_fallback` events — approximate in
    distribution, exact in reproducibility (same counter-based stream,
    bitwise identical across fused/kernel/sharded paths and
    checkpoint/resume)."""

    EXACT = "exact"
    TAU_LEAP = "tau_leap"

    @classmethod
    def coerce(cls, v: Union["Method", str]) -> "Method":
        if isinstance(v, cls):
            return v
        for member in cls:
            if v in (member.value, member.name, member.name.lower()):
                return member
        raise ExperimentError(
            f"unknown method {v!r}; expected one of "
            f"{[m.value for m in cls]}")


class Reduction(Enum):
    """What the per-window statistics aggregate over."""

    ENSEMBLE = "ensemble"    # pool every instance (replicas of one point)
    PER_POINT = "per_point"  # grouped per sweep point (paper §3.1.2)


@dataclass(frozen=True)
class Ensemble:
    """How many stochastic instances, and over which parameter points.

    `replicas` is the number of instances per sweep point (or the total
    ensemble size when there is no sweep). The embedded SweepSpec always
    carries the same replica count — use `Ensemble.make` to build one
    from a plain dict.
    """

    replicas: int = 1
    sweep: Optional[SweepSpec] = None

    @staticmethod
    def make(replicas: int = 1,
             sweep: Union[dict, SweepSpec, None] = None) -> "Ensemble":
        if isinstance(sweep, dict):
            sweep = SweepSpec.make(sweep, replicas)
        elif isinstance(sweep, SweepSpec):
            sweep = SweepSpec(sweep.values, replicas)
        return Ensemble(replicas=replicas, sweep=sweep)

    @property
    def n_points(self) -> int:
        return len(self.sweep.points()) if self.sweep else 1

    @property
    def n_instances(self) -> int:
        return self.n_points * self.replicas

    def group_ids(self) -> np.ndarray:
        """(I,) sweep-point id per instance (instance i -> point i//m)."""
        return np.repeat(np.arange(self.n_points, dtype=np.int32),
                         self.replicas)

    def validate(self) -> None:
        if self.replicas < 1:
            raise ExperimentError(
                f"Ensemble.replicas must be >= 1, got {self.replicas}")
        if self.sweep is not None:
            if self.sweep.replicas != self.replicas:
                raise ExperimentError(
                    f"Ensemble.replicas ({self.replicas}) disagrees with "
                    f"sweep.replicas ({self.sweep.replicas}); build via "
                    "Ensemble.make(replicas=..., sweep=...)")
            if not self.sweep.points():
                raise ExperimentError("sweep has no points (empty values)")
            for name, vals in self.sweep.values:
                if len(vals) == 0:
                    raise ExperimentError(
                        f"sweep axis {name!r} has no values")


@dataclass(frozen=True)
class Schedule:
    """The simulation-time grid and its parallelisation schema."""

    t_end: float
    n_windows: int
    schema: Schema = Schema.ONLINE
    policy: Policy = Policy.ON_DEMAND
    max_steps_per_window: Optional[int] = None

    def __post_init__(self):
        object.__setattr__(self, "schema", Schema.coerce(self.schema))
        object.__setattr__(self, "policy", Policy.coerce(self.policy))

    def validate(self) -> None:
        if not self.t_end > 0:
            raise ExperimentError(
                f"Schedule.t_end must be > 0, got {self.t_end}")
        if self.n_windows < 1:
            raise ExperimentError(
                f"Schedule.n_windows must be >= 1, got {self.n_windows}")
        if (self.schema is Schema.STATIC_FARM
                and self.policy is Policy.PREDICTIVE):
            raise ExperimentError(
                "schema STATIC_FARM (i) uses static round-robin groups; "
                "policy PREDICTIVE is only meaningful for time-sliced "
                "schemas (ii/iii)")
        if self.max_steps_per_window is not None \
                and self.max_steps_per_window < 1:
            raise ExperimentError(
                "Schedule.max_steps_per_window must be >= 1 or None, "
                f"got {self.max_steps_per_window}")


@dataclass(frozen=True)
class Experiment:
    """One fully-specified ensemble simulation.

    sinks: callables receiving each StatsRecord; anything with a
    `close()` is closed when the run completes.
    record_trajectories: buffer raw per-window samples even under
    schema ONLINE (forfeits its memory bound — opt-in).
    host_loop: legacy per-group host dispatch (benchmark baseline).
    use_kernel: advance windows through the Pallas fused SSA kernel
    (one device dispatch per window, in-VREG counter-based RNG) —
    bitwise identical to the unfused path and composable with
    `partitioning` (per-shard kernel under shard_map).
    kernel_chunk_steps / kernel_max_chunks: the kernel path's per-window
    event budget — up to max_chunks chunk iterations of chunk_steps
    fused events in one device-side while_loop; a window needing more
    raises FusedWindowTruncated naming these knobs (never a silent
    partial window). Changing them never changes a trajectory, only
    where the budget cuts off.
    partitioning: shard the instance pool over a device mesh
    (`Partitioning(n_shards=..., stat_blocks=...)`, or a mapping of
    its fields, as plain data such as a JSON object gives); records
    depend on `stat_blocks` (the statistics merge tree), never on the
    physical shard count, so pin it when comparing runs across mesh
    shapes.
    method: the per-lane algorithm — Method.EXACT (default) or
    Method.TAU_LEAP (adaptive tau-leaping, §3d); composes with every
    dispatch path. tau_eps: Cao drift bound (leap sizes scale with it);
    tau_fallback: minimum expected events per leap before a lane falls
    back to exact SSA for that step. Neither changes EXACT runs.
    window_block: superstep width — fuse this many windows into ONE
    device dispatch (a lax.scan over window horizons), with per-window
    records accumulated in an on-device ring and collected by an async
    pipelined pull, so dispatches and blocking host syncs amortise to
    1/window_block per window (DESIGN.md §3e). Records are bitwise
    identical for any value; composes with use_kernel, partitioning,
    and method, but not host_loop (the per-window baseline). With a
    checkpoint_path, saves land on block boundaries (served from the
    in-flight ring's entry snapshot — the pipeline keeps running), and
    resuming needs a checkpoint on a window_block boundary.
    pipeline_depth: how many dispatched window blocks may stay in
    flight before the collector blocks on the oldest ring (DESIGN.md
    §3e). 1 (default) is the classic double-buffer; K > 1 hides the
    collector's host-side reduce/emit work behind K blocks of device
    compute; "auto" profiles the first collected block (blocking-pull
    wall vs host-reduce wall) and picks a depth from that ratio. Depth
    only changes WHEN rings are pulled — records, sketches, grouped
    stats, trajectories, and steering decisions are bitwise identical
    for any value. Each in-flight block buffers a full record ring
    (Telemetry.peak_buffered_bytes accounts for all of them).
    Irrelevant when window_block == 1 or under host_loop.
    sketch: stream device-side per-window sketches (fixed-bin
    histograms, rare-event counters — repro/stats, DESIGN.md §3f)
    alongside the Welford records; read them back via
    `SimulationResult.sketches()`. Integer-count merges keep them
    bitwise identical across dispatch paths, shard counts, and
    superstep widths.
    sparse: large-network encoding (DESIGN.md §3g) — CSR-style padded
    reactant/stoichiometry tables plus a precomputed reaction
    dependency graph, so each SSA event recomputes only the affected
    propensities (O(out-degree) instead of O(R)) and tau-leaping uses
    the gather-form Match (no dense one-hot tensors). Bitwise identical
    to the dense encoding on every dispatch path, and lifts the dense
    path's MAX_COEF unroll ceiling (coefficients > 4 require
    sparse=True).
    steering: adaptive between-block control (repro/steer) — early-stop
    converged sweep points, reallocate their freed replicas, per-lane
    exact<->tau auto-switch, bimodality flags. Decisions are a pure
    function of (seed, Steering), ride checkpoints, and `Steering()`
    (all levers off) is bitwise identical to no steering at all.
    Incompatible with host_loop (no block boundary to steer at);
    bimodality needs a sketch; tau_switch needs Method.TAU_LEAP.
    recovery: supervised self-healing lifecycle (DESIGN.md §3h) —
    simulate() hands the run to `runtime.supervisor.RunSupervisor`:
    cadenced atomic checkpoints with retention, bounded-backoff restart
    from the newest VALID snapshot on any typed recoverable fault,
    elastic shard-loss degradation, straggler re-dispatch, and
    deterministic fault-injection drills. Records/sketches/steering
    decisions from a supervised run (faults or not) are bitwise
    identical to the unsupervised run. Owns checkpointing, so it is
    mutually exclusive with simulate()'s checkpoint_path/resume.
    """

    model: Union[CWCModel, ReactionSystem]
    ensemble: Ensemble
    schedule: Schedule
    reduction: Reduction = Reduction.ENSEMBLE
    sinks: Sequence = ()
    seed: int = 0
    n_lanes: int = 128
    record_trajectories: bool = False
    use_kernel: bool = False
    kernel_chunk_steps: int = 256
    kernel_max_chunks: int = 64
    host_loop: bool = False
    partitioning: Optional[Partitioning] = None
    method: Method = Method.EXACT
    tau_eps: float = 0.03
    tau_fallback: float = 10.0
    window_block: int = 1
    pipeline_depth: Union[int, str] = 1
    sparse: bool = False
    sketch: Optional[SketchSpec] = None
    steering: Optional[Steering] = None
    recovery: Optional[Recovery] = None

    def __post_init__(self):
        object.__setattr__(self, "method", Method.coerce(self.method))
        if isinstance(self.partitioning, Mapping):
            # plain data (a JSON object): its values are checked with
            # the object's at validate()
            try:
                part = Partitioning(**self.partitioning)
            except TypeError as e:
                raise ExperimentError(
                    f"Experiment.partitioning: {e}; expected some of "
                    f"{[f.name for f in dataclasses.fields(Partitioning)]}"
                ) from e
            object.__setattr__(self, "partitioning", part)

    def validate(self) -> None:
        if not isinstance(self.model, (CWCModel, ReactionSystem)):
            raise ExperimentError(
                "Experiment.model must be a CWCModel or ReactionSystem, "
                f"got {type(self.model).__name__}")
        if not isinstance(self.ensemble, Ensemble):
            raise ExperimentError(
                "Experiment.ensemble must be an Ensemble "
                f"(got {type(self.ensemble).__name__}); wrap a SweepSpec "
                "via Ensemble.make(replicas=..., sweep=...)")
        if not isinstance(self.schedule, Schedule):
            raise ExperimentError(
                "Experiment.schedule must be a Schedule, "
                f"got {type(self.schedule).__name__}")
        self.ensemble.validate()
        self.schedule.validate()
        if not isinstance(self.reduction, Reduction):
            raise ExperimentError(
                f"Experiment.reduction must be a Reduction enum, "
                f"got {self.reduction!r}")
        if self.n_lanes < 1:
            raise ExperimentError(
                f"Experiment.n_lanes must be >= 1, got {self.n_lanes}")
        if self.use_kernel and self.schedule.max_steps_per_window:
            raise ExperimentError(
                "max_steps_per_window is not honoured by the fused "
                "Pallas kernel path (use_kernel=True); drop one of them")
        if self.kernel_chunk_steps < 1:
            raise ExperimentError(
                f"Experiment.kernel_chunk_steps must be >= 1, got "
                f"{self.kernel_chunk_steps}")
        if self.kernel_max_chunks < 1:
            raise ExperimentError(
                f"Experiment.kernel_max_chunks must be >= 1, got "
                f"{self.kernel_max_chunks}")
        if self.window_block < 1:
            raise ExperimentError(
                f"Experiment.window_block must be >= 1, got "
                f"{self.window_block}")
        if self.window_block > 1 and self.host_loop:
            raise ExperimentError(
                "window_block > 1 needs the fused or sharded dispatch "
                "strategy; host_loop is the per-window round-trip "
                "baseline (set window_block=1)")
        if isinstance(self.pipeline_depth, str):
            if self.pipeline_depth != "auto":
                raise ExperimentError(
                    f"Experiment.pipeline_depth must be an int >= 1 or "
                    f"'auto', got {self.pipeline_depth!r}")
        elif (not isinstance(self.pipeline_depth, int)
                or self.pipeline_depth < 1):
            raise ExperimentError(
                f"Experiment.pipeline_depth must be an int >= 1 or "
                f"'auto', got {self.pipeline_depth!r}")
        # method itself needs no check here: __post_init__ coerced it
        # (or raised ExperimentError) at construction
        if not self.tau_eps > 0:
            raise ExperimentError(
                f"Experiment.tau_eps must be > 0, got {self.tau_eps}")
        if self.tau_fallback < 0:
            raise ExperimentError(
                f"Experiment.tau_fallback must be >= 0, got "
                f"{self.tau_fallback}")
        if self.partitioning is not None:
            if not isinstance(self.partitioning, Partitioning):
                raise ExperimentError(
                    "Experiment.partitioning must be a Partitioning, "
                    f"got {type(self.partitioning).__name__}")
            try:
                self.partitioning.validate(self.ensemble.n_instances)
            except ValueError as e:
                raise ExperimentError(str(e)) from e
            if self.partitioning.n_shards > 1 and self.host_loop:
                raise ExperimentError(
                    "partitioning with n_shards > 1 is incompatible "
                    "with host_loop (a host-driven single-device "
                    "baseline); use_kernel composes with sharding")
        if self.sketch is not None:
            if not isinstance(self.sketch, SketchSpec):
                raise ExperimentError(
                    "Experiment.sketch must be a SketchSpec, "
                    f"got {type(self.sketch).__name__}")
            try:
                self.sketch.validate()
            except ValueError as e:
                raise ExperimentError(str(e)) from e
        if self.steering is not None:
            if not isinstance(self.steering, Steering):
                raise ExperimentError(
                    "Experiment.steering must be a Steering, "
                    f"got {type(self.steering).__name__}")
            try:
                self.steering.validate()
            except ValueError as e:
                raise ExperimentError(str(e)) from e
            if self.steering.enabled:
                if self.host_loop:
                    raise ExperimentError(
                        "steering is driven from the superstep "
                        "collector; host_loop has no block boundary "
                        "to steer at")
                if self.steering.bimodality and self.sketch is None:
                    raise ExperimentError(
                        "Steering.bimodality reads window histograms; "
                        "set Experiment.sketch as well")
                if self.steering.tau_switch \
                        and self.method is not Method.TAU_LEAP:
                    raise ExperimentError(
                        "Steering.tau_switch only applies to "
                        "method=Method.TAU_LEAP runs")
                if (isinstance(self.pipeline_depth, int)
                        and self.pipeline_depth > 1):
                    raise ExperimentError(
                        "steering forces lock-step collection "
                        "(decisions must see block k before block k+1 "
                        "dispatches), which is incompatible with an "
                        f"explicit pipeline_depth={self.pipeline_depth};"
                        " use pipeline_depth=1 or 'auto' (resolves to "
                        "1 under steering — see "
                        "Telemetry.pipeline_depth_effective)")
        if self.recovery is not None:
            if not isinstance(self.recovery, Recovery):
                raise ExperimentError(
                    "Experiment.recovery must be a Recovery, "
                    f"got {type(self.recovery).__name__}")
            try:
                self.recovery.validate()
            except ValueError as e:
                raise ExperimentError(str(e)) from e
            if self.recovery.workers > 1:
                self._validate_farm()
        for s in self.sinks:
            if not callable(s):
                raise ExperimentError(f"sink {s!r} is not callable")

    def _validate_farm(self) -> None:
        """Cross-checks for the multi-process farm
        (Recovery.workers > 1, DESIGN.md §3i). The bitwise-merge
        contract needs clean shard boundaries: whole stat blocks (and,
        per point, whole sweep points) per worker."""
        w = self.recovery.workers
        if self.partitioning is not None and self.partitioning.n_shards > 1:
            raise ExperimentError(
                "Recovery.workers shards the ensemble at the PROCESS "
                "level; in-process device sharding inside each worker "
                f"(Partitioning.n_shards={self.partitioning.n_shards}) "
                "is not supported — use Partitioning(n_shards=1, "
                "stat_blocks=...) to pin the statistics partition, or "
                "drop workers")
        n_inst = self.ensemble.n_instances
        blocks = (self.partitioning.blocks
                  if self.partitioning is not None else w)
        if blocks % w or n_inst % blocks:
            raise ExperimentError(
                f"Recovery.workers={w} needs each worker to own whole "
                f"stat blocks: stat_blocks ({blocks}) must be a "
                f"multiple of workers and divide n_instances "
                f"({n_inst})")
        if self.reduction is Reduction.PER_POINT \
                and self.ensemble.n_points % w:
            raise ExperimentError(
                f"Recovery.workers={w} with Reduction.PER_POINT needs "
                "each worker to own whole sweep points: n_points "
                f"({self.ensemble.n_points}) must divide evenly over "
                "workers")
        if self.steering is not None and self.steering.enabled:
            if self.steering.reallocate:
                raise ExperimentError(
                    "Steering.reallocate moves lanes ACROSS sweep "
                    "points, which cannot be replayed inside "
                    "process-local shards; drop reallocate or run "
                    "with workers=1")
            if (self.steering.ci_rel_tol > 0 or self.steering.bimodality) \
                    and self.reduction is not Reduction.PER_POINT:
                raise ExperimentError(
                    "steering convergence decisions under "
                    "Recovery.workers > 1 need per-point statistics "
                    "(each worker owns whole points and reproduces "
                    "the global decision locally); use "
                    "Reduction.PER_POINT or workers=1")

    # convenience constructors ----------------------------------------
    def with_(self, **changes) -> "Experiment":
        return dataclasses.replace(self, **changes)
