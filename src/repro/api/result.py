"""SimulationResult — the handle `simulate()` returns.

Wraps the running (or finished) engine: streamed records, per-sweep-
point grouped statistics, raw trajectories, wall-time / peak-memory
telemetry, and the checkpoint()/resume() lifecycle. The handle owns the
run loop so a partially-run experiment (``max_windows=``) can be
continued in-process, or from a checkpoint file in a later process.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.core.spans import span
from repro.core.stream import StatsRecord


@dataclass(frozen=True)
class Telemetry:
    """Run telemetry (DESIGN.md §6).

    dispatches: jitted device launches for pool advancement — the new
    window_step path pays one per window, the legacy host loop one per
    (group × window), and supersteps (window_block=W) one per BLOCK
    (1/W per window).
    host_syncs: blocking device->host pulls (stats, samples, costs);
    under supersteps one combined ring pull per block, so the
    amortised per-window rate drops below 1.
    build_s: wall of the engine build (the span `repro.engine.build`:
    engine construction, dispatch selection, lane init and placement,
    sweep rates); for a farm, the slowest worker's.
    peak_buffered_bytes: engine-side trajectory buffering high-water
    mark (schema iii's memory bound).
    peak_rss_bytes: process high-water RSS where the platform reports
    it (None otherwise).
    steps_per_window: pool-total solver iterations per window (exact:
    events fired; tau-leap: accepted leaps + exact-fallback events) —
    the per-method work metric the tau-leap speedup claim is measured
    in. leaps_per_window: accepted tau-leaps per window (all zero on
    Method.EXACT); steps - leaps is the exact-fallback share.
    chunks_per_window: kernel paths only (empty otherwise) — chunk-loop
    iterations per window, the most any group or shard ran. Against
    `kernel_max_chunks` it says how close a window came to truncation;
    events / (chunks x kernel_chunk_steps x lanes) is the chunk fill.
    kernel_layout: the body the dense exact kernel ran, "lanes"
    (replicas on the 128-wide lane axis) or "columns" (the network's
    (lanes, S) and (lanes, R) tiles), chosen from the network's shape
    (`kernels.ssa_step.kernel_layout`); "" on every other path.
    n_shards: the shards the pool ran in: devices of the sharded
    dispatch's mesh, the farm's workers, else 1.
    steps_per_shard: per window, the events each shard's lanes fired,
    one entry per shard, in shard order; each row sums to that
    window's steps_per_window. Summed on each device beside the pool
    total and pulled with it: no extra host sync.

    WALL ATTRIBUTION (block_walls): each row is (first_window,
    n_windows, dispatch_s, collect_s) — one row per window on
    per-window paths, one per block under supersteps — and both walls
    are sums of program spans (`repro.core.spans`), the same intervals
    a profiler trace shows:
    * DISPATCH-WALL = `repro.dispatch.enqueue` (the jitted window or
      block call) + `repro.dispatch.fold` (the eager per-window
      statistic folds and the async device-to-host copies). It is not
      free of device time: on a TPU v5e the host waited in the fold
      for the block to finish, so the fold held the device's own
      seconds, and the first call of a new engine traces and lowers
      inside the enqueue. The trace splits the two phases; this row
      only sums them.
    * COLLECT-WALL = `repro.collect.pull` (the blocking record-ring
      pull: device wait + transfer remainder after the async copies)
      + `repro.collect.reduce` (the host-side per-window emission).
      The pull is the wall the pipeline depth exists to hide: at depth
      K the collector blocks only once K blocks are queued behind the
      oldest.
    Together with `build_s`, an untraced run thus says in which phase
    a slow ensemble lost its time.
    """

    wall_time_s: float
    peak_buffered_bytes: int
    dispatches: int
    host_syncs: int
    peak_rss_bytes: Optional[int]
    steps_per_window: tuple = ()
    leaps_per_window: tuple = ()
    # straggler watchdog (runtime/straggler.py): (window, wall_s,
    # rolling_median) entries whose wall share exceeded the watchdog
    # factor x the rolling median, and the flagged fraction over ALL
    # observed windows (a monotone counter — NOT the bounded median
    # window, which saturates at its maxlen)
    straggler_windows: tuple = ()
    straggler_rate: float = 0.0
    # supervised runs (Experiment.recovery): engine teardown+restore
    # cycles the RunSupervisor performed; 0 for unsupervised runs and
    # for supervised runs that never faulted
    restarts: int = 0
    # straggler re-dispatches the supervisor performed (EngineStall
    # recoveries) — tracked apart from `restarts` so slow windows never
    # consume the crash budget
    stall_redispatches: int = 0
    # depth-K superstep pipeline (DESIGN.md §3e)
    block_walls: tuple = ()  # (w0, n_win, dispatch_s, collect_s) rows
    pipeline_depth: int = 1  # resolved depth ("auto" probes 1st block)
    # the depth the collector actually ran at: steering forces
    # lock-step, so a steered run reports 1 here no matter what depth
    # was requested ("auto" resolves to 1; explicit >1 is rejected at
    # validation) — the forcing is visible, never silent
    pipeline_depth_effective: int = 1
    peak_inflight_blocks: int = 0  # max queued rings observed
    snapshot_saves: int = 0  # checkpoints served from a ring snapshot
    ckpt_flushes: int = 0  # checkpoints that had to flush the pipeline
    build_s: float = 0.0
    chunks_per_window: tuple = ()
    kernel_layout: str = ""
    n_shards: int = 1
    steps_per_shard: tuple = ()


def _peak_rss_bytes() -> Optional[int]:
    try:
        import resource
        import sys

        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # linux reports KiB, macOS bytes
        return ru * (1 if sys.platform == "darwin" else 1024)
    except Exception:
        return None


class SimulationResult:
    def __init__(self, experiment, engine):
        self.experiment = experiment
        self._engine = engine
        self._wall_time = 0.0

    # ------------------------------------------------------------ run
    def resume(self, max_windows: Optional[int] = None,
               checkpoint_path: Optional[str] = None) -> "SimulationResult":
        """Advance the experiment, at most `max_windows` windows (all
        remaining if None), checkpointing after every window when a
        path is given. Returns self for chaining.

        With `window_block > 1` the run advances in pipelined
        supersteps: up to `pipeline_depth` blocks are dispatched ahead
        of the oldest ring pull, so host-side reduction and sinks
        overlap device simulation. A `checkpoint_path` saves after
        every collected block, on that block's boundary — served from
        the in-flight ring's entry SNAPSHOT (engine.enable_snapshots),
        so saving no longer disables the dispatch-ahead or flushes the
        pipeline. `max_windows` may cut the final block short — such a
        mid-block checkpoint can only be resumed with a window_block
        dividing its window index."""
        eng = self._engine
        t0 = time.perf_counter()
        done = 0
        try:
            # steered runs always use the block loop (decision points
            # live at collected block boundaries), even window_block=1
            if eng.cfg.window_block == 1 and eng._steer is None:
                while eng._window < len(eng.grid) and (
                        max_windows is None or done < max_windows):
                    eng.run_window()
                    done += 1
                    if checkpoint_path:
                        eng.checkpoint(checkpoint_path)
            else:
                limit = len(eng.grid) if max_windows is None else min(
                    len(eng.grid), eng._window + max_windows)
                if checkpoint_path and eng._steer is None:
                    # each save lands on the just-collected block's
                    # boundary, served from the oldest in-flight ring's
                    # entry snapshot — the pipeline keeps running
                    # underneath (steered runs are lock-step anyway)
                    eng.enable_snapshots()
                while eng._window < limit:
                    got = eng.run_block(dispatch_limit=limit)
                    if checkpoint_path and got:
                        eng.checkpoint(checkpoint_path)
                eng.flush()
        finally:
            self._wall_time += time.perf_counter() - t0
        if self.completed:
            eng.stream.close()
        return self

    def checkpoint(self, path: str) -> None:
        """Serialise pool + scheduler + emitted records to `path`."""
        self._engine.checkpoint(path)

    @property
    def completed(self) -> bool:
        return self._engine._window >= len(self._engine.grid)

    @property
    def windows_run(self) -> int:
        return self._engine._window

    # ----------------------------------------------------------- data
    @property
    def obs_names(self) -> list[str]:
        return list(self._engine.obs_names)

    @property
    def records(self) -> list[StatsRecord]:
        return self._engine.stream.records()

    def means(self) -> np.ndarray:
        """(windows_run, n_obs) ensemble means."""
        return np.stack([r.mean for r in self.records])

    @property
    def t_grid(self) -> np.ndarray:
        return np.asarray(self._engine.grid)

    def trajectories(self) -> Optional[np.ndarray]:
        """(I, T, n_obs) raw samples — schemas i/ii always; schema iii
        when Experiment.record_trajectories was set."""
        return self._engine.trajectories()

    def per_point(self) -> Optional[dict]:
        """Grouped per-sweep-point statistics (Reduction.PER_POINT).

        Returns {"mean"|"var"|"ci90"|"n": (windows, points, n_obs)},
        plus "points": the sweep point dicts, or None when the run used
        a pooled ensemble reduction.
        """
        grouped = self._engine.grouped_stats()
        if not grouped:
            return None
        out = {
            "n": np.stack([g.n for g in grouped]),
            "mean": np.stack([g.mean for g in grouped]),
            "var": np.stack([g.var for g in grouped]),
            "ci90": np.stack([g.ci90 for g in grouped]),
        }
        sweep = self.experiment.ensemble.sweep
        out["points"] = sweep.points() if sweep else [{}]
        return out

    def final_state(self) -> np.ndarray:
        """(I, S) species counts at the last completed window, pulled
        from every shard (the span `repro.collect.gather`)."""
        with span("collect.gather", n_shards=self._engine.n_shards):
            return np.asarray(self._engine._pool.x)

    def sketches(self) -> list:
        """Per-window `WindowSketch`es (hist (G, n_obs, n_bins) int32,
        rare (G, n_obs, n_thr) int32 or None) when the Experiment
        carried a SketchSpec; empty list otherwise. Derive quantiles or
        bimodality flags with `repro.stats.quantiles_from_hist` /
        `bimodality_from_hist`."""
        return self._engine.sketches()

    def steering_report(self) -> Optional[dict]:
        """The steering policy's savings + decision summary (stopped
        points, windows saved, pinned lanes, bimodal flags, decision
        log), or None when the Experiment carried no active
        Steering."""
        return self._engine.steering_report()

    def recovery_report(self) -> Optional[dict]:
        """The RunSupervisor's event log + summary (restarts, faults
        by kind, final shard count after any elastic degradation,
        ordered events), or None when the Experiment carried no
        Recovery."""
        return getattr(self, "_recovery", None)

    # ------------------------------------------------------ telemetry
    @property
    def telemetry(self) -> Telemetry:
        eng = self._engine
        return Telemetry(
            wall_time_s=self._wall_time,
            peak_buffered_bytes=eng.peak_buffered_bytes,
            dispatches=eng.n_dispatches,
            host_syncs=eng.n_host_syncs,
            peak_rss_bytes=_peak_rss_bytes(),
            steps_per_window=tuple(eng.window_steps),
            leaps_per_window=tuple(eng.window_leaps),
            straggler_windows=tuple(eng.watchdog.flagged),
            straggler_rate=eng.watchdog.straggler_rate(),
            restarts=getattr(self, "_restarts", 0),
            stall_redispatches=getattr(self, "_stall_redispatches", 0),
            block_walls=tuple(eng.block_walls),
            pipeline_depth=eng.pipeline_depth,
            pipeline_depth_effective=getattr(
                eng, "pipeline_depth_effective", eng.pipeline_depth),
            peak_inflight_blocks=eng.peak_inflight_blocks,
            snapshot_saves=eng.n_snapshot_saves,
            ckpt_flushes=eng.n_ckpt_flushes,
            build_s=eng.build_s,
            chunks_per_window=tuple(eng.window_chunks),
            kernel_layout=eng.kernel_layout,
            n_shards=eng.n_shards,
            steps_per_shard=tuple(eng.window_shard_steps))

    def __repr__(self) -> str:
        state = "completed" if self.completed else (
            f"{self.windows_run}/{len(self._engine.grid)} windows")
        return (f"SimulationResult({state}, instances="
                f"{self.experiment.ensemble.n_instances}, "
                f"schema={self.experiment.schedule.schema.value!r})")
