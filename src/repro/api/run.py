"""simulate() — validate, compile, run, return a result handle.

This is the single entry point over the engine: it resolves the typed
spec onto `SimulationEngine` (schema/policy enums -> engine strings,
sweep -> per-instance rate matrix, PER_POINT reduction -> instance
group ids), attaches sinks, and drives the window loop through the
returned `SimulationResult` so checkpoint/resume and partial runs share
one code path.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.api.result import SimulationResult
from repro.api.spec import Experiment, ExperimentError, Reduction
from repro.core.engine import SimConfig, SimulationEngine
from repro.core.spans import span
from repro.core.sweep import sweep_rates


def observable_names(model) -> list[str]:
    """The observable column names an Experiment on `model` will report
    (what a CsvSink wants), without building an engine."""
    from repro.core.engine import resolve_observables

    return resolve_observables(model)[1]


def build_engine(experiment: Experiment, mesh=None,
                 shard=None) -> SimulationEngine:
    """Compile an Experiment down to a ready-to-run engine (no windows
    are run). Exposed for benchmarks; prefer simulate().

    When the Experiment carries a multi-shard Partitioning and no mesh
    is supplied, the farm's mesh is built by the dispatch seam
    (`core/dispatch.select_dispatch`) with
    `jax.make_mesh((n_shards,), (axis,))`.

    `shard=(lo, hi, stat_blocks)` is the multi-process farm worker's
    seam (runtime/worker.py): the engine covers only instance rows
    [lo, hi) of the GLOBAL ensemble — same seed, rates/group ids
    sliced to the range, and (crucially) RNG key rows taken from the
    GLOBAL `jax.random.split(PRNGKey(seed), I)` table, so each lane's
    counter-based stream is the one the single-process run would give
    it. `stat_blocks` is the worker's share of the global Welford
    block partition (contiguous, so worker blocks ARE global blocks).

    The build is the span `repro.engine.build`; its wall is kept as
    `engine.build_s` (Telemetry.build_s), and its `kernel_layout`
    attribute names the dense exact kernel's body (absent where the
    engine runs none), its `n_shards` the devices the pool is sharded
    over. Inside it, `repro.dispatch.place` spans the mesh build and
    the placement of pool, rates and group ids."""
    with span("engine.build") as build:
        engine = _build_engine(experiment, mesh, shard)
        build.annotate(kernel_layout=engine.kernel_layout,
                       n_shards=engine.n_shards)
    engine.build_s = build.seconds
    return engine


def _build_engine(experiment: Experiment, mesh, shard) -> SimulationEngine:
    experiment.validate()
    ens = experiment.ensemble
    sched = experiment.schedule
    part = experiment.partitioning
    lo, hi = (0, ens.n_instances) if shard is None else shard[:2]
    if shard is not None:
        from repro.core.dispatch import Partitioning

        part = Partitioning(n_shards=1, stat_blocks=shard[2])
    cfg = SimConfig(
        n_instances=hi - lo,
        t_end=float(sched.t_end),
        n_windows=sched.n_windows,
        n_lanes=min(experiment.n_lanes, hi - lo),
        schema=sched.schema.value,
        policy=sched.policy.value,
        seed=experiment.seed,
        max_steps_per_window=sched.max_steps_per_window,
        use_kernel=experiment.use_kernel,
        host_loop=experiment.host_loop,
        kernel_chunk_steps=experiment.kernel_chunk_steps,
        kernel_max_chunks=experiment.kernel_max_chunks,
        method=experiment.method.value,
        tau_eps=experiment.tau_eps,
        tau_fallback=experiment.tau_fallback,
        window_block=experiment.window_block,
        pipeline_depth=experiment.pipeline_depth,
        sparse=experiment.sparse)
    group_ids = (ens.group_ids()
                 if experiment.reduction is Reduction.PER_POINT else None)
    if group_ids is not None and shard is not None:
        # points are contiguous replica runs, so the slice is whole
        # points; re-base to 0 so the worker's grouped rows line up
        # with its local point index (global index = local + base)
        group_ids = group_ids[lo:hi] - group_ids[lo]
    try:
        engine = SimulationEngine(
            experiment.model, cfg, mesh=mesh, group_ids=group_ids,
            record_trajectories=experiment.record_trajectories,
            partitioning=part, sketch=experiment.sketch,
            steering=experiment.steering, _deprecated=False)
    except ValueError as e:
        # dispatch-seam errors (device count, mesh/partitioning
        # mismatch) surface in the API's vocabulary
        raise ExperimentError(str(e)) from e
    if shard is not None:
        import jax.numpy as jnp

        from repro.core.gillespie import init_lanes

        global_pool = init_lanes(engine.system, ens.n_instances,
                                 experiment.seed)
        with span("dispatch.place", n_shards=engine.n_shards):
            engine._pool = engine._dispatch.place(engine._pool._replace(
                key=jnp.asarray(global_pool.key)[lo:hi]))
        if group_ids is not None:
            # declare this shard's place in the GLOBAL (V, G) stats
            # layout: shards are contiguous, block size is uniform, so
            # worker blocks/points ARE global blocks/points at an
            # offset — the engine folds grouped stats through the
            # zero-extended global stack (steering sees reference bits)
            bs = (hi - lo) // shard[2]
            engine.set_global_stats_layout(
                v_total=ens.n_instances // bs, v0=lo // bs,
                g_total=ens.n_points, g0=lo // ens.replicas)
    if ens.sweep is not None:
        try:
            rates = sweep_rates(engine.system, ens.sweep)
        except KeyError as e:
            raise ExperimentError(
                f"sweep names a rate the model does not define: {e}; "
                f"reactions are {list(engine.system.reaction_names)}"
            ) from e
        engine.set_rates(rates[lo:hi] if shard is not None else rates)
    return engine


def simulate(experiment: Experiment, *,
             checkpoint_path: Optional[str] = None,
             resume: bool = False,
             max_windows: Optional[int] = None,
             mesh=None) -> SimulationResult:
    """Run an Experiment end to end.

    checkpoint_path: checkpoint after every window (and the restore
    source when resume=True).
    resume: restore pool/records from checkpoint_path before running —
    the file must exist; records emitted before the checkpoint are
    replayed into the result buffer AND into this run's sinks (a fresh
    CsvSink starts from an empty file, so the replay keeps it
    complete).
    max_windows: stop after this many windows; the returned handle's
    `.resume()` continues the same run in-process.

    With `experiment.recovery` set, the run is handed to
    `runtime.supervisor.RunSupervisor` (cadenced checkpoints +
    restart-on-fault + elastic degradation, DESIGN.md §3h). The
    supervisor owns checkpointing and drives the run to completion,
    so checkpoint_path/resume/max_windows are rejected alongside it.

    The whole call is the span `repro.simulate`, the parent of the
    engine's own spans (`repro.core.spans`).
    """
    with span("simulate"):
        return _simulate(experiment, checkpoint_path, resume,
                         max_windows, mesh)


def _simulate(experiment: Experiment, checkpoint_path: Optional[str],
              resume: bool, max_windows: Optional[int],
              mesh) -> SimulationResult:
    if experiment.recovery is not None:
        if checkpoint_path or resume or max_windows is not None:
            raise ExperimentError(
                "Experiment.recovery owns checkpointing and drives the "
                "run to completion; drop checkpoint_path/resume/"
                "max_windows (set Recovery.ckpt_dir and cadence "
                "instead)")
        if experiment.recovery.workers > 1:
            # multi-process elastic farm: a coordinator process shards
            # the ensemble over worker processes and merges their
            # results bitwise (DESIGN.md §3i)
            from repro.runtime.coordinator import FarmCoordinator

            return FarmCoordinator(experiment,
                                   experiment.recovery).run()
        from repro.runtime.supervisor import RunSupervisor

        return RunSupervisor(experiment, experiment.recovery,
                             mesh=mesh).run()
    engine = build_engine(experiment, mesh=mesh)
    if resume:
        if not checkpoint_path:
            raise ExperimentError("resume=True requires checkpoint_path")
        path = (checkpoint_path if checkpoint_path.endswith(".npz")
                else checkpoint_path + ".npz")
        if not os.path.exists(path):
            raise ExperimentError(
                f"resume=True but no checkpoint at {path!r}")
        try:
            engine.restore(checkpoint_path)
        except ValueError as e:
            # e.g. a mid-block checkpoint under window_block > 1
            raise ExperimentError(str(e)) from e
    for sink in experiment.sinks:
        engine.stream.attach(sink)
        for rec in engine.stream.records():  # replay restored windows
            sink(rec)
    result = SimulationResult(experiment, engine)
    return result.resume(max_windows=max_windows,
                         checkpoint_path=checkpoint_path)
