"""The campaign a cell's traffic describes: back-to-back ensembles of
one deployment, each one `repro.api.simulate()` call run to its
horizon.

An ensemble's cost is set by its busiest lane: the window loops run
until every lane reaches the horizon, so a seed whose lanes swing wide
costs more (on the chip, fresh `lotka77` seeds took 19.2–20.7 s on the
kernel path where most took 19.2–19.5 s). So that every run does the
same work, the ensembles' seeds come from a fixed pool of
`ensemble_pool` seeds drawn from `pool_seed`, in an order drawn from the
run's seed, and the window counts whole cycles of the pool. The run's
seed also sets which ensemble meets the reference and the reference's
own stream.

Warm-up runs the first window block of one ensemble at a horizon cut
to `warmup_horizon_share` of the deployment's: the same programs at the
same shapes (horizons are arguments, not constants), at a sliver of
the work. The window then runs the pool's cycle until the first cycle
that ends at or after `seconds`. A traced run closes its window at the
end of the first ensemble that ends after `trace_seconds` instead: the
XLA windows put one device operation per event step in the trace, and
a long trace takes minutes to write and read.
"""
from __future__ import annotations

import dataclasses
import importlib
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np


def ensemble_seed(seed: int, k: int) -> int:
    """A 31-bit ensemble seed drawn from (`seed`, `k`); `seed` may be
    any size."""
    words = [seed % (1 << 64), (k + 1) % (1 << 32)]
    return int(np.random.SeedSequence(words).generate_state(1)[0]
               & 0x7FFFFFFF)


def window_seeds(campaign: dict, seed: int) -> list:
    """One cycle of the window's ensemble seeds: the pool's seeds in an
    order drawn from the run's seed."""
    n, base = int(campaign["ensemble_pool"]), int(campaign["pool_seed"])
    pool = [ensemble_seed(base, i) for i in range(n)]
    order = np.random.default_rng([seed % (1 << 64), 3]).permutation(n)
    return [pool[i] for i in order]


def build_model(model: dict):
    """The program's own model builder, named `module:function` in the
    configuration, called with the configuration's arguments."""
    mod, fn = model["builder"].split(":")
    return getattr(importlib.import_module(mod), fn)(**model["args"])


def experiment(cell, seed: int, t_end: float | None = None,
               dropped: list | None = None):
    """The cell's Experiment. A setting is passed only where Experiment
    still has that field; the others are named in `dropped`."""
    from repro.api import Ensemble, Experiment, Method, Schedule

    dep = cell.config["deployment"]
    fields = {f.name for f in dataclasses.fields(Experiment)}
    settings = {}
    for k, v in cell.settings.items():
        if k in fields:
            settings[k] = v
        elif dropped is not None:
            dropped.append(k)
    return Experiment(
        model=build_model(dep["model"]),
        ensemble=Ensemble(replicas=int(dep["replicas"])),
        schedule=Schedule(t_end=float(t_end or dep["t_end"]),
                          n_windows=int(dep["n_windows"]),
                          schema=dep["schema"]),
        method=Method.coerce(dep["method"]), seed=seed, **settings)


@dataclass
class EnsembleRun:
    """What one ensemble of the window delivered, on the host."""

    seed: int
    replicas: int
    wall_s: float
    obs_names: tuple
    mean: np.ndarray  # (W, n_obs) per-window records
    var: np.ndarray
    n: np.ndarray  # (W,)
    final: np.ndarray  # (I, S) lane states at the horizon
    steps_per_window: tuple
    completed: bool


@dataclass
class Window:
    setup_s: float
    window_s: float
    compile_s: float
    cache_misses: int
    ensembles: list = field(default_factory=list)
    dropped: list = field(default_factory=list)


def _span(name: str, traced: bool):
    if not traced:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def warm_up(cell, seed: int, dropped: list) -> None:
    from repro.api import simulate

    share = float(cell.traffic["campaign"]["warmup_horizon_share"])
    t_end = float(cell.config["deployment"]["t_end"]) * share
    exp = experiment(cell, ensemble_seed(seed, -1), t_end, dropped)
    res = simulate(exp, max_windows=max(1, int(exp.window_block)))
    res.final_state()


def run_ensemble(cell, seed: int, traced: bool = False) -> EnsembleRun:
    """One ensemble through the served path, run to its horizon, and
    what it delivered, pulled to the host."""
    from repro.api import simulate

    exp = experiment(cell, seed)
    t0 = time.perf_counter()
    with _span("bench.ensemble", traced):
        res = simulate(exp)
    with _span("bench.collect", traced):
        recs = res.records
        final = np.asarray(res.final_state())
        tel = res.telemetry
    return EnsembleRun(
        seed=seed, replicas=exp.ensemble.n_instances,
        wall_s=time.perf_counter() - t0,
        obs_names=tuple(res.obs_names),
        mean=np.stack([np.asarray(r.mean, np.float64) for r in recs]),
        var=np.stack([np.asarray(r.var, np.float64) for r in recs]),
        n=np.asarray([float(r.n) for r in recs]),
        final=final,
        steps_per_window=tuple(tel.steps_per_window),
        completed=bool(res.completed))


def canary() -> None:
    """Trace, lower, compile and run one fresh function, so that the
    trace holds JAX's compile spans even where the window compiles
    nothing."""
    import jax
    import jax.numpy as jnp

    jax.jit(lambda x: x + 1)(jnp.float32(0)).block_until_ready()


def run_window(cell, seed: int, seconds: float, clock, t_start: float,
               trace_dir: str | None = None) -> Window:
    """Warm up, then run the measured window. `t_start` is the
    perf_counter() reading of process start; `clock` a CompileClock.

    A traced run starts the profiler, then runs the `canary` before the
    window's first `bench.` span and before the compile clock's first
    reading: the trace's window (`trace.from_profile`) and the window's
    `compile_s` leave it out. Its spans show that JAX's span names still
    match `entry.compile_s`'s, so that metric reads 0 s where the window
    compiled nothing, and nothing, failing the run, where the names
    went stale. An untraced run has no canary."""
    import jax

    dropped: list = []
    warm_up(cell, seed, dropped)
    traced = trace_dir is not None
    if traced:
        seconds = float(cell.traffic["campaign"]["trace_seconds"])
    win = Window(setup_s=0.0, window_s=0.0, compile_s=0.0,
                 cache_misses=0, dropped=sorted(set(dropped)))
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        canary()
    c0, m0 = clock.read()
    t0 = time.perf_counter()
    win.setup_s = t0 - t_start
    cycle = window_seeds(cell.traffic["campaign"], seed)
    k = 0
    while True:
        win.ensembles.append(run_ensemble(cell, cycle[k % len(cycle)],
                                          traced))
        k += 1
        if (time.perf_counter() - t0 >= seconds
                and (traced or k % len(cycle) == 0)):
            break
    win.window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    c1, m1 = clock.read()
    win.compile_s, win.cache_misses = c1 - c0, m1 - m0
    return win
