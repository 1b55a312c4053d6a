"""Program spans (repro.core.spans) and the chunk counter.

A tiny ensemble runs through simulate() under the profiler; the trace
is read back with the benchmark's own reader (bench/benchkit/trace.py),
so these tests pin what a reader of the spans can rely on: every
`repro.` span is on the host's line, the phases nest inside
`repro.simulate`, and Telemetry's walls are the spans' own intervals.
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.api import Ensemble, Experiment, Schedule, simulate
from repro.core.cwc.models import MODELS, lotka_volterra
from repro.core.spans import span

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))

from benchkit import trace as tracemod  # noqa: E402

N_WINDOWS, WINDOW_BLOCK = 4, 2
PHASES = ("repro.dispatch.enqueue", "repro.dispatch.fold",
          "repro.collect.pull", "repro.collect.reduce")


def make_exp(window_block=WINDOW_BLOCK, **kw):
    return Experiment(
        model=lotka_volterra(2), ensemble=Ensemble.make(replicas=16),
        schedule=Schedule(t_end=0.2, n_windows=N_WINDOWS),
        n_lanes=8, seed=3, window_block=window_block, **kw)


def traced(tmp_path, exp):
    """(result, host spans) of one simulate() call under the profiler."""
    simulate(exp)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = simulate(exp)
        res.final_state()
    finally:
        jax.profiler.stop_trace()
    tr = tracemod.read(str(tmp_path))
    return res, [h for h in tr.host_spans if h[0].startswith("repro.")]


@pytest.fixture(scope="module", params=["unfused", "kernel"])
def run(request, tmp_path_factory):
    exp = make_exp(use_kernel=request.param == "kernel")
    return traced(tmp_path_factory.mktemp(request.param), exp)


def by_name(spans, name):
    return sorted((s for s in spans if s[0] == name), key=lambda s: s[1])


def test_every_span_is_in_the_trace(run):
    _, spans = run
    names = {s[0] for s in spans}
    assert {"repro.simulate", "repro.engine.build"} | set(PHASES) <= names


def test_one_build_per_ensemble_and_one_enqueue_per_block(run):
    _, spans = run
    assert len(by_name(spans, "repro.simulate")) == 1
    assert len(by_name(spans, "repro.engine.build")) == 1
    n_blocks = N_WINDOWS // WINDOW_BLOCK
    for name in PHASES:
        assert len(by_name(spans, name)) == n_blocks, name


def test_phases_nest_inside_simulate(run):
    _, spans = run
    (_, s0, s1), = by_name(spans, "repro.simulate")
    for name in ("repro.engine.build",) + PHASES:
        for _, a, b in by_name(spans, name):
            assert s0 <= a <= b <= s1, name


def test_telemetry_walls_are_the_spans(run):
    """build_s and each block_walls row agree with the trace's spans to
    within 1 ms: the two are one pair of clock readings each."""
    res, spans = run
    tel = res.telemetry

    def secs(s):
        return (s[2] - s[1]) / 1e9

    (build,) = by_name(spans, "repro.engine.build")
    assert tel.build_s == pytest.approx(secs(build), abs=1e-3)
    enq, fold, pull, red = (by_name(spans, n) for n in PHASES)
    assert len(tel.block_walls) == len(enq)
    for k, (w0, n_win, dispatch_s, collect_s) in enumerate(tel.block_walls):
        assert (w0, n_win) == (k * WINDOW_BLOCK, WINDOW_BLOCK)
        assert dispatch_s == pytest.approx(
            secs(enq[k]) + secs(fold[k]), abs=1e-3)
        assert collect_s == pytest.approx(
            secs(pull[k]) + secs(red[k]), abs=1e-3)


def test_per_window_path_has_the_same_phases(tmp_path):
    res, spans = traced(tmp_path, make_exp(window_block=1))
    for name in PHASES:
        assert len(by_name(spans, name)) == N_WINDOWS, name
    assert [r[:2] for r in res.telemetry.block_walls] == [
        (w, 1) for w in range(N_WINDOWS)]


def test_span_seconds_without_a_trace():
    with span("test.phase", w0=0) as sp:
        pass
    assert 0.0 <= sp.seconds < 1.0
    sp.annotate(events=[1, 2])  # a no-op with the profiler off


# ------------------------------------------------------ chunk counter
@pytest.mark.parametrize("window_block", [1, 2])
def test_chunks_per_window_bound_every_lane(window_block):
    """With small chunks each window runs several chunk-loop
    iterations; no lane can fire more than chunks x chunk_steps events
    in a window, and the records stay bitwise the unfused path's."""
    chunk_steps = 4
    kw = dict(window_block=window_block, record_trajectories=True)
    kern = simulate(make_exp(use_kernel=True, kernel_chunk_steps=chunk_steps,
                             kernel_max_chunks=4096, **kw))
    plain = simulate(make_exp(**kw))
    chunks = kern.telemetry.chunks_per_window
    assert len(chunks) == N_WINDOWS
    assert plain.telemetry.chunks_per_window == ()
    assert max(chunks) > 1
    for ra, rb in zip(kern.records, plain.records):
        assert (ra.mean == rb.mean).all() and (ra.var == rb.var).all()
    assert (kern.trajectories() == plain.trajectories()).all()
    assert kern.telemetry.steps_per_window == \
        plain.telemetry.steps_per_window
    # per-lane events of each window, from a per-window replay of the
    # engine's lane counters
    eng = simulate(make_exp(use_kernel=True, kernel_chunk_steps=chunk_steps,
                            kernel_max_chunks=4096, window_block=1),
                   max_windows=0)._engine
    prev = np.asarray(eng._pool.steps)
    for w in range(N_WINDOWS):
        eng.run_window()
        steps = np.asarray(eng._pool.steps)
        assert chunks[w] * chunk_steps >= int((steps - prev).max()), w
        prev = steps
    assert tuple(eng.window_chunks) == chunks


# ------------------------------------------------------ kernel layout
def build_span_attrs(trace_dir):
    """Attributes of the one `repro.engine.build` span in the trace."""
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    (attrs,) = [dict(ev.stats) for plane in data.planes
                for line in plane.lines for ev in line.events
                if ev.name == "repro.engine.build"]
    return attrs


@pytest.mark.parametrize("model,use_kernel,layout", [
    ("lv2", True, "lanes"), ("ring8", True, "columns"), ("lv2", False, "")])
def test_kernel_layout_on_telemetry_and_build_span(tmp_path, model,
                                                    use_kernel, layout):
    """The dense exact kernel's body is recorded on Telemetry and on the
    build span: the lane body for `lv2`, the column body for `ring8`
    (S 32, R 56), nothing on the unfused path (the attribute is then
    left out of the trace)."""
    exp = Experiment(
        model=MODELS[model](), ensemble=Ensemble.make(replicas=16),
        schedule=Schedule(t_end=0.02, n_windows=2), n_lanes=8, seed=3,
        use_kernel=use_kernel)
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = simulate(exp)
    finally:
        jax.profiler.stop_trace()
    assert res.completed
    assert res.telemetry.kernel_layout == layout
    assert build_span_attrs(tmp_path).get("kernel_layout", "") == layout


# ------------------------------------------------- sharded dispatch
_SHARDED = r"""
import json, sys, tempfile
import jax
sys.path.insert(0, sys.argv[1])
from benchkit import trace as tracemod
from repro.api import Ensemble, Experiment, Schedule, simulate
from repro.core.cwc.models import lotka_volterra

out = {}
for wb in (1, 2):
    exp = Experiment(
        model=lotka_volterra(2), ensemble=Ensemble.make(replicas=32),
        schedule=Schedule(t_end=0.2, n_windows=4), n_lanes=8, seed=3,
        window_block=wb, partitioning={"n_shards": 4, "stat_blocks": 4})
    simulate(exp).final_state()  # compile outside the trace
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    try:
        res = simulate(exp)
        res.final_state()
    finally:
        jax.profiler.stop_trace()
    tr = tracemod.read(tdir)
    from jax.profiler import ProfileData
    import glob
    (path,) = glob.glob(tdir + "/plugins/profile/*/*.xplane.pb")
    builds = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name == "repro.engine.build"]
    tel = res.telemetry
    out[wb] = dict(
        spans=sorted({h[0] for h in tr.host_spans
                      if h[0].startswith("repro.")}),
        build_n_shards=[b.get("n_shards") for b in builds],
        n_shards=tel.n_shards, steps=list(tel.steps_per_window),
        per_shard=[list(r) for r in tel.steps_per_shard])
print("RESULT" + json.dumps(out))
"""


def test_sharded_run_spans_and_steps_per_shard():
    """On 4 forced host devices a sharded run puts the mesh placement
    (`repro.dispatch.place`) and the final-state gather
    (`repro.collect.gather`) in the trace, the build span carries
    `n_shards`, and Telemetry.steps_per_shard splits each window's
    events over the shards, per window and per block."""
    import json
    import os
    import subprocess

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(root / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED, str(root / "bench")], env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT")]
    for wb, got in json.loads(line[len("RESULT"):]).items():
        assert {"repro.dispatch.place", "repro.collect.gather",
                "repro.engine.build"} <= set(got["spans"]), wb
        assert got["build_n_shards"] == [4], wb
        assert got["n_shards"] == 4
        assert len(got["per_shard"]) == len(got["steps"]) == N_WINDOWS
        for steps, per in zip(got["steps"], got["per_shard"]):
            assert len(per) == 4 and sum(per) == steps and min(per) > 0


def test_one_device_run_counts_one_shard(run):
    """Off the sharded strategy the pool is one shard: each window's
    row holds the window's events alone."""
    res, spans = run
    tel = res.telemetry
    assert tel.n_shards == 1
    assert tel.steps_per_shard == tuple((s,) for s in tel.steps_per_window)
    assert {"repro.dispatch.place", "repro.collect.gather"} <= {
        s[0] for s in spans}
