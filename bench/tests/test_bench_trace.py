"""The trace reduction, on a small trace in the profiler's own format."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit import trace  # noqa: E402

# one device, four operations (ns): a kernel (a Mosaic custom call)
# 1000-3000 and 2500-4000 (overlapping), an XLA fusion 6000-7000, the
# kernel again 9000-9500; host spans: bench.ensemble 1000-8000 with
# JAX's tracing 1100-1500 (a nested tracing 1200-1300 inside it) and
# lowering 1500-1900, a dispatch 4200-5800, then bench.collect
# 8000-10000
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 500000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 8500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%ssa_kernel.3 = f32[8,2]{1,0} custom-call(f32[8,2]{1,0} %p.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.7" } }
  event_metadata { key: 3 value { id: 3 name: "jit_block_body" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 7000000 }
    events { metadata_id: 2 offset_ps: 4200000 duration_ps: 1600000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 1100000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 1200000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 1500000 duration_ps: 400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.ensemble" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(block_body)" } }
  event_metadata { key: 3 value { id: 3 name: "bench.collect" } }
  event_metadata { key: 4 value { id: 4 name: "trace_to_jaxpr_dynamic" } }
  event_metadata { key: 5 value { id: 5 name: "lower_sharding_computation" } }
}
"""


# the same run once its programs are reused: JAX's tracing 200-400 and
# lowering 400-600 only in the canary, with its add 650-700 on the
# device, all before the first benchmark span; none in the window
JAX_IN_WINDOW = """
    events { metadata_id: 4 offset_ps: 1100000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 1200000 duration_ps: 100000 }
    events { metadata_id: 5 offset_ps: 1500000 duration_ps: 400000 }
"""
JAX_CANARY = """
    events { metadata_id: 4 offset_ps: 200000 duration_ps: 200000 }
    events { metadata_id: 5 offset_ps: 400000 duration_ps: 200000 }
"""
FIRST_OP = "events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }"
CANARY_OP = "events { metadata_id: 2 offset_ps: 650000 duration_ps: 50000 }"
REUSED = XSPACE.replace(JAX_IN_WINDOW, JAX_CANARY).replace(
    FIRST_OP, f"{CANARY_OP}\n    {FIRST_OP}")


def _profile(text):
    from jax.profiler import ProfileData

    return trace.from_profile(ProfileData.from_text_proto(text))


@pytest.fixture(scope="module")
def tr():
    return _profile(XSPACE)


@pytest.fixture(scope="module")
def reused():
    assert JAX_IN_WINDOW in XSPACE and FIRST_OP in XSPACE
    return _profile(REUSED)


def test_window_is_the_benchmark_spans(tr):
    assert (tr.t0_ns, tr.t1_ns) == (1000, 10000)
    assert tr.window_s == pytest.approx(9e-6)


def test_busy_is_the_union_of_operations(tr):
    # 1000-4000, 6000-7000, 9000-9500: 4500 ns; the module line is
    # not an operation line and is not counted again
    assert trace.busy_s(tr) == pytest.approx(4.5e-6)


def test_kernel_time_and_calls(tr):
    secs, calls = trace.kernel_time(tr, r"(?s)%ssa_kernel(\.\d+)? = .*")
    assert calls == 3
    assert secs == pytest.approx((2000 + 1500 + 500) * 1e-9)
    assert trace.kernel_time(tr, r"ssa") == (0.0, 0)  # whole names only


def test_top_ops(tr):
    top = trace.top_ops(tr)
    assert [n for n, _ in top] == ["ssa_kernel.3 (custom-call)", "fusion.7"]
    assert top[0][1] == pytest.approx(4e-6)


def test_idle_gaps_named_by_host_span(tr):
    gaps = dict(trace.idle_gaps(tr))
    # 4000-6000 (mid 5000, inside the dispatch), 7000-9000 (mid 8000:
    # bench.collect starts there), 9500-10000 (collect)
    assert gaps["bench.ensemble > PjitFunction(block_body)"] == \
        pytest.approx(2e-6)
    assert gaps["bench.collect"] == pytest.approx(2.5e-6)
    assert sum(gaps.values()) == pytest.approx(
        tr.window_s - trace.busy_s(tr))


class _Run:
    def __init__(self, tr):
        self.trace = tr


def test_idle_metric_reader(tr):
    from benchkit import spec

    idle = spec.load_module("metrics", "device.idle").read(_Run(tr))
    assert idle == pytest.approx(100 * (1 - 4.5 / 9))
    assert spec.load_module("metrics", "device.idle").read(
        _Run(None)) is None


def test_host_union_counts_nested_spans_once(tr):
    secs, spans = trace.host_union_s(
        tr, {"trace_to_jaxpr_dynamic", "lower_sharding_computation"})
    assert spans == 3 and secs == pytest.approx(800e-9)
    assert trace.host_union_s(tr, {"no such span"}) == (0.0, 0)


def test_custom_calls_are_the_kernels(tr):
    assert trace.custom_calls(tr) == {"ssa_kernel.3 (custom-call)"}


class _Window:
    compile_s = 1e-6


class _Ensembles(_Run):
    window = _Window()
    ensembles = [None, None]


def test_entry_metric_reader(tr):
    from benchkit import spec

    read = spec.load_module("metrics", "entry.compile_s").read
    assert read(_Ensembles(tr)) == pytest.approx((800e-9 + 1e-6) / 2)
    bare = trace.Trace(device_ops=tr.device_ops, host_spans=[],
                       t0_ns=tr.t0_ns, t1_ns=tr.t1_ns)
    assert read(_Ensembles(bare)) is None


class _Rec:
    def __init__(self, tr, compile_s=0.0):
        self.trace = tr
        self.cell = type("Cell", (), {"name": "test.cell"})()
        self.window = type("Window", (), {"compile_s": compile_s})()
        self.ensembles = [None, None]


def test_canary_before_the_window_moves_nothing_of_it(tr, reused):
    # the window, the busy time and the breakdown are the first run's:
    # the canary's spans and its device op lie before the window
    assert (reused.t0_ns, reused.t1_ns) == (tr.t0_ns, tr.t1_ns)
    assert trace.busy_s(reused) == trace.busy_s(tr)
    assert trace.top_ops(reused) == trace.top_ops(tr)
    assert trace.idle_gaps(reused) == trace.idle_gaps(tr)


def test_entry_metric_reads_zero_where_the_window_compiled_nothing(
        reused):
    from benchkit import spec

    entry = spec.load_module("metrics", "entry.compile_s")
    assert trace.host_union_s(reused, entry.SPANS) == (0.0, 2)
    assert entry.read(_Rec(reused)) == 0.0
    # backend compiles in the window still count
    assert entry.read(_Rec(reused, compile_s=1e-6)) == pytest.approx(
        0.5e-6)


def test_entry_metric_without_any_compile_span_fails_the_run(tr):
    # no JAX span anywhere, not even the canary's: the names went stale
    from benchkit import harness

    stale = trace.Trace(
        device_ops=tr.device_ops, t0_ns=tr.t0_ns, t1_ns=tr.t1_ns,
        host_spans=[h for h in tr.host_spans
                    if h[0].startswith(trace.BENCH_SPAN)])
    listed = [{"name": "entry.compile_s", "unit": "s"}]
    rec = _Rec(stale, compile_s=1e-6)
    metrics = harness.read_metrics(listed, rec)
    assert metrics == {}
    with pytest.raises(harness.MetricMissing, match="entry.compile_s"):
        harness.check_listed(listed, metrics, rec)


def _listed(*names):
    return [{"name": n} for n in names]


def test_listed_metric_that_reads_nothing_fails_the_run(tr):
    from benchkit import harness

    with pytest.raises(harness.MetricMissing, match="device.idle"):
        harness.check_listed(_listed("device.idle"), {}, _Rec(tr))
    harness.check_listed(_listed("device.idle"), {"device.idle": {}},
                         _Rec(tr))


def test_renamed_kernel_fails_the_run(tr):
    # a kernel ran, but not under the name the metric's file matches
    from benchkit import harness

    with pytest.raises(harness.MetricMissing, match="ssa_kernel.3"):
        harness.check_listed(_listed("kernel.ssa_dense.share"), {},
                             _Rec(tr))


def test_kernel_taken_off_the_path_leaves_its_metric_silent(tr):
    from benchkit import harness

    xla_only = {d: [o for o in ops if "custom-call" not in o[0]]
                for d, ops in tr.device_ops.items()}
    bare = trace.Trace(device_ops=xla_only, host_spans=tr.host_spans,
                       t0_ns=tr.t0_ns, t1_ns=tr.t1_ns)
    harness.check_listed(_listed("kernel.ssa_dense.share",
                                 "kernel.ssa_dense_roofline"), {},
                         _Rec(bare))


def _no_compile_ensemble(cell, seed, traced=False):
    """An ensemble whose programs are all reused: it compiles nothing."""
    from benchkit import campaign

    with campaign._span("bench.ensemble", traced):
        time.sleep(0.01)
    return campaign.EnsembleRun(
        seed=seed, replicas=1, wall_s=0.01, obs_names=(),
        mean=np.zeros((1, 0)), var=np.zeros((1, 0)), n=np.ones(1),
        final=np.zeros((1, 0)), steps_per_window=(0,), completed=True)


@pytest.mark.parametrize("programs", ["fresh", "reused"])
def test_traced_window_leaves_the_canary_out(monkeypatch, tmp_path,
                                             programs):
    """A traced run of the tiny cell on the CPU: the canary's compile
    spans end before the window and its compile seconds are not the
    window's. With the engine's own ensembles each builds and lowers
    its programs in the window; with ensembles that compile nothing
    `entry.compile_s` reads 0."""
    from benchkit import campaign, device, harness, spec

    data = Path(__file__).resolve().parent / "data"
    cell = spec.resolve("lv2-tiny.xla",
                        spec.load_spec(data / "BENCHMARK.json"), data)
    if programs == "reused":
        monkeypatch.setattr(campaign, "warm_up", lambda *a: None)
        monkeypatch.setattr(campaign, "run_ensemble", _no_compile_ensemble)
    clock = device.CompileClock()
    win = campaign.run_window(cell, 2 ** 31 + 11, 0.0, clock,
                              device.start_clock(), str(tmp_path))
    tr = trace.read(str(tmp_path))
    entry = spec.load_module("metrics", "entry.compile_s")
    jax_spans = [h for h in tr.host_spans if h[0] in entry.SPANS]
    canary = [h for h in jax_spans if h[1] < tr.t0_ns]
    assert canary and all(e <= tr.t0_ns for _, _, e in canary)
    rec = harness.RunRecord(cell=cell, window=win, trace=tr, peaks=None,
                            network=None)
    if programs == "reused":
        assert jax_spans == canary
        assert clock.read()[0] > 0  # the canary compiled, before c0
        assert (win.compile_s, win.cache_misses) == (0.0, 0)
        assert entry.read(rec) == 0.0
    else:
        assert len(canary) < len(jax_spans)
        assert entry.read(rec) > 0
