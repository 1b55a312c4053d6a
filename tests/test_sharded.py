"""Sharded ensemble farm (subprocess: needs forced host devices).

Each test shells out with XLA_FLAGS=--xla_force_host_platform_device_count
so the main pytest process keeps the real 1-device platform (see
conftest.py note). Validation-only Partitioning tests that never touch
a device live in test_api.py.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_EXP = """
import numpy as np
from repro.api import (Ensemble, Experiment, Partitioning, Reduction,
                       Schedule, simulate)
from repro.core.cwc.models import lotka_volterra

def make_exp(n_shards, stat_blocks=8, policy="on_demand", **kw):
    kw.setdefault("record_trajectories", True)
    return Experiment(
        model=lotka_volterra(2),
        ensemble=Ensemble.make(replicas=16, sweep={"die": [0.3, 1.2]}),
        schedule=Schedule(t_end=1.0, n_windows=4, schema="iii",
                          policy=policy),
        reduction=Reduction.PER_POINT,
        n_lanes=8, seed=11,
        partitioning=Partitioning(n_shards=n_shards,
                                  stat_blocks=stat_blocks), **kw)
"""


def _run(body: str, devices: int = 8) -> str:
    """Run `_EXP` + the dedented test body in a forced-device child.

    The body MUST be dedented BEFORE prepending `_EXP`: `_EXP` sits at
    the margin, so dedenting the concatenation is a no-op — and the
    still-indented body then parses as unreachable code inside
    `make_exp`'s def. That exact bug made every test here vacuously
    pass (subprocesses finishing in ~1s having executed nothing); the
    sentinel asserts the body really ran to its last line.
    """
    snippet = _EXP + textwrap.dedent(body) + '\nprint("SNIPPET-RAN")\n'
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SNIPPET-RAN" in out.stdout, (
        "test body did not execute — harness regression")
    return out.stdout


def test_sharded_bit_identical_to_fused_single_device():
    """The acceptance bar: on 8 forced host devices the sharded path
    reproduces the single-device fused path bit-identically — records,
    grouped per-point stats, and trajectories — with one device
    dispatch per window (O(1) in shard count)."""
    _run("""
    base = simulate(make_exp(n_shards=1))
    for K in (2, 4, 8):
        shard = simulate(make_exp(n_shards=K))
        for a, b in zip(base.records, shard.records):
            assert a.t == b.t and a.n == b.n
            assert (a.mean == b.mean).all()
            assert (a.var == b.var).all()
            assert (a.ci90 == b.ci90).all()
        pb, ps = base.per_point(), shard.per_point()
        for k in ("n", "mean", "var", "ci90"):
            assert (pb[k] == ps[k]).all(), (K, k)
        assert (base.trajectories() == shard.trajectories()).all()
        assert shard.telemetry.dispatches == 4  # one per window, any K
    """)


def test_sharded_records_invariant_to_shard_count_without_pinning():
    """stat_blocks defaults to n_shards, so two different shard counts
    only compare bitwise when stat_blocks is pinned — which the default
    does NOT do across meshes. Pinning blocks=4 must equalise K=2/K=4."""
    _run("""
    a = simulate(make_exp(n_shards=2, stat_blocks=4))
    b = simulate(make_exp(n_shards=4, stat_blocks=4))
    for ra, rb in zip(a.records, b.records):
        assert (ra.mean == rb.mean).all() and (ra.var == rb.var).all()
    """, devices=4)


def test_predictive_groups_stay_within_shards():
    """The predictive policy must form cost-homogeneous groups WITHIN
    shard blocks (no cross-shard gathers), and still reproduce the
    on_demand results bitwise (keyed per-lane RNG)."""
    _run("""
    from repro.api.run import build_engine
    pred = make_exp(n_shards=4, policy="predictive")
    eng = build_engine(pred)
    res_p = simulate(pred)
    res_o = simulate(make_exp(n_shards=4, policy="on_demand"))
    for a, b in zip(res_p.records, res_o.records):
        assert (a.mean == b.mean).all()
    # drive a couple of windows so EMA costs are non-trivial, then
    # check every group is contained in one shard block
    eng.run_window(); eng.run_window()
    per = eng.cfg.n_instances // 4
    for g in eng.scheduler.groups():
        shards = set(int(i) // per for i in g)
        assert len(shards) == 1, (g, shards)
    """, devices=4)


def test_sharded_checkpoint_is_mesh_shape_agnostic_artifact():
    """checkpoint() gathers to plain global npz arrays — restorable by
    any mesh — and a same-process 8-shard resume is bit-identical."""
    _run("""
    import tempfile, os
    ck = os.path.join(tempfile.mkdtemp(), "ck")
    clean = simulate(make_exp(n_shards=8))
    part = simulate(make_exp(n_shards=8), max_windows=2,
                    checkpoint_path=ck)
    z = np.load(ck + ".npz")
    assert z["x"].shape[0] == 32  # global pool, not a shard
    resumed = simulate(make_exp(n_shards=8), checkpoint_path=ck,
                       resume=True)
    assert (np.stack([r.mean for r in resumed.records])
            == np.stack([r.mean for r in clean.records])).all()
    """)


def test_sharded_step_rebuilds_when_group_count_changes():
    """Re-calling set_groups with a different group count must rebuild
    the cached sharded step (its jit closes over n_groups)."""
    _run("""
    from repro.api.run import build_engine
    eng = build_engine(make_exp(n_shards=4))
    eng.run_window()
    assert eng.grouped_stats()[-1].mean.shape == (2, 2)
    eng.set_groups(np.arange(32, dtype=np.int32) % 4)
    eng.run_window()
    assert eng.grouped_stats()[-1].mean.shape == (4, 2)
    """, devices=4)


def test_sharded_schema_ii_buffers_global_trajectories():
    """Schema ii on the sharded path gathers per-window samples for
    post-hoc use exactly like the fused path."""
    _run("""
    a = simulate(make_exp(n_shards=8).with_(
        schedule=Schedule(t_end=1.0, n_windows=3, schema="ii")))
    b = simulate(make_exp(n_shards=1).with_(
        schedule=Schedule(t_end=1.0, n_windows=3, schema="ii")))
    ta, tb = a.trajectories(), b.trajectories()
    assert ta.shape == (32, 3, 2)
    assert (ta == tb).all()
    """)


def test_kernel_composes_with_sharded_dispatch():
    """The Pallas fused kernel under the sharded strategy (the paper's
    two families composed): on 8 forced host devices, per-shard kernel
    windows reproduce the single-device kernel run — and the UNFUSED
    jnp path — bit-identically for 1/2/4/8 shards (counter-based
    per-lane RNG; stat_blocks pinned), one device dispatch per window."""
    _run("""
    base = simulate(make_exp(n_shards=1, use_kernel=True))
    plain = simulate(make_exp(n_shards=1))
    assert (np.stack([r.mean for r in base.records])
            == np.stack([r.mean for r in plain.records])).all()
    assert (base.trajectories() == plain.trajectories()).all()
    for K in (2, 4, 8):
        shard = simulate(make_exp(n_shards=K, use_kernel=True))
        for a, b in zip(base.records, shard.records):
            assert a.t == b.t and a.n == b.n
            assert (a.mean == b.mean).all()
            assert (a.var == b.var).all()
            assert (a.ci90 == b.ci90).all()
        pb, ps = base.per_point(), shard.per_point()
        for k in ("n", "mean", "var", "ci90"):
            assert (pb[k] == ps[k]).all(), (K, k)
        assert (base.trajectories() == shard.trajectories()).all()
        assert shard.telemetry.dispatches == 4  # one per window, any K
    """)


def test_tau_leap_composes_with_sharded_dispatch():
    """Method.TAU_LEAP under the sharded strategy: records, grouped
    per-point stats, and trajectories bit-identical across 1/2/4/8
    shards AND across the jnp/kernel window bodies (the same
    `tau_step_core` runs per shard under shard_map), one dispatch per
    window — the full exact-SSA invariant matrix, on the second
    algorithm."""
    _run("""
    base = simulate(make_exp(n_shards=1, method="tau_leap"))
    assert sum(base.telemetry.leaps_per_window) > 0  # it actually leaps
    kern1 = simulate(make_exp(n_shards=1, method="tau_leap",
                              use_kernel=True))
    assert (np.stack([r.mean for r in base.records])
            == np.stack([r.mean for r in kern1.records])).all()
    assert (base.trajectories() == kern1.trajectories()).all()
    for K in (2, 4, 8):
        for kernel in (False, True):
            shard = simulate(make_exp(n_shards=K, method="tau_leap",
                                      use_kernel=kernel))
            for a, b in zip(base.records, shard.records):
                assert a.t == b.t and a.n == b.n
                assert (a.mean == b.mean).all()
                assert (a.var == b.var).all()
                assert (a.ci90 == b.ci90).all()
            pb, ps = base.per_point(), shard.per_point()
            for k in ("n", "mean", "var", "ci90"):
                assert (pb[k] == ps[k]).all(), (K, kernel, k)
            assert (base.trajectories() == shard.trajectories()).all()
            assert shard.telemetry.dispatches == 4  # one per window
            assert (shard.telemetry.leaps_per_window
                    == base.telemetry.leaps_per_window)
    """)


def test_supersteps_compose_with_sharded_dispatch():
    """window_block under the sharded strategy: the per-shard window
    body (jnp or Pallas kernel) scans W windows inside ONE shard_map'd
    dispatch, per-window psum-gathered stat stacks ride the record
    ring, and records/grouped stats/trajectories stay bit-identical to
    the per-window single-device baseline for 2/4/8 shards ×
    window_block ∈ {2, 4} × both window bodies — at 4 windows and
    window_block=4 the whole run is ONE dispatch and ONE blocking
    pull."""
    _run("""
    base = simulate(make_exp(n_shards=1))
    for K in (2, 4, 8):
        for wb in (2, 4):
            for kernel in (False, True):
                shard = simulate(make_exp(n_shards=K, window_block=wb,
                                          use_kernel=kernel))
                for a, b in zip(base.records, shard.records):
                    assert a.t == b.t and a.n == b.n
                    assert (a.mean == b.mean).all()
                    assert (a.var == b.var).all()
                    assert (a.ci90 == b.ci90).all()
                pb, ps = base.per_point(), shard.per_point()
                for k in ("n", "mean", "var", "ci90"):
                    assert (pb[k] == ps[k]).all(), (K, wb, k)
                assert (base.trajectories()
                        == shard.trajectories()).all()
                tele = shard.telemetry
                assert tele.dispatches == -(-4 // wb), (K, wb)
                assert tele.host_syncs == -(-4 // wb), (K, wb)
    """)


def test_depth_k_pipeline_composes_with_sharded_dispatch():
    """PR9: pipeline_depth on the sharded path — K ∈ {1, 2, 4} blocks
    in flight, including the predictive policy's in-scan device cost
    carry (shard-local argsort, no host round trips) — stays bitwise
    with the single-device per-window baseline; the depth only moves
    the collect point, never the dispatched work."""
    _run("""
    base = simulate(make_exp(n_shards=1))
    pred_base = simulate(make_exp(n_shards=1, policy="predictive"))
    for a, b in zip(base.records, pred_base.records):
        assert (a.mean == b.mean).all()
    for depth in (1, 2, 4):
        for policy in ("on_demand", "predictive"):
            shard = simulate(make_exp(n_shards=4, window_block=2,
                                      pipeline_depth=depth,
                                      policy=policy))
            for a, b in zip(base.records, shard.records):
                assert a.t == b.t and a.n == b.n
                assert (a.mean == b.mean).all()
                assert (a.var == b.var).all()
                assert (a.ci90 == b.ci90).all()
            pb, ps = base.per_point(), shard.per_point()
            for k in ("n", "mean", "var", "ci90"):
                assert (pb[k] == ps[k]).all(), (depth, policy, k)
            assert (base.trajectories() == shard.trajectories()).all()
            tele = shard.telemetry
            assert tele.pipeline_depth == depth
            assert tele.dispatches == 2 and tele.host_syncs == 2
    """, devices=4)


def test_superstep_checkpoint_resumes_on_sharded_path():
    """A block-boundary checkpoint from a sharded superstep run is the
    same mesh-shape-agnostic artifact: resume on a different shard
    count and window_block, bitwise."""
    _run("""
    import tempfile, os
    ck = os.path.join(tempfile.mkdtemp(), "ck")
    clean = simulate(make_exp(n_shards=1))
    simulate(make_exp(n_shards=8, window_block=2), max_windows=2,
             checkpoint_path=ck)
    z = np.load(ck + ".npz")
    assert int(z["window"]) == 2
    resumed = simulate(make_exp(n_shards=4, window_block=2),
                       checkpoint_path=ck, resume=True)
    assert (np.stack([r.mean for r in resumed.records])
            == np.stack([r.mean for r in clean.records])).all()
    """)


def test_kernel_truncation_raises_under_sharded_dispatch():
    """A chunk-budget overrun on ANY shard surfaces (psum'd flag) —
    never a silent partial window."""
    _run("""
    import warnings
    from repro.core.dispatch import Partitioning
    from repro.core.engine import SimConfig, SimulationEngine
    from repro.kernels.ops import FusedWindowTruncated

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng = SimulationEngine(
            lotka_volterra(2),
            SimConfig(n_instances=32, t_end=2.0, n_windows=2, n_lanes=8,
                      schema="iii", seed=5, use_kernel=True,
                      kernel_chunk_steps=1, kernel_max_chunks=1),
            partitioning=Partitioning(n_shards=4, stat_blocks=8))
    try:
        eng.run_window()
        raise AssertionError("expected FusedWindowTruncated")
    except FusedWindowTruncated:
        pass
    """, devices=4)


def test_four_shard_farm_matches_one_shard_and_the_plain_reference():
    """The `lotka77x4` deployment in small: 1,024 replicas of
    `lotka_volterra(2)` through the kernel on 4 forced host devices,
    the pool given as plain data (a mapping, as the benchmark's traffic
    file gives it). Records and final states are bitwise those of one
    shard at stat_blocks 4, and the per-window means and variances stay
    within the benchmark cell's z_stats limit of the plain reference
    (bench/benchkit/reference.py) run on the same network."""
    _run("""
    import sys
    sys.path.insert(0, %r)
    from benchkit import campaign, check, reference, spec
    from benchkit.harness import program_species

    args = dict(n_species=2, k_reproduce=1.0, k_eat=0.005, k_die=0.6,
                prey0=1000, pred0=1000)
    exp = Experiment(
        model=lotka_volterra(**args), ensemble=Ensemble(replicas=1024),
        schedule=Schedule(t_end=0.2, n_windows=4, schema="iii"),
        seed=2340, n_lanes=256, use_kernel=True, window_block=2,
        kernel_chunk_steps=64, kernel_max_chunks=4096,
        partitioning={"n_shards": 4, "stat_blocks": 4})
    four = simulate(exp)
    one = simulate(exp.with_(partitioning=Partitioning(n_shards=1,
                                                       stat_blocks=4)))
    assert four._engine._dispatch.name == "sharded"
    assert four.telemetry.n_shards == 4 and one.telemetry.n_shards == 1
    for a, b in zip(four.records, one.records):
        assert a.n == b.n == 1024
        assert (a.mean == b.mean).all() and (a.var == b.var).all()
    final = four.final_state()
    assert (final == one.final_state()).all()

    cell = spec.resolve("lotka77x4.shard4")
    net = spec.load_module("configs", "lotka77").network(args)
    ens = campaign.EnsembleRun(
        seed=2340, replicas=1024, wall_s=0.0,
        obs_names=tuple(four.obs_names),
        mean=np.stack([np.asarray(r.mean, np.float64)
                       for r in four.records]),
        var=np.stack([np.asarray(r.var, np.float64)
                      for r in four.records]),
        n=np.asarray([r.n for r in four.records]), final=final,
        steps_per_window=four.telemetry.steps_per_window, completed=True)
    order = program_species(exp.model)
    cols = check.obs_columns(net, order, ens.obs_names)
    exact = check.exact_numbers(ens, cols)
    assert exact["n_short"] == 0 and exact["bad_states"] == 0
    ref = reference.simulate(net, 1024, four.t_grid, (2340, 0, 1))
    assert not ref.capped.any()
    z = check.reference_numbers(ens, ref, net, order, cols)
    assert z["z_stats"] <= cell.limits["z_stats"], z
    """ % os.path.join(REPO, "bench"), devices=4)
