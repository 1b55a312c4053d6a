"""repro.api: spec validation, old-API shim equivalence, grouped
per-sweep-point reduction, checkpoint/resume through SimulationResult,
and the sink close() lifecycle."""
import warnings

import numpy as np
import pytest

from repro.api import (
    CsvSink,
    Ensemble,
    Experiment,
    ExperimentError,
    Partitioning,
    Policy,
    Reduction,
    Schedule,
    Schema,
    simulate,
)
from repro.core.cwc.models import lotka_volterra
from repro.core.engine import SimConfig, SimulationEngine


def _exp(schema="iii", replicas=24, windows=4, seed=13, **kw):
    return Experiment(
        model=lotka_volterra(2),
        ensemble=Ensemble.make(replicas=replicas),
        schedule=Schedule(t_end=1.0, n_windows=windows, schema=schema),
        n_lanes=8, seed=seed, **kw)


def _old_engine(schema, replicas=24, windows=4, seed=13, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return SimulationEngine(
            lotka_volterra(2),
            SimConfig(n_instances=replicas, t_end=1.0, n_windows=windows,
                      n_lanes=8, schema=schema, seed=seed, **kw))


# ---------------------------------------------------------- validation
def test_validation_errors_name_the_field():
    good = _exp()
    with pytest.raises(ExperimentError, match="t_end"):
        simulate(good.with_(schedule=Schedule(t_end=0.0, n_windows=4)))
    with pytest.raises(ExperimentError, match="n_windows"):
        simulate(good.with_(schedule=Schedule(t_end=1.0, n_windows=0)))
    with pytest.raises(ExperimentError, match="replicas"):
        simulate(good.with_(ensemble=Ensemble.make(replicas=0)))
    with pytest.raises(ExperimentError, match="n_lanes"):
        simulate(good.with_(n_lanes=0))
    with pytest.raises(ExperimentError, match="PREDICTIVE"):
        simulate(good.with_(schedule=Schedule(
            t_end=1.0, n_windows=4, schema=Schema.STATIC_FARM,
            policy=Policy.PREDICTIVE)))
    with pytest.raises(ExperimentError, match="Reduction"):
        simulate(good.with_(reduction="per_point"))
    with pytest.raises(ExperimentError, match="Ensemble"):
        simulate(good.with_(ensemble=None))


def test_schema_policy_coercion_and_unknown_strings():
    assert Schema.coerce("iii") is Schema.ONLINE
    assert Schema.coerce("STATIC_FARM") is Schema.STATIC_FARM
    assert Policy.coerce("on_demand") is Policy.ON_DEMAND
    with pytest.raises(ExperimentError, match="unknown schema"):
        Schema.coerce("iv")
    with pytest.raises(ExperimentError, match="unknown policy"):
        Policy.coerce("greedy")
    # Schedule coerces strings at construction
    assert Schedule(t_end=1.0, n_windows=2, schema="ii",
                    policy="predictive").schema is Schema.TIME_SLICED


def test_sweep_unknown_rate_name_is_an_experiment_error():
    exp = _exp().with_(ensemble=Ensemble.make(
        replicas=4, sweep={"not_a_reaction": [1.0, 2.0]}))
    with pytest.raises(ExperimentError, match="not_a_reaction"):
        simulate(exp)


def test_partitioning_validation():
    """Partitioning is pure data; its constraints surface as
    ExperimentError before any device is touched."""
    good = _exp()  # 24 instances
    with pytest.raises(ExperimentError, match="n_shards"):
        simulate(good.with_(partitioning=Partitioning(n_shards=0)))
    with pytest.raises(ExperimentError, match="divide evenly"):
        simulate(good.with_(partitioning=Partitioning(n_shards=5)))
    with pytest.raises(ExperimentError, match="stat_blocks"):
        simulate(good.with_(
            partitioning=Partitioning(n_shards=2, stat_blocks=3)))
    with pytest.raises(ExperimentError, match="multiple"):
        simulate(good.with_(
            partitioning=Partitioning(n_shards=4, stat_blocks=2)))
    with pytest.raises(ExperimentError, match="Partitioning"):
        simulate(good.with_(partitioning="data"))
    with pytest.raises(ExperimentError, match="host_loop"):
        simulate(good.with_(partitioning=Partitioning(n_shards=2),
                            host_loop=True))
    # more shards than visible devices: named, actionable error
    with pytest.raises(ExperimentError, match="device"):
        simulate(good.with_(partitioning=Partitioning(n_shards=24)))
    # n_shards=1 is a plain fused run and needs no extra devices
    assert Partitioning(n_shards=2).blocks == 2
    assert Partitioning(n_shards=2, stat_blocks=8).blocks == 8
    res = simulate(good.with_(partitioning=Partitioning(n_shards=1)))
    assert res.completed


def test_partitioning_from_a_mapping():
    """A mapping of Partitioning's fields (plain data, as a JSON file
    gives) is coerced at construction and equals the object."""
    good = _exp()  # 24 instances
    exp = good.with_(partitioning={"n_shards": 2, "stat_blocks": 4})
    assert exp.partitioning == Partitioning(n_shards=2, stat_blocks=4)
    assert good.with_(partitioning={}).partitioning == Partitioning()
    obj = Partitioning(n_shards=1, stat_blocks=8)
    assert good.with_(partitioning=obj).partitioning is obj
    res = simulate(good.with_(partitioning={"n_shards": 1,
                                            "stat_blocks": 8}))
    ref = simulate(good.with_(partitioning=obj))
    assert (res.means() == ref.means()).all()


@pytest.mark.parametrize("fields,match", [
    ({"n_shard": 2}, "unexpected keyword"),
    ({"n_shards": 2, "blocks": 4}, "unexpected keyword"),
    ({"n_shards": "2"}, "must be an int"),
    ({"n_shards": True}, "must be an int"),
    ({"stat_blocks": 2.0}, "must be an int"),
    ({"axis": 0}, "mesh axis name"),
])
def test_partitioning_mapping_refuses_unknown_keys_and_types(fields, match):
    """An unknown key fails at construction, a value of the wrong type
    at validate(): the object's own checks, so a Partitioning built
    directly with that value is refused alike."""
    with pytest.raises(ExperimentError, match=match):
        _exp().with_(partitioning=fields).validate()
    if "unexpected keyword" not in match:
        with pytest.raises(ExperimentError, match=match):
            _exp().with_(partitioning=Partitioning(**fields)).validate()


def test_partitioning_mapping_validates_like_the_object():
    good = _exp()  # 24 instances
    with pytest.raises(ExperimentError, match="divide evenly"):
        simulate(good.with_(partitioning={"n_shards": 5}))
    with pytest.raises(ExperimentError, match="multiple"):
        simulate(good.with_(partitioning={"n_shards": 4,
                                          "stat_blocks": 2}))


def test_stat_blocks_changes_merge_tree_but_stays_close():
    """Records are a function of stat_blocks (the pinned merge tree):
    blocks=1 reproduces the historical records bitwise; blocks=8 agrees
    to float tolerance (same Welford algebra, different fold order)."""
    legacy = simulate(_exp())
    blocked = simulate(_exp().with_(
        partitioning=Partitioning(n_shards=1, stat_blocks=8)))
    assert (legacy.means() == simulate(_exp()).means()).all()
    np.testing.assert_allclose(blocked.means(), legacy.means(),
                               rtol=1e-5, atol=1e-5)
    var_l = np.stack([r.var for r in legacy.records])
    var_b = np.stack([r.var for r in blocked.records])
    # variance merges via s2 - n*mean^2, which cancels in float32 when
    # mean >> std — hence the looser bound than the means get
    np.testing.assert_allclose(var_b, var_l, rtol=5e-3, atol=1e-3)


# --------------------------------------------------- shim equivalence
@pytest.mark.parametrize("schema", ["i", "ii", "iii"])
def test_old_api_shim_bit_identical(schema):
    """simulate(Experiment) reproduces SimulationEngine(model, SimConfig)
    records bit-identically for a fixed seed, on every schema."""
    res = simulate(_exp(schema=schema))
    eng = _old_engine(schema)
    old = eng.run()
    assert len(old) == len(res.records)
    for a, b in zip(old, res.records):
        assert a.t == b.t and a.window == b.window and a.n == b.n
        assert (a.mean == b.mean).all()
        assert (a.var == b.var).all()
        assert (a.ci90 == b.ci90).all()


@pytest.mark.parametrize("schema", ["i", "ii", "iii"])
def test_host_loop_and_window_step_bit_identical(schema):
    """The legacy per-group gather/scatter path and the fused scan-based
    window_step produce bit-identical records AND trajectories."""
    new = simulate(_exp(schema=schema, record_trajectories=True))
    old = simulate(_exp(schema=schema, record_trajectories=True,
                        host_loop=True))
    assert (new.means() == old.means()).all()
    assert (new.trajectories() == old.trajectories()).all()
    # and measurably fewer device dispatches (3 groups of 8 lanes)
    assert new.telemetry.dispatches < old.telemetry.dispatches


def test_trajectories_schema_i_and_ii_present_and_equal():
    """Schema i materialises full trajectories (regression: it used to
    return None) and matches schema ii bitwise (keyed per-lane RNG)."""
    t_i = simulate(_exp(schema="i")).trajectories()
    t_ii = simulate(_exp(schema="ii")).trajectories()
    assert t_i is not None and t_i.shape == (24, 4, 2)
    assert (t_i == t_ii).all()
    # schema iii stays memory-bounded unless opted in
    assert simulate(_exp(schema="iii")).trajectories() is None
    t_iii = simulate(_exp(schema="iii",
                          record_trajectories=True)).trajectories()
    assert (t_iii == t_ii).all()


# ----------------------------------------------------- grouped stats
def test_per_point_grouped_reduction_matches_numpy():
    exp = Experiment(
        model=lotka_volterra(2),
        ensemble=Ensemble.make(replicas=8, sweep={"die": [0.1, 2.0]}),
        schedule=Schedule(t_end=2.0, n_windows=3, schema="ii"),
        reduction=Reduction.PER_POINT,
        n_lanes=16, seed=9)
    res = simulate(exp)
    pp = res.per_point()
    assert pp["mean"].shape == (3, 2, 2)
    assert pp["points"] == [{"die": 0.1}, {"die": 2.0}]
    assert (pp["n"] == 8).all()
    # oracle: per-point stats straight from the buffered trajectories
    traj = res.trajectories()  # (16, 3, 2)
    for p, sl in ((0, slice(0, 8)), (1, slice(8, 16))):
        np.testing.assert_allclose(
            pp["mean"][:, p], traj[sl].mean(axis=0), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            pp["var"][:, p], traj[sl].var(axis=0, ddof=1),
            rtol=1e-4, atol=1e-4)
    # higher predator death rate -> fewer predators at the end
    assert pp["mean"][-1, 1, 1] < pp["mean"][-1, 0, 1]


def test_ensemble_reduction_has_no_grouped_stats():
    assert simulate(_exp()).per_point() is None


# ------------------------------------------------- checkpoint / resume
def test_checkpoint_resume_in_process(tmp_path):
    clean = simulate(_exp(windows=6))
    part = simulate(_exp(windows=6), max_windows=2,
                    checkpoint_path=str(tmp_path / "ck"))
    assert not part.completed and part.windows_run == 2
    part.resume()
    assert part.completed
    assert (part.means() == clean.means()).all()


def test_checkpoint_resume_from_file(tmp_path):
    """A fresh simulate(resume=True) continues bit-identically, records
    before the checkpoint included (replayed from the npz)."""
    ck = str(tmp_path / "ck")
    clean = simulate(_exp(windows=6))
    simulate(_exp(windows=6), max_windows=3, checkpoint_path=ck)
    resumed = simulate(_exp(windows=6), checkpoint_path=ck, resume=True)
    assert resumed.completed
    assert len(resumed.records) == 6
    assert (resumed.means() == clean.means()).all()


def test_resume_keeps_csv_and_grouped_stats_complete(tmp_path):
    """File-based resume replays restored records into fresh sinks and
    restores per-point grouped stats, so neither loses the
    pre-checkpoint windows."""
    ck = str(tmp_path / "ck")
    csv_path = str(tmp_path / "out.csv")

    def exp(sink=None):
        return Experiment(
            model=lotka_volterra(2),
            ensemble=Ensemble.make(replicas=8, sweep={"die": [0.1, 2.0]}),
            schedule=Schedule(t_end=1.0, n_windows=5, schema="iii"),
            reduction=Reduction.PER_POINT,
            sinks=(sink,) if sink else (),
            n_lanes=16, seed=2)

    simulate(exp(), max_windows=2, checkpoint_path=ck)
    resumed = simulate(exp(CsvSink(csv_path, ["prey", "pred"])),
                       checkpoint_path=ck, resume=True)
    clean = simulate(exp())
    assert len(open(csv_path).read().strip().splitlines()) == 6  # hdr + 5
    pp, pp_clean = resumed.per_point(), clean.per_point()
    assert pp["mean"].shape == pp_clean["mean"].shape == (5, 2, 2)
    assert (pp["mean"] == pp_clean["mean"]).all()


def test_max_steps_per_window_same_on_both_paths():
    sched = Schedule(t_end=1.0, n_windows=3, schema="iii",
                     max_steps_per_window=5)
    new = simulate(_exp().with_(schedule=sched))
    old = simulate(_exp().with_(schedule=sched, host_loop=True))
    unbounded = simulate(_exp(windows=3))
    assert (new.means() == old.means()).all()
    # the cap actually bit (5 SSA steps rarely reach the horizon)
    assert not (new.means() == unbounded.means()).all()
    with pytest.raises(ExperimentError, match="max_steps_per_window"):
        simulate(_exp().with_(schedule=sched, use_kernel=True))


def test_resume_requires_existing_checkpoint(tmp_path):
    with pytest.raises(ExperimentError, match="checkpoint_path"):
        simulate(_exp(), resume=True)
    with pytest.raises(ExperimentError, match="no checkpoint"):
        simulate(_exp(), resume=True,
                 checkpoint_path=str(tmp_path / "missing"))


# ------------------------------------------------------ sink lifecycle
def test_csv_sink_closed_by_simulate(tmp_path):
    path = str(tmp_path / "out.csv")
    sink = CsvSink(path, ["prey", "pred"])
    res = simulate(_exp(windows=5).with_(sinks=(sink,)))
    assert res.completed and sink.closed
    lines = open(path).read().strip().splitlines()
    assert len(lines) == 6  # header + one row per window
    assert lines[0].startswith("t,n,prey_mean")
    with pytest.raises(ValueError, match="closed"):
        sink(res.records[0])


def test_telemetry_counts_one_dispatch_per_window():
    res = simulate(_exp(windows=4))
    assert res.telemetry.dispatches == 4
    assert res.telemetry.wall_time_s > 0
    walls = res.telemetry.block_walls
    assert [(w0, n) for w0, n, _, _ in walls] == [(w, 1) for w in range(4)]
    assert all(d > 0 and c > 0 for _, _, d, c in walls)


def test_kernel_path_is_one_dispatch_per_window():
    """The Pallas chunk loop now runs device-side: a kernel window is
    ONE dispatch (vs one per group for the host loop), there are no
    per-chunk host syncs (the truncation flag rides the per-window
    record pull — no longer its own sync), and the records are BITWISE
    equal to both the fused jnp path and the host-loop baseline —
    parity the counter-based RNG guarantees for any chunk size."""
    kern = simulate(_exp(windows=2, replicas=16, use_kernel=True))
    fused = simulate(_exp(windows=2, replicas=16))
    host = simulate(_exp(windows=2, replicas=16, host_loop=True))
    assert kern.telemetry.dispatches == 2  # one launch per window
    assert (kern.means() == fused.means()).all()
    assert (kern.means() == host.means()).all()
    # the truncation flag joins the combined end-of-window pull: the
    # kernel path's sync profile now EQUALS the fused jnp path's
    # (BENCH_PR3 recorded 2.0 syncs/window vs 1.0 before the fix)
    assert kern.telemetry.host_syncs == fused.telemetry.host_syncs
    # host_loop+use_kernel stays the per-group baseline: one fused
    # launch per (group x window), still no chunk-loop sync storm
    both = simulate(_exp(windows=2, replicas=16, host_loop=True,
                         use_kernel=True))
    assert both.telemetry.dispatches == 2 * 2  # 16 inst / 8 lanes
    assert (both.means() == kern.means()).all()


def test_kernel_budget_knobs_exposed_on_experiment():
    """The FusedWindowTruncated remedy ("raise kernel_max_chunks /
    kernel_chunk_steps") must be applicable through the declarative
    API, and the chunking must never change a trajectory."""
    from repro.kernels.ops import FusedWindowTruncated

    tight = _exp(windows=2, replicas=16, use_kernel=True,
                 kernel_chunk_steps=2, kernel_max_chunks=1)
    with pytest.raises(FusedWindowTruncated, match="kernel_max_chunks"):
        simulate(tight)
    odd = simulate(_exp(windows=2, replicas=16, use_kernel=True,
                        kernel_chunk_steps=7, kernel_max_chunks=512))
    default = simulate(_exp(windows=2, replicas=16, use_kernel=True))
    assert (odd.means() == default.means()).all()
    with pytest.raises(ExperimentError, match="kernel_chunk_steps"):
        simulate(_exp(use_kernel=True, kernel_chunk_steps=0))
    with pytest.raises(ExperimentError, match="kernel_max_chunks"):
        simulate(_exp(use_kernel=True, kernel_max_chunks=-1))
