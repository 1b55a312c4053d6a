"""Pallas kernels vs jnp oracles: shape/dtype sweeps + bitwise checks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cwc.compile import compile_model
from repro.core.cwc.models import (
    MODELS,
    ecoli_gene_regulation,
    lotka_volterra,
    membrane_transport,
)
from repro.core.gillespie import advance_to, init_lanes, system_tensors
from repro.kernels.ops import FusedWindowOut, _chunk_while, fused_window
from repro.kernels.propensity import propensity_call, reactant_onehots
from repro.kernels.ref import propensity_ref, ssa_window_ref
from repro.kernels.ssa_step import (
    LANES_MAX,
    kernel_layout,
    ssa_window_call,
    ssa_window_columns,
    ssa_window_lanes,
)

SYSTEMS = {
    "lv2": lotka_volterra(2),
    "lv8": lotka_volterra(8),
    "ecoli": ecoli_gene_regulation(),
    "transport": membrane_transport(),
}


@pytest.mark.parametrize("name", list(SYSTEMS))
@pytest.mark.parametrize("batch", [1, 17, 256, 300])
def test_propensity_kernel_shape_sweep(name, batch, rng):
    sys, _ = compile_model(SYSTEMS[name])
    e = jnp.asarray(reactant_onehots(sys))
    coef = jnp.asarray(sys.reactant_coef.T, jnp.float32)
    x = jnp.asarray(rng.integers(0, 50, (batch, sys.n_species))
                    .astype(np.float32))
    a_k = propensity_call(x, e, coef, jnp.asarray(sys.rates), interpret=True)
    a_r = propensity_ref(x, jnp.asarray(sys.reactant_idx),
                         jnp.asarray(sys.reactant_coef),
                         jnp.asarray(sys.rates))
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", list(SYSTEMS))
def test_propensity_kernel_per_lane_rates(name, rng):
    sys, _ = compile_model(SYSTEMS[name])
    b = 9
    e = jnp.asarray(reactant_onehots(sys))
    coef = jnp.asarray(sys.reactant_coef.T, jnp.float32)
    rates = jnp.asarray(
        rng.uniform(0.1, 3.0, (b, sys.n_reactions)).astype(np.float32))
    x = jnp.asarray(rng.integers(0, 30, (b, sys.n_species))
                    .astype(np.float32))
    a_k = propensity_call(x, e, coef, rates, interpret=True)
    a_r = propensity_ref(x, jnp.asarray(sys.reactant_idx),
                         jnp.asarray(sys.reactant_coef), rates)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), rtol=1e-6)


@pytest.mark.parametrize("name", ["lv2", "ecoli", "transport"])
@pytest.mark.parametrize("batch,n_steps", [(8, 16), (33, 64), (128, 32)])
def test_fused_window_bitwise_vs_ref(name, batch, n_steps, rng):
    """The kernel's in-VREG counter-based draws match the jnp oracle
    consuming the same (key, ctr) stream — bitwise."""
    sys, _ = compile_model(SYSTEMS[name])
    pool = init_lanes(sys, batch, seed=batch + n_steps)
    e = jnp.asarray(reactant_onehots(sys))
    coef = jnp.asarray(sys.reactant_coef.T, jnp.float32)
    delta = jnp.asarray(sys.delta, jnp.float32)
    rates = jnp.asarray(sys.rates)
    horizon = 0.1
    out_k = ssa_window_call(pool.x, pool.t, pool.dead.astype(jnp.int32),
                            pool.key, pool.ctr, pool.ctr_hi, e, coef,
                            delta, rates, horizon, n_steps=n_steps,
                            interpret=True)
    out_r = ssa_window_ref(pool.x, pool.t, pool.dead.astype(jnp.int32),
                           pool.key, pool.ctr, pool.ctr_hi,
                           jnp.asarray(sys.reactant_idx),
                           jnp.asarray(sys.reactant_coef), delta, rates,
                           horizon, n_steps=n_steps)
    assert (out_k[0] == out_r[0]).all(), "state mismatch"
    np.testing.assert_allclose(np.asarray(out_k[1]), np.asarray(out_r[1]),
                               rtol=1e-5, atol=1e-6)
    assert (out_k[3] == out_r[3]).all(), "step counts mismatch"
    assert (out_k[4] == out_r[4]).all(), "draw counters mismatch"
    assert (out_k[5] == out_r[5]).all(), "high counter words mismatch"


@pytest.mark.parametrize("chunk_steps,max_chunks",
                         [(1, 2048), (7, 512), (256, 64)])
def test_fused_window_bitwise_vs_unfused_any_chunk(chunk_steps, max_chunks):
    """Counter-based RNG makes kernel<->unfused parity bitwise for ANY
    chunk size, INCLUDING across a window boundary (previously only the
    first window was bitwise; across windows it was distributional)."""
    sys, _ = compile_model(lotka_volterra(2))
    tens = system_tensors(sys)
    p1 = init_lanes(sys, 64, seed=9)
    p2 = init_lanes(sys, 64, seed=9)
    adv = jax.jit(lambda p, h: advance_to(p, tens, h))
    a = adv(adv(p1, 0.1), 0.2)  # two windows, unfused
    out = fused_window(p2, tens, 0.1, chunk_steps=chunk_steps,
                       max_chunks=max_chunks)
    out = fused_window(out.state, tens, 0.2, chunk_steps=chunk_steps,
                       max_chunks=max_chunks)
    b = out.state
    assert not bool(out.truncated)
    assert (a.x == b.x).all()
    assert (a.t == b.t).all()
    assert (a.ctr == b.ctr).all()
    assert (a.steps == b.steps).all()
    assert (a.dead == b.dead).all()


#: the dense exact kernel's two bodies, called directly
BODIES = {"lanes": ssa_window_lanes, "columns": ssa_window_columns}
FIELDS = ("x", "t", "dead", "steps", "ctr", "ctr_hi")


def kernel_tables(sys):
    """(e, coef, delta) in the kernels' form."""
    return (jnp.asarray(reactant_onehots(sys)),
            jnp.asarray(sys.reactant_coef.T, jnp.float32),
            jnp.asarray(sys.delta, jnp.float32))


@pytest.mark.parametrize("per_lane", [False, True],
                         ids=["shared", "per_lane"])
@pytest.mark.parametrize("batch", [8, 33, 1024, 2065])
@pytest.mark.parametrize("name", ["lv2", "lv8", "ecoli", "transport"])
def test_fused_window_bitwise_vs_ref_lane_body(name, batch, per_lane, rng):
    """The lane body, called directly, gives the oracle's bits and the
    column body's, with shared rates and per-lane (sweep) rates. Lane
    counts off a whole (8, 128) tile are padded inside the call as
    lanes at the horizon and cut off again."""
    sys, _ = compile_model(SYSTEMS[name])
    pool = init_lanes(sys, batch, seed=batch)
    e, coef, delta = kernel_tables(sys)
    rates = jnp.asarray(sys.rates)
    if per_lane:
        rates = rates * jnp.asarray(
            rng.uniform(0.5, 2.0, (batch, sys.n_reactions)), jnp.float32)
    lanes = (pool.x, pool.t, pool.dead.astype(jnp.int32), pool.key,
             pool.ctr, pool.ctr_hi)
    horizon, n_steps = 0.1, 16
    out_l = ssa_window_lanes(*lanes, e, coef, delta, rates, horizon,
                             n_steps=n_steps, interpret=True)
    out_c = ssa_window_columns(*lanes, e, coef, delta, rates, horizon,
                               n_steps=n_steps, interpret=True)
    out_r = ssa_window_ref(*lanes, jnp.asarray(sys.reactant_idx),
                           jnp.asarray(sys.reactant_coef), delta, rates,
                           horizon, n_steps=n_steps)
    for field, a, c, r in zip(FIELDS, out_l, out_c, out_r):
        assert a.shape == r.shape, field
        assert (a == r).all(), f"{field}: lane body vs oracle"
        assert (a == c).all(), f"{field}: lane body vs column body"
    assert int(out_l[3].sum()) > 0


@pytest.mark.parametrize("chunk_steps,max_chunks",
                         [(1, 2048), (7, 512), (256, 64)])
@pytest.mark.parametrize("body", list(BODIES))
@pytest.mark.parametrize("name", ["lv2", "transport"])
def test_fused_window_bitwise_vs_unfused_any_chunk_body(
        name, body, chunk_steps, max_chunks):
    """Each body, called directly in the engine's chunk loop, matches
    the unfused path bitwise over two windows for any chunk size."""
    sys, _ = compile_model(SYSTEMS[name])
    tens = system_tensors(sys)
    e, coef, delta = kernel_tables(sys)
    run = BODIES[body]
    adv = jax.jit(lambda p, h: advance_to(p, tens, h))
    a = adv(adv(init_lanes(sys, 64, seed=9), 0.1), 0.2)

    @jax.jit
    def window(pool, horizon):
        def chunk(x, t, dead, ctr, ctr_hi, h):
            x, t, dead, steps, ctr, ctr_hi = run(
                x, t, dead, pool.key, ctr, ctr_hi, e, coef, delta,
                tens[3], h, n_steps=chunk_steps, interpret=True)
            return x, t, dead, steps, jnp.zeros_like(steps), ctr, ctr_hi
        return _chunk_while(pool, horizon, chunk, max_chunks)

    out = window(init_lanes(sys, 64, seed=9), 0.1)
    out = window(out.state, 0.2)
    b = out.state
    assert not bool(out.truncated)
    for field in ("x", "t", "ctr", "ctr_hi", "steps", "dead"):
        assert (getattr(a, field) == getattr(b, field)).all(), field
    assert int(b.steps.sum()) > 0


@pytest.mark.parametrize("name,layout", [
    ("lv2", "lanes"), ("lv4", "lanes"), ("lv8", "lanes"),
    ("ecoli", "lanes"), ("transport", "lanes"),
    ("ring8", "columns"), ("ring80", "columns"),
    ("lattice8x8", "columns")])
def test_kernel_layout_rule(name, layout):
    """The small models run the lane body; networks past LANES_MAX
    species or reactions keep the column body."""
    sys, _ = compile_model(MODELS[name]())
    assert kernel_layout(sys.n_species, sys.n_reactions) == layout


def test_kernel_layout_bound():
    assert kernel_layout(LANES_MAX, LANES_MAX) == "lanes"
    assert kernel_layout(LANES_MAX + 1, 2) == "columns"
    assert kernel_layout(2, LANES_MAX + 1) == "columns"


def test_fused_window_single_launch_telemetry():
    """FusedWindowOut carries single-launch telemetry only: a device
    chunk count and a truncation flag — the host-driven per-chunk
    dispatch/sync counters are gone along with the loop itself."""
    sys, _ = compile_model(lotka_volterra(2))
    tens = system_tensors(sys)
    out = fused_window(init_lanes(sys, 64, seed=9), tens, 0.1,
                       chunk_steps=128)
    assert set(FusedWindowOut._fields) == {"state", "n_chunks",
                                           "truncated"}
    assert int(out.n_chunks) >= 1
    assert not bool(out.truncated)


def test_fused_window_truncation_is_flagged_not_silent():
    """A window that exhausts max_chunks with live lanes below the
    horizon must say so — previously it returned a partial window as if
    complete."""
    sys, _ = compile_model(lotka_volterra(2))
    tens = system_tensors(sys)
    out = fused_window(init_lanes(sys, 16, seed=3), tens, 5.0,
                       chunk_steps=2, max_chunks=1)
    assert bool(out.truncated)
    assert int(out.n_chunks) == 1
    # the partial state is still below the horizon on some live lane
    live = (np.asarray(out.state.t) < 5.0) & ~np.asarray(out.state.dead)
    assert live.any()
    # a generous budget on the same start completes and clears the flag
    out2 = fused_window(init_lanes(sys, 16, seed=3), tens, 5.0,
                        chunk_steps=256, max_chunks=64)
    assert not bool(out2.truncated)


def test_engine_raises_on_truncated_kernel_window():
    import warnings

    from repro.core.engine import SimConfig, SimulationEngine
    from repro.kernels.ops import FusedWindowTruncated

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        eng = SimulationEngine(
            lotka_volterra(2),
            SimConfig(n_instances=16, t_end=2.0, n_windows=2, n_lanes=16,
                      schema="iii", seed=5, use_kernel=True,
                      kernel_chunk_steps=2, kernel_max_chunks=1))
    with pytest.raises(FusedWindowTruncated, match="kernel_max_chunks"):
        eng.run()
