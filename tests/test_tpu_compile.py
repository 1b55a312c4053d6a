"""Every Pallas kernel compiles for a TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib, so a chip that is only
described (`topologies.get_topology_desc`) is enough to lower each
kernel through Mosaic at the widths the benchmark networks use —
which catches what interpret mode cannot: primitives Mosaic does not
lower, layouts it refuses, and programs that do not fit the device.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture (never at import
time): only one process may load the TPU library, and under pytest-xdist
only the worker that runs this file does.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cwc.compile import cell_ring_model, compile_model
from repro.core.cwc.models import MODELS, lotka_volterra
from repro.core.gillespie import bind_sparse_step, sparse_system_tensors
from repro.core.reactions import MAX_COEF, sparse_tables
from repro.core.tau_leap import gi_tables, onehot_tensors, reactant_mask

#: lanes per compiled call — a realistic pool on one chip
LANES = 16_384
#: device memory of one v5e chip
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


#: networks past the benchmark set: the 240-cell ring (S=960, R=1680)
#: carries the largest tables the tau kernel takes (TAU_TABLE_BYTES),
#: the 320-cell ring (S=1280, R=2240) more
RINGS = {"ring240": 240, "ring320": 320}
#: the largest Lotka-Volterra chain the dense exact kernel runs in its
#: lane body: S = 15, R = 16 = LANES_MAX
CHAINS = {"lv15": 15}


def _system(name):
    if name in RINGS:
        return compile_model(cell_ring_model(RINGS[name]))[0]
    if name in CHAINS:
        return compile_model(lotka_volterra(CHAINS[name]))[0]
    return compile_model(MODELS[name]())[0]


def _lane_specs(s):
    """Shapes of the per-lane window operands for LANES lanes."""
    b = LANES
    return dict(
        x=((b, s), jnp.float32), t=((b,), jnp.float32),
        dead=((b,), jnp.int32), no_leap=((b,), jnp.int32),
        key=((b, 2), jnp.uint32), ctr=((b,), jnp.uint32),
        ctr_hi=((b,), jnp.uint32))


def _case(kernel, name):
    """(fn, {arg: (shape, dtype)}) for one kernel on one network."""
    from repro.kernels.propensity import propensity_call, reactant_onehots
    from repro.kernels.ssa_step import (
        sparse_window_call,
        ssa_window_call,
        ssa_window_lanes,
        tau_window_call,
    )

    sys = _system(name)
    s, r = sys.n_species, sys.n_reactions
    lanes = _lane_specs(s)
    e_shape = reactant_onehots(sys).shape
    dense = dict(e=(e_shape, jnp.float32),
                 coef=((e_shape[0], r), jnp.float32),
                 delta=((r, s), jnp.float32), rates=((r,), jnp.float32),
                 horizon=((), jnp.float32))
    if kernel == "propensity":
        return (lambda x, e, coef, rates: propensity_call(
            x, e, coef, rates, interpret=False),
            dict(x=lanes["x"], e=dense["e"], coef=dense["coef"],
                 rates=((LANES, r), jnp.float32)))
    if kernel == "ssa_window":
        args = {k: lanes[k] for k in ("x", "t", "dead", "key", "ctr",
                                      "ctr_hi")}
        args.update(dense)
        return (lambda *a: ssa_window_call(*a, n_steps=256,
                                           interpret=False), args)
    if kernel in ("ssa_lanes", "ssa_lanes_sweep"):
        # the lane body, called directly; a sweep's per-lane rates
        args = {k: lanes[k] for k in ("x", "t", "dead", "key", "ctr",
                                      "ctr_hi")}
        args.update(dense)
        if kernel == "ssa_lanes_sweep":
            args["rates"] = ((LANES, r), jnp.float32)
        return (lambda *a: ssa_window_lanes(*a, n_steps=256,
                                            interpret=False), args)
    if kernel == "tau_window":
        # the sparse seam runs the same kernel with its own comb bound
        max_c = max(sys.max_coef, MAX_COEF)
        e, _ = onehot_tensors(jnp.asarray(sys.reactant_idx),
                              jnp.asarray(sys.reactant_coef), s)
        args = {k: lanes[k] for k in ("x", "t", "dead", "no_leap", "key",
                                      "ctr", "ctr_hi")}
        args.update(e=(e.shape, jnp.float32), coef=dense["coef"],
                    delta=dense["delta"], rates=dense["rates"],
                    gi=(gi_tables(sys).shape, jnp.float32),
                    rmask=(reactant_mask(sys).shape, jnp.float32),
                    horizon=dense["horizon"])
        return (lambda *a: tau_window_call(
            *a, n_steps=64, eps=0.03, fallback=10.0, max_c=max_c,
            interpret=False), args)
    assert kernel == "sparse_window"
    sp = sparse_system_tensors(sparse_tables(sys))
    int_tab, flt_tab, _, max_c, d, k, m = bind_sparse_step(
        sp, jnp.asarray(sys.rates))
    args = {k_: lanes[k_] for k_ in ("x", "t", "dead", "key", "ctr",
                                     "ctr_hi")}
    args.update(a=((LANES, r), jnp.float32),
                int_tab=(int_tab.shape, jnp.int32),
                flt_tab=(flt_tab.shape, jnp.float32),
                horizon=((), jnp.float32))

    def fn(x, t, dead, key, ctr, ctr_hi, a, int_tab, flt_tab, horizon):
        return sparse_window_call(x, t, dead, key, ctr, ctr_hi, a, int_tab,
                                  flt_tab, None, horizon, n_steps=256,
                                  max_c=max_c, d=d, k=k, m=m,
                                  interpret=False)
    return fn, args


CASES = [
    ("propensity", "lv2"), ("propensity", "lv8"),
    ("propensity", "lattice8x8"),
    ("ssa_window", "lv2"), ("ssa_window", "lv8"),
    ("ssa_window", "lattice8x8"),
    ("tau_window", "lv2"), ("tau_window", "lv8"),
    ("tau_window", "lattice8x8"),
    # sparse seam on the 80-cell ring (S=320, R=560): the exact kernel,
    # and the tau kernel the sparse tau path runs
    ("sparse_window", "ring80"), ("tau_window", "ring80"),
    # the tau kernel at its table limit
    ("tau_window", "ring240"),
    # the dense exact kernel's lane body, up to the largest network it
    # takes, with a sweep's per-lane rates
    ("ssa_lanes", "lv2"), ("ssa_lanes", "lv8"), ("ssa_lanes", "lv15"),
    ("ssa_lanes_sweep", "lv8"),
]


@pytest.mark.parametrize("kernel,name", CASES,
                         ids=[f"{k}-{n}" for k, n in CASES])
def test_kernel_compiles_for_v5e(one_chip, kernel, name):
    fn, args = _case(kernel, name)
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args.values()]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    assert np.isfinite(used)


def test_lane_body_bound_is_lv15():
    from repro.kernels.ssa_step import LANES_MAX, kernel_layout

    sys = _system("lv15")
    assert max(sys.n_species, sys.n_reactions) == LANES_MAX
    assert kernel_layout(sys.n_species, sys.n_reactions) == "lanes"


def test_tau_table_limit_brackets_the_rings():
    from repro.kernels.ssa_step import TAU_TABLE_BYTES, tau_table_bytes

    def need(name):
        sys = _system(name)
        return tau_table_bytes(sys.n_species, sys.n_reactions)

    assert need("ring80") < need("ring240") <= TAU_TABLE_BYTES
    assert need("ring320") > TAU_TABLE_BYTES


def test_tau_kernel_refuses_tables_past_limit(monkeypatch):
    """On an accelerator, a tau kernel whose tables exceed VMEM is
    refused with a typed error before anything runs; the interpreter
    (CPU) takes any size."""
    from repro.api import (Ensemble, Experiment, ExperimentError, Method,
                           Schedule, simulate)
    from repro.kernels import ssa_step

    sys = _system("ring320")
    with pytest.raises(ValueError, match="VMEM"):
        ssa_step.check_tau_tables_fit(sys.n_species, sys.n_reactions,
                                      backend="tpu")
    ssa_step.check_tau_tables_fit(sys.n_species, sys.n_reactions,
                                  backend="cpu")
    small = _system("ring80")
    ssa_step.check_tau_tables_fit(small.n_species, small.n_reactions,
                                  backend="tpu")
    monkeypatch.setattr(ssa_step, "resolve_interpret",
                        lambda interpret, backend=None: False)
    with pytest.raises(ExperimentError, match="use_kernel=False"):
        simulate(Experiment(
            model=sys, ensemble=Ensemble(replicas=8),
            schedule=Schedule(t_end=0.1, n_windows=1), n_lanes=8,
            method=Method.TAU_LEAP, sparse=True, use_kernel=True))
