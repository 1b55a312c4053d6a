"""A run of the harness with its look for a chip skipped and the timed
path broken underneath must come out not correct, for each fault a
one-chip cell can have: a step that returns its state unchanged, half
of the lanes left out of the statistics, an answer altered where it is
produced. (The exchange between chips does not exist on one chip.)"""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

from benchkit import device, harness, spec  # noqa: E402


def _unchanged(monkeypatch):
    import jax.numpy as jnp

    from repro.core.engine import SimulationEngine

    def advance(self):
        return lambda lanes, rates, h: lanes._replace(
            t=jnp.maximum(lanes.t, h))

    monkeypatch.setattr(SimulationEngine, "_make_advance_fn", advance)


def _half(monkeypatch):
    from repro.core import reduction

    orig = reduction.blocked_stats
    monkeypatch.setattr(reduction, "blocked_stats",
                        lambda obs, n_blocks=1: orig(
                            obs[: obs.shape[0] // 2], n_blocks))


def _altered(monkeypatch):
    from repro.core import engine

    orig, prev = engine.StatsRecord, {}

    def shifted(**kw):  # each record carries the previous window's
        last = prev.get("rec")
        prev["rec"] = dict(kw)
        if last is not None and kw["window"] > 0:
            kw.update(mean=last["mean"], var=last["var"])
        return orig(**kw)

    monkeypatch.setattr(engine, "StatsRecord", shifted)


def _run():
    cell = spec.resolve("lv2-tiny.xla",
                        spec.load_spec(DATA / "BENCHMARK.json"), DATA)
    return harness.run(cell, 2 ** 31 + 5, 0.1, False, device.start_clock(),
                       require_chip=False)


@pytest.fixture(autouse=True)
def _no_cache(monkeypatch):
    monkeypatch.setattr(device, "enable_cache", lambda: "")


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["attempted"] % 2 == 0  # whole cycles of the pool of 2
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("plant", [_unchanged, _half, _altered],
                         ids=["unchanged", "half", "altered"])
def test_broken_run_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1
