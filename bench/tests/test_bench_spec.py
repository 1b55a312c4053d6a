"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix, metric and work count is found by its name, and they agree
with one another."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchkit import check, spec  # noqa: E402

SPEC = spec.load_spec()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]] + WORKLOADS + \
        [m["name"] for m in METRICS]
    for n in names:
        assert NAME.fullmatch(n), n
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({g["name"] for g in group}) == len(group)
    for m in METRICS:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in (
            "lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_has_setup_and_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_resolve(workload):
    cell = spec.resolve(workload)
    assert cell.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == workload)
    assert set(cell.limits) == set(check.NAMES)
    assert cell.limits["n_short"] == 0 and cell.limits["bad_states"] == 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    # every per-layer metric of the cell moves one of its end-to-end ones
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_file_and_reference(config):
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    assert entry["file"] == f"bench/configs/{config}.json"
    data = spec.load_json("configs", config)
    assert data["name"] == config and data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"]
    # every configuration keeps a cell
    assert any(w["config"] == config for w in SPEC["workloads"])
    # the plain reference names the program's own species and
    # observables, so that their columns match by name
    from repro.core.cwc.compile import compile_model
    from benchkit import campaign

    model = campaign.build_model(data["deployment"]["model"])
    _, meta = compile_model(model)
    cell = spec.resolve(next(w["name"] for w in SPEC["workloads"]
                             if w["config"] == config))
    net = cell.network()
    assert sorted(net.species) == sorted(meta["species"])
    assert set(net.observables) == set(meta["observables"])
    ix = {s: i for i, s in enumerate(meta["species"])}
    for name, species in net.observables.items():
        assert sorted(ix[s] for s in species) == sorted(
            meta["observables"][name])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_and_cells(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert callable(spec.load_module("metrics", metric).read)
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    if m in SPEC["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        for w in m.get("workloads", WORKLOADS):
            cell = spec.resolve(w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
    if metric.endswith("_roofline"):
        assert m["unit"] == "%"
        src = (BENCH / "metrics" / f"{metric}.py").read_text()
        work = re.search(r'"(ssa_[a-z]+)"\)', src).group(1)
        assert (BENCH / "work" / f"{work}.py").exists()


def test_layers_are_named_alike():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    perf = (BENCH.parent / "PERF.md").read_text()
    for layer in layers:
        assert f"`{layer}`" in perf, layer


def test_peaks_table():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 1.97e14 and v5e["hbm_bytes_per_s"] == 8.19e11
    from benchkit import device

    with pytest.raises(device.NoChip):
        device.load_peaks("TPU v9 imaginary")
