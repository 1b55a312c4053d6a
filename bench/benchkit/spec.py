"""The benchmark's data: BENCHMARK.json and the files it names.

Everything that belongs to one configuration, traffic mix, cell or
metric lives in a file of its own, found by its name:

    configs/<config>.json   deployment, reference sizes (+ <config>.py,
                            its plain reference network)
    traffic/<traffic>.json  the campaign and the execution path
    cells/<workload>.json   the cell's own settings and limits
    metrics/<metric>.py     a reader: `read(run) -> float | None`,
                            None where it finds nothing to read
    work/<kernel>.py        operations and bytes a kernel needs

A cell reports every end-to-end metric whose reader returns a number,
and the per-layer metrics whose `workloads` list it (every per-layer
metric lists its cells). Adding one of them never needs an edit to this
module.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC_FILE = ROOT / "BENCHMARK.json"


def load_spec(path: Path = SPEC_FILE) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_json(kind: str, name: str, base: Path = BENCH) -> dict:
    with open(base / kind / f"{name}.json", encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import `<kind>/<name>.py` under a private module name."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Cell:
    """One workload, resolved: its entry in BENCHMARK.json and the
    files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    cell: dict
    end_to_end: tuple  # every end-to-end metric (--trace 0)
    per_layer: tuple  # the per-layer metrics that list this cell

    @property
    def settings(self) -> dict:
        """The execution settings: the traffic's path, then the cell's
        own (which win)."""
        return {**self.traffic.get("settings", {}),
                **self.cell.get("settings", {})}

    @property
    def limits(self) -> dict:
        return self.cell["limits"]

    def network(self):
        """The configuration's plain reference network: the module
        `configs/<reference.network>.py`, given the model's arguments."""
        mod = load_module("configs", self.config["reference"]["network"])
        return mod.network(self.config["deployment"]["model"]["args"])


def resolve(workload: str, spec: dict | None = None,
            base: Path = BENCH) -> Cell:
    """The workload's cell. `spec` and `base` (where the configuration,
    traffic and cell files lie) default to the benchmark's own."""
    spec = load_spec() if spec is None else spec
    entries = {w["name"]: w for w in spec["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(entries)}")
    w = entries[workload]
    unlisted = [m["name"] for m in spec["per_layer"] if "workloads" not in m]
    if unlisted:
        raise KeyError(f"per-layer metrics {unlisted} list no workloads")
    per_layer = tuple(m for m in spec["per_layer"]
                      if workload in m["workloads"])
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json("configs", w["config"], base),
                traffic=load_json("traffic", w["traffic"], base),
                cell=load_json("cells", workload, base),
                end_to_end=tuple(spec["end_to_end"]),
                per_layer=per_layer)
