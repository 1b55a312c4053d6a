#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set.

    python3 bench/readings.py --workload lotka77.kernel --seeds 12 \\
        --control-seeds 3 --seed 1000 --json chiprun_out/readings.json

On the chip, at the cell's own size: `--seeds` ensembles through the
timed path (the lower readings: the largest each number reads on sound
runs), and the control on `--control-seeds` seeds (the upper readings:
the smallest each number reads when the plain reference, computed in
bfloat16, is put in the program's place), and on the same seeds each
planted fault of `benchkit.control.FAULTS` in the float32 reference put
in the program's place. Prints one line per number with the readings
and the cell's present limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from benchkit import device  # noqa: E402


def readings(cell, base_seed: int, seeds: int, control_seeds: int) -> dict:
    """{"program": [numbers per seed], "control": [...],
    "<fault>": [...] for each planted fault}"""
    from benchkit import campaign, check, control, harness

    net = cell.network()
    order = harness.program_species(
        campaign.build_model(cell.config["deployment"]["model"]))
    campaign.warm_up(cell, base_seed, [])
    prog, ctrl, names = [], [], None
    faults = {f: [] for f in control.FAULTS}
    for i in range(seeds):
        t0 = time.perf_counter()
        ens = campaign.run_ensemble(
            cell, campaign.ensemble_seed(base_seed, i))
        t1 = time.perf_counter()
        names = ens.obs_names
        nums = harness.compare(ens, cell, net, order, (base_seed, i, 1))
        nums.update(ensemble_s=t1 - t0,
                    reference_s=time.perf_counter() - t1)
        prog.append(nums)
        print(f"program seed {i}: {nums}", file=sys.stderr, flush=True)
    if names is None:
        names = campaign.run_ensemble(
            cell, campaign.ensemble_seed(base_seed, -2)).obs_names
    for c in range(control_seeds):
        t0 = time.perf_counter()
        smp = harness.reference_run(cell, net, (base_seed, c, 2),
                                    dtype="bfloat16")
        ens = control.as_delivered(smp, net, order, names, "bfloat16")
        nums = harness.compare(ens, cell, net, order, (base_seed, c, 3))
        nums.update(control_s=time.perf_counter() - t0,
                    control_capped=int(smp.capped.sum()))
        ctrl.append(nums)
        print(f"control seed {c}: {nums}", file=sys.stderr, flush=True)
        smp = harness.reference_run(cell, net, (base_seed, c, 4))
        for f in control.FAULTS:
            ens = control.as_delivered(smp, net, order, names, "float32",
                                       fault=f)
            nums = harness.compare(ens, cell, net, order,
                                   (base_seed, c, 3))
            faults[f].append(nums)
            print(f"fault {f} seed {c}: {nums}", file=sys.stderr,
                  flush=True)
    return {"program": prog, "control": ctrl, **faults}


def summary(out: dict, limits: dict) -> dict:
    from benchkit import check

    from benchkit import control

    rows = {}
    for name in check.NAMES:
        row = {"lower": max((r[name] for r in out["program"]),
                            default=None)}
        for kind in ("control",) + control.FAULTS:
            row[kind] = min((r[name] for r in out[kind]), default=None)
        row["limit"] = limits[name]
        rows[name] = row
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    from benchkit import spec

    cell = spec.resolve(args.workload)
    device.enable_cache()
    try:
        device.chips(cell.chips)
    except device.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    out = readings(cell, args.seed, args.seeds, args.control_seeds)
    out["summary"] = summary(out, cell.limits)
    for name, row in out["summary"].items():
        print(f"{args.workload} {name}: " + ", ".join(
            f"{k} {v}" for k, v in row.items()))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
