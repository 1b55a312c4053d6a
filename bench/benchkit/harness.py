"""One run of one cell: device check, warm-up, the measured window,
the check against the plain reference, the metrics, the result line."""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

from benchkit import campaign, check, device, spec
from benchkit import trace as tracemod


@dataclass
class RunRecord:
    """What a metric reader sees (`metrics/<name>.py: read(run)`)."""

    cell: spec.Cell
    window: campaign.Window
    trace: tracemod.Trace | None
    peaks: dict | None
    network: object

    @property
    def ensembles(self) -> list:
        return self.window.ensembles

    def work(self, kernel: str):
        """The operation and byte counts of `kernel` (work/<kernel>.py)."""
        return spec.load_module("work", kernel)


def program_species(model) -> list:
    """The program's species labels, in the column order of its lane
    states, so that the reference's species can be matched by name."""
    from repro.core.cwc.compile import compile_model
    from repro.core.reactions import ReactionSystem

    if isinstance(model, ReactionSystem):
        return list(model.species_names)
    return list(compile_model(model)[1]["species"])


def checked_ensemble(seed: int, n: int) -> int:
    """Which of the window's `n` ensembles meets the reference."""
    return int(np.random.default_rng([seed % (1 << 64), 7]).integers(n))


def grid(cell) -> np.ndarray:
    """The deployment's window horizons, from its configuration."""
    dep = cell.config["deployment"]
    return np.linspace(0.0, float(dep["t_end"]),
                       int(dep["n_windows"]) + 1)[1:]


def reference_run(cell, net, seed_words, dtype="float32"):
    """The plain reference's ensemble (or the control's, in a lower
    `dtype`, at the cell's own size and under its own step cap)."""
    from benchkit import reference

    ref = cell.config["reference"]
    control = dtype != "float32"
    n = int(cell.config["deployment"]["replicas"] if control
            else ref["replicas"])
    cap = int(ref["control_step_cap"] if control else ref["step_cap"])
    return reference.simulate(net, n, grid(cell), seed_words,
                              dtype=dtype, cap=cap)


def compare(ens, cell, net, order, seed_words) -> dict:
    """Every number of one delivered ensemble: its exact numbers, and
    its z-scores against a reference ensemble of stream `seed_words`."""
    cols = check.obs_columns(net, order, ens.obs_names)
    nums = check.exact_numbers(ens, cols)
    ref = reference_run(cell, net, seed_words)
    z = check.reference_numbers(ens, ref, net, order, cols)
    if bool(ref.capped.any()):  # the reference itself fell short
        z = {k: float("nan") for k in z}
    nums.update(z)
    return nums


def correctness(cell, win, seed: int, net) -> tuple:
    """(numbers, failed ensembles): every ensemble's exact numbers, and
    the reference numbers of one drawn from the seed."""
    limits = cell.limits
    order = program_species(
        campaign.build_model(cell.config["deployment"]["model"]))
    ens = win.ensembles
    cols = check.obs_columns(net, order, ens[0].obs_names)
    per, bad = [], set()
    for i, e in enumerate(ens):
        nums = check.exact_numbers(e, cols)
        per.append(nums)
        if not e.completed or any(not nums[n] <= limits[n] for n in nums):
            bad.add(i)
    k = checked_ensemble(seed, len(ens))
    nums = compare(ens[k], cell, net, order, (seed % (1 << 64), k, 1))
    if any(not v <= limits[n] for n, v in nums.items()):
        bad.add(k)
    per.append(nums)
    return check.worst(per), len(bad)


class MetricMissing(RuntimeError):
    """A per-layer metric that BENCHMARK.json lists for the cell read
    nothing in a traced run."""


def check_listed(entries, metrics: dict, rec: RunRecord) -> None:
    """Raise MetricMissing where a listed per-layer metric read nothing.
    A kernel metric (its reader has a `PATTERN`) may stay silent only
    where no kernel ran at all, the kernel having been taken off the
    path; a kernel that ran under another name fails the run, so that
    a rename is mirrored in the metric's file."""
    kernels = tracemod.custom_calls(rec.trace)
    for m in entries:
        if m["name"] in metrics:
            continue
        mod = spec.load_module("metrics", m["name"])
        if getattr(mod, "PATTERN", None) is not None and not kernels:
            continue
        raise MetricMissing(
            f"per-layer metric {m['name']!r} is listed for "
            f"{rec.cell.name} but read nothing in the trace"
            + (f"; kernels that ran: {sorted(kernels)}" if kernels
               else ""))


def read_metrics(entries, rec: RunRecord) -> dict:
    out = {}
    for m in entries:
        v = spec.load_module("metrics", m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, require_chip: bool = True) -> dict:
    """Run the cell once; return the result object. Raises
    device.NoChip where the chip is missing and `require_chip`, and
    MetricMissing where a traced run reads nothing for a listed
    per-layer metric."""
    device.enable_cache()
    import jax

    if require_chip:
        devs, dev = device.chips(cell.chips)
        peaks = device.load_peaks(dev["kind"])
    else:
        devs = jax.devices()[:cell.chips]
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        peaks = None
    clock = device.CompileClock()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        win = campaign.run_window(cell, seed, seconds, clock, t_start,
                                  tdir)
        dev["memory_peak_bytes"] = device.memory_peak_bytes(devs)
        tr = tracemod.read(tdir) if traced else None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    for name in win.dropped:
        print(f"setting {name!r} dropped: Experiment has no such field",
              file=sys.stderr)
    net = cell.network()
    t_ref = time.perf_counter()
    numbers, failed = correctness(cell, win, seed, net)
    t_ref = time.perf_counter() - t_ref
    correct = check.judge(numbers, cell.limits)
    rec = RunRecord(cell=cell, window=win, trace=tr, peaks=peaks,
                    network=net)
    result = {"correct": bool(correct), "attempted": len(win.ensembles),
              "failed": failed,
              "metrics": read_metrics(
                  cell.per_layer if traced else cell.end_to_end, rec),
              "device": dev}
    if tr is not None:
        dev["busy_s"] = tracemod.busy_s(tr)
        dev["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tracemod.top_ops(tr),
                               "idle_gaps": tracemod.idle_gaps(tr)}
    print(f"window: {len(win.ensembles)} ensembles in {win.window_s} s "
          f"({[e.wall_s for e in win.ensembles]}), setup {win.setup_s} s, "
          f"compile in window {win.compile_s} s, cache misses in window "
          f"{win.cache_misses}; check against the reference {t_ref} s",
          file=sys.stderr)
    if traced:
        check_listed(cell.per_layer, result["metrics"], rec)
    result["checks"] = check.checks_line(numbers, cell.limits)
    return result


def print_result(result: dict) -> None:
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
