"""Farm worker process — one ensemble shard under a RunSupervisor.

Launched by `runtime.coordinator.FarmCoordinator` as
``python -m repro.runtime.worker <spec.pkl>``. The spec pickle carries
the full Experiment (sinks/recovery stripped), this worker's shard
``(lo, hi, stat_blocks)``, a per-shard Recovery whose `namespace` keys
this shard's checkpoints inside the SHARED ckpt_dir, and the heartbeat
/ result paths.

The worker is the existing single-process machinery, re-based onto a
slice of the global ensemble:

* the engine is built through `api.run.build_engine(shard=...)`: same
  seed and knobs, instance rows [lo, hi), RNG key rows taken from the
  GLOBAL key table (counter-based streams are position-independent, so
  lane i simulates the identical trajectory it would in the
  single-process run), rates/group ids sliced to the range;
* `RunSupervisor` drives it with cadenced namespaced checkpoints —
  engine-level faults (nan guards, …) recover in-process exactly as
  before; process death is the COORDINATOR's job;
* a daemon thread writes newline-terminated JSON heartbeats
  (window frontier, checkpoint frontier, straggler rate) every
  ``heartbeat_s / 2``; a SIGSTOP freezes it, which is precisely how
  the coordinator detects a stalled worker;
* on completion the worker writes an atomic, checksummed result bundle
  (`ckpt.store.save_atomic`): per-window Welford PARTIAL stacks for
  the bitwise record merge, grouped/sketch/trajectory slices, final
  pool state, and a JSON meta blob (supervisor report, telemetry,
  steering report). A worker relaunched AFTER finishing restores its
  final checkpoint, falls straight through the drive loop, and
  rewrites the same bundle — so a crash between "done" and "bundle
  durable" is recoverable too.
"""
from __future__ import annotations

import json
import os
import pickle
import sys
import threading
import time

import numpy as np

from repro.ckpt import store as ckpt_store
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.supervisor import Recovery, RunSupervisor


def _write_json_atomic(path: str, payload: dict) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(json.dumps(payload) + "\n")
    os.replace(tmp, path)


class WorkerSupervisor(RunSupervisor):
    """RunSupervisor over one shard: builds the engine through the
    shard seam (global RNG key rows, sliced rates/groups, partials
    export on) and feeds the heartbeat writer from the drive loop."""

    def __init__(self, experiment, recovery: Recovery, shard: tuple,
                 shard_index: int, heartbeat_path: str):
        super().__init__(experiment, recovery)
        self._shard = tuple(shard)
        self._shard_index = int(shard_index)
        self._hb_path = heartbeat_path
        self._hb_lock = threading.Lock()
        # phase "init" covers engine build + first restore + jit
        # compile, where XLA holds the GIL long enough to starve the
        # heartbeat thread — the coordinator applies the launch grace
        # instead of the run-phase staleness timeout until "run"
        self._hb_state = {"shard": self._shard_index, "pid": os.getpid(),
                          "window": 0, "ckpt_window": -1,
                          "straggler_rate": 0.0, "phase": "init"}
        self._hb_stop = threading.Event()

    # ------------------------------------------------------- heartbeat
    def write_heartbeat(self) -> None:
        with self._hb_lock:
            payload = dict(self._hb_state, time=time.time())
        _write_json_atomic(self._hb_path, payload)

    def start_heartbeat(self) -> None:
        self.write_heartbeat()  # announce liveness before the run

        def beat():
            while not self._hb_stop.wait(self.recovery.heartbeat_s / 2):
                self.write_heartbeat()

        threading.Thread(target=beat, daemon=True).start()

    def stop_heartbeat(self) -> None:
        self._hb_stop.set()

    def _progress(self, engine) -> None:
        with self._hb_lock:
            self._hb_state.update(
                window=engine._window,
                ckpt_window=self._ckpt_frontier,
                straggler_rate=engine.watchdog.straggler_rate(),
                phase="run")

    # ----------------------------------------------------------- build
    def _build(self):
        from repro.api.run import build_engine  # lazy: api imports us

        exp = self.experiment.with_(sinks=(), recovery=None,
                                    partitioning=None)
        engine = build_engine(exp, shard=self._shard)
        engine.enable_block_partials()
        return engine


def _result_bundle(experiment, result, sup: WorkerSupervisor) -> dict:
    eng = result._engine
    W = eng._window
    arrays = {
        "window": np.int64(W),
        "grid": np.asarray(eng.grid, np.float64),
        "final_x": np.asarray(eng._pool.x),
    }
    if eng._block_partials:
        for name in ("n", "mean", "m2"):
            arrays[f"bp_{name}"] = np.stack(
                [getattr(b, name) for b in eng._block_partials])
    if eng._grouped_partials:
        for name in ("n", "mean", "m2"):
            arrays[f"gp_{name}"] = np.stack(
                [getattr(b, name) for b in eng._grouped_partials])
    traj = eng.trajectories()
    if traj is not None:
        arrays["samples"] = traj
    grouped = eng.grouped_stats()
    if grouped:
        for name in ("n", "mean", "var", "ci90"):
            arrays[f"grouped_{name}"] = np.stack(
                [getattr(g, name) for g in grouped])
    sketches = eng.sketches()
    if sketches:
        arrays["sketch_hist"] = np.stack([s.hist for s in sketches])
        if sketches[0].rare is not None:
            arrays["sketch_rare"] = np.stack([s.rare for s in sketches])
    if eng._sketch is not None:  # bin params for downstream quantiles
        arrays["sketch_lo"] = np.asarray(eng._sketch.lo)
        arrays["sketch_width"] = np.asarray(eng._sketch.width)
    tele = result.telemetry
    meta = {
        "shard": sup._shard_index,
        "lo": sup._shard[0], "hi": sup._shard[1],
        "obs_names": list(eng.obs_names),
        "report": sup.report(),
        "steering": eng.steering_report(),
        "telemetry": {
            "wall_time_s": tele.wall_time_s,
            "build_s": tele.build_s,
            "peak_buffered_bytes": int(tele.peak_buffered_bytes),
            "dispatches": int(tele.dispatches),
            "host_syncs": int(tele.host_syncs),
            "steps_per_window": [int(v) for v in tele.steps_per_window],
            "leaps_per_window": [int(v) for v in tele.leaps_per_window],
            "chunks_per_window": [int(v) for v in tele.chunks_per_window],
            "kernel_layout": tele.kernel_layout,
            "straggler_windows": [list(v) for v in
                                  tele.straggler_windows],
            "watchdog_observed": int(eng.watchdog.observed),
            "block_walls": [list(v) for v in tele.block_walls],
            "pipeline_depth": int(tele.pipeline_depth),
            "pipeline_depth_effective": int(
                tele.pipeline_depth_effective),
            "peak_inflight_blocks": int(tele.peak_inflight_blocks),
            "snapshot_saves": int(tele.snapshot_saves),
            "ckpt_flushes": int(tele.ckpt_flushes),
            "restarts": int(tele.restarts),
            "stall_redispatches": int(tele.stall_redispatches),
        },
    }
    arrays["meta"] = np.array(json.dumps(meta))
    return arrays


def run_worker(spec_path: str) -> int:
    with open(spec_path, "rb") as f:
        spec = pickle.load(f)
    sup = WorkerSupervisor(
        spec["experiment"], spec["recovery"], spec["shard"],
        spec["shard_index"], spec["heartbeat_path"])
    # start the device before announcing liveness: a worker that hangs
    # bringing up its chip then writes no heartbeat, and the
    # coordinator's launch grace catches it
    import jax

    jax.devices()
    sup.start_heartbeat()
    try:
        result = sup.run()
        # final heartbeat with the completed frontier, then the bundle
        sup._hb_state.update(window=result.windows_run, phase="done")
        sup.write_heartbeat()
        ckpt_store.save_atomic(
            spec["result_path"],
            _result_bundle(spec["experiment"], result, sup))
    finally:
        sup.stop_heartbeat()
    return 0


def main(argv) -> int:
    if len(argv) != 1:
        print("usage: python -m repro.runtime.worker <spec.pkl>",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    return run_worker(argv[0])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
