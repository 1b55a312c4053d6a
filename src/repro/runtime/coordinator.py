"""FarmCoordinator — a coordinator process that outlives its workers.

The multi-process elastic ensemble farm (DESIGN.md §3i): the
coordinator partitions an Experiment's ensemble (sweep points x
replicas) into `Recovery.workers` contiguous shards, launches each
shard as a separate WORKER PROCESS (`runtime.worker`, itself the
existing RunSupervisor loop with cadenced checkpoints under a
per-worker namespace inside the shared ckpt_dir), and supervises the
fleet through a heartbeat-file protocol:

* each worker writes an atomic JSON heartbeat (window frontier,
  checkpoint frontier, straggler rate) every ``heartbeat_s / 2``;
* a heartbeat stale for ``3 x heartbeat_s`` is a typed `WorkerStall`
  (the worker is SIGKILLed — which also unwedges a SIGSTOPped
  process — and restarted);
* a dead process, or a live exit without a verifying result bundle,
  is a typed `HostLost`;
* every restart waits a bounded exponential backoff
  (``backoff_base_s * 2^(restarts-1)``, capped at ``backoff_max_s``)
  and resumes from the newest VALID checkpoint in the worker's own
  namespace — corrupt files are skipped by the worker's
  RunSupervisor, exactly as in the single-process story;
* a worker that dies more than ``max_worker_restarts`` times is
  RETIRED (elastic host-loss degradation): its shard goes back on the
  queue and the first survivor that finishes its own shard picks it
  up — same namespace, so the reassigned run resumes from the retired
  worker's checkpoints;
* a coordinator-level `FrontierWatchdog` flags workers whose window
  frontier falls behind the fleet median (telemetry — liveness is the
  heartbeat timeout's job).

WHY THE MERGE IS BITWISE (the contract every drill asserts): worker
lanes take their RNG key rows from the GLOBAL key table (counter-based
threefry streams are position-independent), so each lane simulates the
identical trajectory it would in one process; the statistics partition
is pinned (each worker owns whole stat blocks of the global Welford
block partition), and workers export per-window Welford PARTIAL
stacks; the coordinator concatenates those stacks in global block
order and re-runs the same associative `merge_blocks` + `finalize`
fold the single-process engine uses. Grouped per-point stats, sketch
histograms (pure counts), trajectories, and steering decisions merge
by concatenation / integer addition. The final `SimulationResult` is
therefore bitwise identical to `Partitioning(n_shards=1,
stat_blocks=B)` run in a single process — no matter how many workers
died, stalled, or were reassigned on the way.

Fault injection (`Recovery.inject`) is PROCESS-level here: `host_lost`
/ `crash` SIGKILL a worker, `worker_stall` / `stall` SIGSTOP it past
the heartbeat timeout, `ckpt_corrupt` truncates the newest checkpoint
in the target's namespace and then kills it. Each scheduled fault
fires once, on the first worker whose heartbeat frontier crosses the
scheduled window. The coordinator itself has no checkpoint: its only
state is the shard queue, which is a pure function of the Experiment —
a crashed coordinator is rerun from scratch and workers' completed
result bundles / checkpoints make the rerun cheap.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

try:  # models may close over lambdas (observables, init_fn) — plain
    import cloudpickle as _pickle  # pickle rejects those; cloudpickle
except ImportError:  # output still loads with stdlib pickle.load
    import pickle as _pickle

from repro.ckpt import store as ckpt_store
from repro.core import reduction
from repro.core.stream import StatsRecord, StatsStream
from repro.runtime.fault import FailureInjector
from repro.runtime.straggler import FrontierWatchdog
from repro.runtime.supervisor import Recovery
from repro.stats.sketch import WindowSketch

# process-level fault kinds the coordinator can inject (see module
# docstring); engine-internal kinds (device_lost, nan_pool) belong to
# the worker's own Recovery.inject and are rejected here
_INJECTABLE = {
    "host_lost": "kill", "crash": "kill",
    "worker_stall": "stop", "stall": "stop",
    "ckpt_corrupt": "corrupt",
}


def host_tpu_chips() -> int:
    """TPU chips a JAX process started here could open, counted without
    starting a JAX backend — 0 on a host with no TPU on its PCI bus, or
    where JAX_PLATFORMS leaves TPU out (CPU farms, the test suite). The
    bus lists every chip of the host, but a container may be given only
    some of their device nodes (/dev/accel*, or one /dev/vfio/<group>
    per chip on v5e and later), so the count is the smaller. A TPU host
    that shows no device node is refused (ExperimentError), never taken
    for a CPU host: its workers would all contend for the same chips."""
    import glob

    platforms = [p for p in os.environ.get("JAX_PLATFORMS", "").split(",")
                 if p]
    if platforms and "tpu" not in platforms:
        return 0
    from jax._src import hardware_utils

    on_bus = hardware_utils.num_available_tpu_chips_and_device_id()[0]
    nodes = len(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))
    chips = min(on_bus, nodes)
    if not chips and (on_bus or platforms == ["tpu"]):
        from repro.api.spec import ExperimentError

        raise ExperimentError(
            f"this host lists {on_bus} TPU chip(s) on its PCI bus and "
            f"{nodes} TPU device node(s) (/dev/accel*, /dev/vfio/<n>), "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}: no "
            "chip can be given to a farm worker of its own")
    return chips


def chip_env(chip: int, port: int, metrics_port: int) -> dict:
    """libtpu's per-process chip visibility: the worker sees chip
    `chip` of the host as a one-chip slice of its own, with its own
    slice-builder and runtime-metrics ports. Each worker holds a
    different chip, so libtpu's one-process-per-host lock is lifted."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_RUNTIME_METRICS_PORTS": str(metrics_port),
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def _free_port() -> int:
    import socket

    with socket.socket() as sock:
        sock.bind(("localhost", 0))
        return sock.getsockname()[1]


def _backend_started() -> bool:
    """Whether this process already started a JAX backend (on a TPU
    host, that process holds every chip)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


class _Shard:
    """One contiguous slice of the global ensemble and its on-disk
    protocol endpoints (spec / heartbeat / result bundle paths)."""

    def __init__(self, index: int, lo: int, hi: int, blocks: int,
                 ckpt_dir: str):
        self.index = index
        self.lo, self.hi, self.blocks = lo, hi, blocks
        self.namespace = f"shard{index:02d}"
        self.spec_path = os.path.join(
            ckpt_dir, f"{self.namespace}__spec.pkl")
        self.hb_path = os.path.join(
            ckpt_dir, f"hb_{self.namespace}.json")
        # contains no "ckpt_", so the checkpoint store's namespaced
        # listing can never mistake a result bundle for a checkpoint
        self.result_path = os.path.join(
            ckpt_dir, f"{self.namespace}__result.npz")
        self.owner = index  # original slot; differs after reassignment
        self.bundle: Optional[dict] = None


class _Slot:
    """One worker slot ("host"): the unit the restart budget and
    retirement apply to. Slot i initially runs shard i."""

    def __init__(self, index: int):
        self.index = index
        self.proc: Optional[subprocess.Popen] = None
        self.shard: Optional[_Shard] = None
        self.restarts = 0
        self.retired = False
        self.next_start = 0.0
        self.launch_t = 0.0
        self.shards_run: list[int] = []


class FarmCoordinator:
    """Drives one Experiment across `recovery.workers` worker
    processes and merges their results bitwise. `run()` returns the
    same SimulationResult handle simulate() does, with
    `recovery_report()` carrying the farm's event log."""

    def __init__(self, experiment, recovery: Recovery):
        recovery.validate()
        experiment.validate()
        self.experiment = experiment
        self.recovery = recovery
        ens = experiment.ensemble
        k = recovery.workers
        n_inst = ens.n_instances
        blocks = (experiment.partitioning.blocks
                  if experiment.partitioning is not None else k)
        per = n_inst // k
        self.n_windows = experiment.schedule.n_windows
        self.shards = [
            _Shard(i, i * per, (i + 1) * per, blocks // k,
                   recovery.ckpt_dir)
            for i in range(k)]
        self._events: list[dict] = []
        self._faults: dict = {}
        self._total_restarts = 0
        self._reassignments = 0
        self.watchdog = FrontierWatchdog()
        # on a TPU host every worker gets one chip of its own (slot i
        # -> chip i); refuse before anything launches what cannot run
        self.chips = host_tpu_chips()
        if self.chips:
            from repro.api.spec import ExperimentError

            if k > self.chips:
                raise ExperimentError(
                    f"Recovery.workers={k} needs one TPU chip per worker "
                    f"and this host has {self.chips}; use at most "
                    f"{self.chips} workers")
            if _backend_started():
                raise ExperimentError(
                    "this process already started JAX on the TPU and "
                    "holds the chips its farm workers need; launch the "
                    "farm before the parent touches a device (the "
                    "merge runs after every worker has exited)")
        self._injector = None
        if recovery.inject is not None:
            self._injector = FailureInjector(recovery.inject,
                                             n_windows=self.n_windows)
            bad = [kind for kind in self._injector.schedule.values()
                   if kind not in _INJECTABLE]
            if bad:
                raise ValueError(
                    f"fault kind(s) {sorted(set(bad))} cannot be "
                    "injected at the farm coordinator (process) level;"
                    f" coordinator kinds are {sorted(_INJECTABLE)} — "
                    "engine-internal kinds run under a workers=1 "
                    "Recovery")

    # ------------------------------------------------------------- api
    def run(self):
        from repro.api.result import SimulationResult  # lazy: no cycle

        rec = self.recovery
        t0 = time.perf_counter()
        os.makedirs(rec.ckpt_dir, exist_ok=True)
        for sh in self.shards:
            self._write_spec(sh)
        slots = [_Slot(i) for i in range(rec.workers)]
        for slot, sh in zip(slots, self.shards):
            slot.shard = sh
        pending: collections.deque = collections.deque()
        done: set = set()
        poll = min(0.2, rec.heartbeat_s / 4.0)
        try:
            while len(done) < len(self.shards):
                now = time.time()
                for slot in slots:
                    if slot.retired or slot.proc is not None:
                        continue
                    if slot.shard is None:
                        if not pending:
                            continue
                        sh = pending.popleft()
                        slot.shard = sh
                        self._reassignments += 1
                        self._log("shard_reassigned", shard=sh.index,
                                  from_worker=sh.owner,
                                  to_worker=slot.index)
                        sh.owner = slot.index
                    if now >= slot.next_start:
                        self._launch(slot)
                if pending and all(s.retired or s.proc is None
                                   and s.shard is None for s in slots):
                    raise RuntimeError(
                        "farm dead: every worker slot is retired "
                        f"({self._total_restarts} restarts) with "
                        f"{len(pending)} shard(s) unfinished; raise "
                        "Recovery.max_worker_restarts or fix the "
                        "underlying fault")
                time.sleep(poll)
                for slot in slots:
                    if slot.proc is None:
                        continue
                    self._poll_slot(slot, pending, done)
        finally:
            for slot in slots:
                if slot.proc is not None:
                    self._kill(slot.proc)
                    slot.proc = None
        wall = time.perf_counter() - t0
        view = self._merge()
        for sink in self.experiment.sinks:
            view.stream.attach(sink)
            for r in view.stream.records():
                sink(r)
        view.stream.close()
        result = SimulationResult(self.experiment, view)
        result._wall_time = wall
        result._restarts = self._total_restarts
        result._stall_redispatches = sum(
            sh.bundle["_meta"]["report"].get("stall_redispatches", 0)
            for sh in self.shards)
        result._recovery = self._report(slots)
        return result

    # --------------------------------------------------- process layer
    def _write_spec(self, sh: _Shard) -> None:
        worker_rec = dataclasses.replace(
            self.recovery, workers=1, namespace=sh.namespace,
            inject=None)
        spec = {
            "experiment": self.experiment.with_(sinks=(), recovery=None),
            "recovery": worker_rec,
            "shard": (sh.lo, sh.hi, sh.blocks),
            "shard_index": sh.index,
            "heartbeat_path": sh.hb_path,
            "result_path": sh.result_path,
        }
        with open(sh.spec_path, "wb") as f:
            _pickle.dump(spec, f)

    def _launch(self, slot: _Slot) -> None:
        import repro

        sh = slot.shard
        try:
            os.remove(sh.hb_path)  # a stale file must not look alive
        except FileNotFoundError:
            pass
        env = dict(os.environ)
        src_root = str(Path(repro.__file__).resolve().parents[1])
        env["PYTHONPATH"] = src_root + (
            os.pathsep + env["PYTHONPATH"]
            if env.get("PYTHONPATH") else "")
        if self.chips:
            env.update(chip_env(slot.index, _free_port(),
                                _free_port()))
        log_path = os.path.join(self.recovery.ckpt_dir,
                                f"{sh.namespace}.log")
        with open(log_path, "ab") as logf:
            slot.proc = subprocess.Popen(
                [sys.executable, "-m", "repro.runtime.worker",
                 sh.spec_path],
                stdout=logf, stderr=subprocess.STDOUT, env=env)
        slot.launch_t = time.time()
        if sh.index not in slot.shards_run:
            slot.shards_run.append(sh.index)
        self._log("worker_launched", worker=slot.index, shard=sh.index,
                  pid=slot.proc.pid, attempt=slot.restarts)

    def _poll_slot(self, slot: _Slot, pending, done: set) -> None:
        rec = self.recovery
        sh = slot.shard
        hb = self._read_heartbeat(sh)
        if hb is not None:
            if self.watchdog.observe(sh.index, int(hb.get("window", 0))):
                self._log("frontier_straggler", worker=slot.index,
                          shard=sh.index, window=int(hb["window"]))
            self._maybe_inject(slot, int(hb.get("window", 0)))
        rc = slot.proc.poll()
        if rc is not None:
            slot.proc = None
            bundle = self._load_bundle(sh) if rc == 0 else None
            if bundle is not None:
                sh.bundle = bundle
                done.add(sh.index)
                slot.shard = None
                self.watchdog.forget(sh.index)
                self._log("worker_done", worker=slot.index,
                          shard=sh.index)
            else:
                why = (f"exit code {rc}" if rc != 0 else
                       "exit 0 without a verifying result bundle")
                self._fault(slot, pending, "host_lost",
                            f"worker process died ({why})",
                            window=-1 if hb is None
                            else int(hb.get("window", -1)))
            return
        now = time.time()
        grace = max(60.0, 10.0 * rec.heartbeat_s)
        if hb is not None:
            try:
                stale = now - os.path.getmtime(sh.hb_path)
            except OSError:
                return
            # during "init" (engine build + restore + jit compile) XLA
            # can hold the GIL long enough to starve the heartbeat
            # thread — judge init-phase workers by the launch grace,
            # running workers by the 3 x heartbeat_s contract
            limit = (grace if hb.get("phase") == "init"
                     else 3.0 * rec.heartbeat_s)
            if stale > limit and now - slot.launch_t > limit:
                self._kill(slot.proc)
                slot.proc = None
                self._fault(slot, pending, "worker_stall",
                            f"heartbeat stale for {stale:.1f}s "
                            f"(limit {limit:.1f}s, heartbeat_s="
                            f"{rec.heartbeat_s})",
                            window=int(hb.get("window", -1)))
        elif now - slot.launch_t > grace:
            # never wrote a first heartbeat: hung before liveness
            self._kill(slot.proc)
            slot.proc = None
            self._fault(slot, pending, "worker_stall",
                        "no heartbeat after launch grace", window=-1)

    def _fault(self, slot: _Slot, pending, kind: str, msg: str,
               window: int) -> None:
        rec = self.recovery
        sh = slot.shard
        self._faults[kind] = self._faults.get(kind, 0) + 1
        self._log("fault", kind=kind, worker=slot.index,
                  shard=sh.index, window=window, error=msg)
        try:
            os.remove(sh.hb_path)
        except FileNotFoundError:
            pass
        slot.restarts += 1
        self._total_restarts += 1
        if slot.restarts > rec.max_worker_restarts:
            slot.retired = True
            slot.shard = None
            pending.append(sh)
            self._log("worker_retired", worker=slot.index,
                      shard=sh.index, restarts=slot.restarts)
        else:
            backoff = (min(rec.backoff_max_s,
                           rec.backoff_base_s * 2 ** (slot.restarts - 1))
                       if rec.backoff_base_s > 0 else 0.0)
            slot.next_start = time.time() + backoff
            self._log("restart_scheduled", worker=slot.index,
                      shard=sh.index, backoff_s=backoff,
                      attempt=slot.restarts)

    @staticmethod
    def _kill(proc: subprocess.Popen) -> None:
        for sig in (signal.SIGCONT, signal.SIGKILL):
            try:
                proc.send_signal(sig)  # CONT first: a SIGSTOPped
            except (ProcessLookupError, OSError):  # child must still
                pass                               # die on KILL
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass

    def _read_heartbeat(self, sh: _Shard) -> Optional[dict]:
        try:
            with open(sh.hb_path) as f:
                return json.loads(f.read())
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            return None

    def _load_bundle(self, sh: _Shard) -> Optional[dict]:
        try:
            arrays = ckpt_store.verify(
                sh.result_path,
                required=("window", "grid", "final_x", "meta"))
        except (ckpt_store.CheckpointCorrupt, FileNotFoundError):
            return None
        if int(arrays["window"]) != self.n_windows:
            return None
        arrays["_meta"] = json.loads(str(arrays.pop("meta")))
        return arrays

    # -------------------------------------------------- fault injection
    def _maybe_inject(self, slot: _Slot, frontier: int) -> None:
        if self._injector is None or slot.proc is None:
            return
        for w in sorted(self._injector.schedule):
            if w > frontier:
                break
            kind = self._injector.maybe_fail(w)
            if kind is None:
                continue
            self._log("fault_injected", kind=kind, window=w,
                      worker=slot.index, shard=slot.shard.index)
            mode = _INJECTABLE[kind]
            if mode in ("kill", "corrupt"):
                # corrupt mode kills FIRST: truncating while the worker
                # is alive races a concurrent cadence save (which could
                # replace the corrupt file with a fresh checkpoint
                # before the restart reads it)
                self._kill(slot.proc)
                if mode == "corrupt":
                    self._corrupt_newest(slot.shard)
            elif mode == "stop":
                try:
                    slot.proc.send_signal(signal.SIGSTOP)
                except (ProcessLookupError, OSError):
                    pass
            return  # at most one injection per poll

    def _corrupt_newest(self, sh: _Shard) -> None:
        ckpts = ckpt_store.list_checkpoints(self.recovery.ckpt_dir,
                                            sh.namespace)
        if not ckpts:
            return
        _, path = ckpts[-1]
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(1, size // 2))

    # ----------------------------------------------------------- merge
    def _merge(self):
        bundles = [sh.bundle for sh in self.shards]  # global order
        metas = [b["_meta"] for b in bundles]
        grid = np.asarray(bundles[0]["grid"])
        w_total = self.n_windows
        stream = StatsStream()
        if all("bp_n" in b for b in bundles):
            import jax.numpy as jnp

            # concatenate worker partial stacks in global block order
            # and re-run the exact single-process merge_blocks +
            # finalize fold per window — this is the bitwise step
            bp_n = np.concatenate([b["bp_n"] for b in bundles], axis=1)
            bp_mean = np.concatenate(
                [b["bp_mean"] for b in bundles], axis=1)
            bp_m2 = np.concatenate([b["bp_m2"] for b in bundles], axis=1)
            for w in range(w_total):
                st = reduction.finalize(reduction.merge_blocks(
                    reduction.Welford(n=jnp.asarray(bp_n[w]),
                                      mean=jnp.asarray(bp_mean[w]),
                                      m2=jnp.asarray(bp_m2[w]))))
                n = np.asarray(st.n)
                stream.emit(StatsRecord(
                    t=float(grid[w]), window=w,
                    mean=np.asarray(st.mean), var=np.asarray(st.var),
                    ci90=np.asarray(st.ci90), n=float(n.max())))
        grouped: list = []
        if all("gp_n" in b for b in bundles):
            import jax.numpy as jnp

            # the reference grouped fold merges per-(block, group)
            # masked partials — including the ZERO partials of groups
            # with no members in a block — so worker-local finalized
            # rows are not bit-identical to it; instead embed each
            # worker's (V_k, G_k) partial stack into the global (V, G)
            # layout (zeros elsewhere, exactly what the masked update
            # produces for memberless groups) and rerun the same fold
            v_tot = sum(b["gp_n"].shape[1] for b in bundles)
            g_tot = sum(b["gp_n"].shape[2] for b in bundles)
            tail = bundles[0]["gp_n"].shape[3:]
            for w in range(w_total):
                leaves = []
                for name in ("n", "mean", "m2"):
                    full = np.zeros((v_tot, g_tot) + tail,
                                    bundles[0][f"gp_{name}"].dtype)
                    v0 = g0 = 0
                    for b in bundles:
                        part = b[f"gp_{name}"][w]
                        vk, gk = part.shape[:2]
                        full[v0:v0 + vk, g0:g0 + gk] = part
                        v0 += vk
                        g0 += gk
                    leaves.append(jnp.asarray(full))
                st = reduction.finalize(reduction.merge_blocks(
                    reduction.Welford(*leaves)))
                grouped.append(reduction.Stats(
                    *(np.asarray(v) for v in st)))
        sketches: list = []
        if all("sketch_hist" in b for b in bundles):
            pooled = not grouped  # G == 1 everywhere: counts add
            for w in range(w_total):
                hists = [b["sketch_hist"][w] for b in bundles]
                rares = ([b["sketch_rare"][w] for b in bundles]
                         if all("sketch_rare" in b for b in bundles)
                         else None)
                if pooled:
                    hist = np.sum(hists, axis=0, dtype=np.int32)
                    rare = (np.sum(rares, axis=0, dtype=np.int32)
                            if rares is not None else None)
                else:
                    hist = np.concatenate(hists, axis=0)
                    rare = (np.concatenate(rares, axis=0)
                            if rares is not None else None)
                sketches.append(WindowSketch(hist=hist, rare=rare))
        samples = (np.concatenate([b["samples"] for b in bundles], axis=0)
                   if all("samples" in b for b in bundles) else None)
        sketch_params = (SimpleNamespace(
            lo=np.asarray(bundles[0]["sketch_lo"]),
            width=np.asarray(bundles[0]["sketch_width"]))
            if "sketch_lo" in bundles[0] else None)
        final_x = np.concatenate([b["final_x"] for b in bundles], axis=0)
        return _FarmEngineView(
            experiment=self.experiment, grid=grid, stream=stream,
            grouped=grouped, sketches=sketches, samples=samples,
            final_x=final_x, metas=metas, sketch_params=sketch_params,
            steering=_merge_steering(
                metas, self.experiment.ensemble.replicas, w_total),
            watchdog_flagged=list(self.watchdog.flagged))

    # ---------------------------------------------------------- report
    def _report(self, slots) -> dict:
        return {
            "workers": self.recovery.workers,
            "restarts": self._total_restarts,
            "faults_by_kind": dict(self._faults),
            "reassignments": self._reassignments,
            "pipeline_depth_effective": max(
                m["telemetry"]["pipeline_depth_effective"]
                for m in (sh.bundle["_meta"] for sh in self.shards)),
            "frontier_stragglers": list(self.watchdog.flagged),
            "per_worker": {
                s.index: {"restarts": s.restarts, "retired": s.retired,
                          "shards_run": list(s.shards_run)}
                for s in slots},
            "worker_reports": {
                sh.index: sh.bundle["_meta"]["report"]
                for sh in self.shards},
            # engine-only run wall per shard (final successful attempt)
            # — process lifetime minus this is the worker's startup
            # cost (interpreter + jax import + jit), the part a farm
            # duplicates per process but overlaps on real multicore
            "worker_walls": {
                sh.index: sh.bundle["_meta"]["telemetry"]["wall_time_s"]
                for sh in self.shards},
            "events": list(self._events),
        }

    def _log(self, event: str, **detail) -> None:
        self._events.append({"event": event,
                             "t": round(time.time(), 3), **detail})


# -------------------------------------------------------------- merge
def _merge_steering(metas: list, replicas: int,
                    n_windows: int) -> Optional[dict]:
    """Merge worker-local steering reports into the global report the
    single-process run would have produced.

    Each worker steers its own whole sweep points, so its decisions ARE
    the global decisions restricted to its point range: stop entries
    concatenate (point indices offset by the worker's base point, in
    ascending shard order — matching the single flatnonzero scan),
    no_leap entries sum lane counts per window with `total_pinned`
    rebuilt from every worker's last-seen cumulative count."""
    reps = [m.get("steering") for m in metas]
    if all(r is None for r in reps):
        return None
    p0s = [m["lo"] // replicas for m in metas]
    stop: dict = {}
    noleap: dict = {}
    bimodal: list = []
    for wk, (rep, p0) in enumerate(zip(reps, p0s)):
        if rep is None:
            continue
        for d in rep["decisions"]:
            if d["action"] == "stop":
                stop.setdefault(d["window"], []).append(
                    ([p + p0 for p in d["points"]], d["rel_ci"]))
            elif d["action"] == "no_leap":
                noleap.setdefault(d["window"], []).append((wk, d))
        for f in rep.get("bimodal_flags", []):
            bimodal.append({"window": f["window"],
                            "point": f["point"] + p0, "obs": f["obs"]})
    decisions: list = []
    totals = [0] * len(metas)
    for w in sorted(set(stop) | set(noleap)):
        if w in stop:  # decide() logs stops before no_leap pins
            pts: list = []
            ci: list = []
            for p_list, ci_list in stop[w]:
                pts += p_list
                ci += ci_list
            decisions.append({"window": w, "action": "stop",
                              "points": pts, "rel_ci": ci})
        if w in noleap:
            n_new = 0
            for wk, d in noleap[w]:
                totals[wk] = d["total_pinned"]
                n_new += d["n_lanes"]
            decisions.append({"window": w, "action": "no_leap",
                              "n_lanes": n_new,
                              "total_pinned": sum(totals)})
    bimodal.sort(key=lambda f: (f["window"], f["point"], f["obs"]))
    stop_windows: dict = {}
    stopped: list = []
    total = simulated = 0
    pinned = 0
    for rep, p0 in zip(reps, p0s):
        if rep is None:
            continue
        total += rep["point_windows_total"]
        simulated += rep["point_windows_simulated"]
        pinned += rep["lanes_pinned_exact"]
        stopped += [p + p0 for p in rep["stopped_points"]]
        for p, w in rep["stop_windows"].items():
            stop_windows[int(p) + p0] = int(w)
    stopped.sort()
    return {
        "n_points": sum(r["n_points"] for r in reps if r is not None),
        "stopped_points": stopped,
        "stop_windows": {p: stop_windows[p] for p in sorted(stop_windows)},
        "point_windows_total": total,
        "point_windows_simulated": simulated,
        "windows_saved_ratio": (total / simulated if simulated
                                else float(total)),
        "lanes_pinned_exact": pinned,
        "bimodal_flags": bimodal,
        "decisions": decisions,
    }


class _FarmEngineView:
    """A merged, finished pseudo-engine: exactly the attribute surface
    SimulationResult reads, fed from the workers' merged bundles. It
    cannot run further windows — `resume()` on the handle is a no-op
    (the run is complete) and `checkpoint()` is rejected."""

    def __init__(self, experiment, grid, stream, grouped, sketches,
                 samples, final_x, metas, sketch_params, steering,
                 watchdog_flagged):
        self.grid = grid
        self._sketch = sketch_params
        self.stream = stream
        self.obs_names = list(metas[0]["obs_names"])
        self.cfg = SimpleNamespace(window_block=experiment.window_block)
        self._steer = None
        self._window = len(grid)
        self._pool = SimpleNamespace(x=final_x)
        self._grouped = grouped
        self._sketches = sketches
        self._samples = samples
        self._steering = steering
        tels = [m["telemetry"] for m in metas]
        w = len(grid)
        self.build_s = max(t["build_s"] for t in tels)
        # every worker runs the same network through the same path
        self.kernel_layout = tels[0]["kernel_layout"]
        self.peak_buffered_bytes = max(
            t["peak_buffered_bytes"] for t in tels)
        self.n_dispatches = sum(t["dispatches"] for t in tels)
        self.n_host_syncs = sum(t["host_syncs"] for t in tels)
        # per-window step/leap counts only merge when every worker has
        # a full-length series (no mid-run restarts trimmed it)
        if all(len(t["steps_per_window"]) == w for t in tels):
            self.window_steps = [
                sum(col) & 0xFFFFFFFF
                for col in zip(*(t["steps_per_window"] for t in tels))]
            self.window_leaps = [
                sum(col) & 0xFFFFFFFF
                for col in zip(*(t["leaps_per_window"] for t in tels))]
        else:
            self.window_steps = []
            self.window_leaps = []
        # each worker is one shard of the pool
        self.n_shards = len(tels)
        self.window_shard_steps = (
            [tuple(col) for col in zip(*(t["steps_per_window"]
                                         for t in tels))]
            if self.window_steps else [])
        # the most chunks any worker's window ran (kernel paths)
        self.window_chunks = (
            [max(col) for col in zip(*(t["chunks_per_window"]
                                       for t in tels))]
            if all(len(t["chunks_per_window"]) == w for t in tels)
            else [])
        observed = sum(t["watchdog_observed"] for t in tels)
        flagged = sorted(
            (tuple(f) for t in tels for f in t["straggler_windows"]),
            key=lambda f: f[0])
        self.watchdog = SimpleNamespace(
            flagged=flagged + [("frontier",) + tuple(f)
                               for f in watchdog_flagged],
            straggler_rate=lambda: (len(flagged) / observed
                                    if observed else 0.0))
        self.block_walls = [tuple(bw) for t in tels
                            for bw in t["block_walls"]]
        self.block_walls.sort(key=lambda b: b[0])
        self.pipeline_depth = max(t["pipeline_depth"] for t in tels)
        self.pipeline_depth_effective = max(
            t["pipeline_depth_effective"] for t in tels)
        self.peak_inflight_blocks = max(
            t["peak_inflight_blocks"] for t in tels)
        self.n_snapshot_saves = sum(t["snapshot_saves"] for t in tels)
        self.n_ckpt_flushes = sum(t["ckpt_flushes"] for t in tels)

    # ----------------------------------------------- result interface
    def flush(self) -> None:
        pass  # nothing in flight: the farm merged finished bundles

    def trajectories(self):
        return self._samples

    def grouped_stats(self):
        return list(self._grouped)

    def sketches(self):
        return list(self._sketches)

    def steering_report(self):
        return self._steering

    def checkpoint(self, path: str) -> None:
        raise RuntimeError(
            "a farm result is already complete and has no live pool to "
            "checkpoint; per-worker checkpoints live under "
            "Recovery.ckpt_dir namespaces")
