"""Sharded ensemble farm scalability (the paper's Fig. 7 sweep, taken
distributed): the same experiment farmed over 1/2/4/8 shards, with and
without the Pallas fused kernel inside each shard (the paper's two
families — single-simulation speedup × simulation farm — composed).

XLA's forced host-device count must be set before jax imports, so each
shard count runs in a subprocess (same pattern as
tests/test_distributed.py). Per point we report:

  * steady-state window wall time (median, post-warmup),
  * device dispatches — one per window on the sharded path, O(1) in
    shard count (vs one per group x window on the host-loop baseline),
  * blocking host syncs,
  * a digest of the records, asserting every shard count — AND the
    kernel vs jnp window body — reproduces the single-device fused
    baseline BIT-IDENTICALLY (counter-based per-lane RNG, stat_blocks
    pinned).

Forced host devices share the machine's cores, so wall time on one CPU
is about flat (the win is the dispatch/sync profile and the per-device
memory slice); on a real multi-host mesh the same program scales the
paper's farm across nodes.

  PYTHONPATH=src python benchmarks/sharded_farm.py
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_COUNTS = (1, 2, 4, 8)
STAT_BLOCKS = 8

CHILD = """
import hashlib
import numpy as np
from repro.api import (Ensemble, Experiment, Partitioning, Schedule,
                       simulate)
from repro.core.cwc.models import lotka_volterra

K = {k}
exp = Experiment(
    model=lotka_volterra(2),
    ensemble=Ensemble.make(replicas={instances}),
    schedule=Schedule(t_end=2.0, n_windows={windows}, schema="iii"),
    n_lanes={lanes}, seed=7, use_kernel={kernel},
    window_block={window_block},
    partitioning=Partitioning(n_shards=K, stat_blocks={blocks}))
res = simulate(exp)
tele = res.telemetry
steady = sorted((d + c) / n for _, n, d, c in tele.block_walls[1:])
digest = hashlib.sha256(
    np.stack([np.concatenate([r.mean, r.var, r.ci90]) for r in
              res.records]).tobytes()).hexdigest()[:16]
print(f"{{K}},{{tele.dispatches}},{{tele.host_syncs}},"
      f"{{1e3 * steady[len(steady) // 2]:.2f}},"
      f"{{tele.wall_time_s:.2f}},{{digest}}")
"""


def run_point(k: int, instances: int, lanes: int, windows: int,
              kernel: bool = False, window_block: int = 1) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={k}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    snippet = textwrap.dedent(CHILD.format(
        k=k, instances=instances, lanes=lanes, windows=windows,
        blocks=STAT_BLOCKS, kernel=kernel, window_block=window_block))
    out = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise SystemExit(out.stderr[-4000:])
    return out.stdout.strip()


def main() -> None:
    instances, lanes, windows = 512, 64, 8
    print(f"# sharded_farm: {instances} instances, {lanes} lanes, "
          f"{windows} windows, stat_blocks={STAT_BLOCKS}")
    print("shards,kernel,dispatches,host_syncs,wall_per_window_ms,"
          "wall_total_s,records_sha")
    digests = {}
    for kernel in (False, True):
        for k in SHARD_COUNTS:
            row = run_point(k, instances, lanes, windows, kernel=kernel)
            digests[(k, kernel)] = row.rsplit(",", 1)[1]
            shards, rest = row.split(",", 1)
            print(f"{shards},{int(kernel)},{rest}")
    assert len(set(digests.values())) == 1, (
        f"records diverged across shard counts / window bodies: "
        f"{digests}")
    print(f"#  records bit-identical across shards {SHARD_COUNTS} AND "
          "across kernel/jnp window bodies; dispatches stay one per "
          "window (O(1) in shard count)")


if __name__ == "__main__":
    main()
