"""The device under measurement: which chip, its published peaks, the
compile cache and compile clock, and device memory."""
from __future__ import annotations

import json
import os
import time

from benchkit.spec import BENCH, ROOT

#: the checkout's own persistent compile cache: a fixed path, so that
#: every run of a cell in one checkout finds what the first compiled
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux /proc), so that
    `setup_s` counts the interpreter's start and every import."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def start_clock() -> float:
    """perf_counter() reading of the moment this process started."""
    return time.perf_counter() - process_age_s()


def enable_cache() -> str:
    """Point the program's compile cache at the checkout and cache
    every program, however fast it compiled (the window kernels compile
    in under a second and would otherwise be compiled again in every
    run). Call before JAX starts a backend."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_peaks(kind: str) -> dict:
    """Published peaks of one chip of `kind` (peaks.json); an unknown
    kind is an error, never a default."""
    with open(BENCH / "peaks.json", encoding="utf-8") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise NoChip(f"no published peaks for device kind {kind!r} in "
                     f"peaks.json (have {sorted(table['devices'])})")
    return dict(table["devices"][kind])


def chips(needed: int):
    """The devices of this run: refuses anything but `needed` or more
    TPU chips. Returns (devices, description dict)."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChip(f"platform is {platform!r}, not tpu")
    if len(devs) < needed:
        raise NoChip(f"the cell asks for {needed} chips, JAX sees "
                     f"{len(devs)}")
    devs = devs[:needed]
    return devs, {"platform": platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest of `devs`, where reported."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileClock:
    """XLA backend compile seconds (persistent-cache reads included)
    and persistent-cache misses, from JAX's monitoring events. Tracing
    is left out: its events nest for jit-in-jit and would count twice."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == self.COMPILE:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self.MISS:
            self.misses += 1

    def read(self) -> tuple:
        return self.compile_s, self.misses
