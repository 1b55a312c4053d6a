"""From the profiler's trace to device busy time, kernel time and the
idle gaps, with the host span each gap falls in.

A trace is read as plain intervals, so that the reduction can be
checked on a small recorded trace without a chip:

    device_ops  {device: [(name, start_ns, end_ns), ...]}  operations
                on each device's "XLA Ops" line
    host_spans  [(name, start_ns, end_ns), ...]  the host's events
                (the benchmark's TraceAnnotations among them)

Busy time is the union of a device's operation intervals; the idle share
is 1 - busy / window. Times are in the trace's own clock, in which host
and device events are aligned by the profiler.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: host spans the benchmark opens itself; a gap is named by the
#: innermost of these, then by the innermost other host event
BENCH_SPAN = "bench."


@dataclass
class Trace:
    device_ops: dict
    host_spans: list
    t0_ns: int  # traced window
    t1_ns: int

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def read(trace_dir: str) -> Trace:
    """Read the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(files[-1]))


def from_profile(data) -> Trace:
    """A jax.profiler.ProfileData as plain intervals. The traced window
    runs from the first to the last end of the benchmark's own spans
    (or of the device operations, where there are none)."""
    device_ops, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                            for ev in line.events]
            device_ops[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(ev.name, int(ev.start_ns), int(ev.end_ns))
                         for ev in line.events if ev.duration_ns > 0]
    spans = [h for h in host if h[0].startswith(BENCH_SPAN)]
    if spans:
        t0, t1 = min(s[1] for s in spans), max(s[2] for s in spans)
    else:
        every = [o for ops in device_ops.values() for o in ops]
        t0 = min((o[1] for o in every), default=0)
        t1 = max((o[2] for o in every), default=0)
    return Trace(device_ops=device_ops, host_spans=host, t0_ns=t0,
                 t1_ns=t1)


def busy_intervals(ops, t0: int, t1: int) -> list:
    """Union of the operation intervals, clipped to [t0, t1]."""
    out = []
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the traced devices."""
    if not trace.device_ops:
        return 0.0
    tot = 0.0
    for ops in trace.device_ops.values():
        tot += sum(e - s for s, e in busy_intervals(
            ops, trace.t0_ns, trace.t1_ns))
    return tot / len(trace.device_ops) / 1e9


def kernel_time(trace: Trace, pattern: str) -> tuple:
    """(seconds, calls) of the operations whose whole name matches the
    regular expression `pattern`, summed over devices (clipped to the
    window)."""
    match = re.compile(pattern).fullmatch
    secs, calls = 0.0, 0
    for ops in trace.device_ops.values():
        for name, s, e in ops:
            if match(name):
                s, e = max(s, trace.t0_ns), min(e, trace.t1_ns)
                if e > s:
                    secs += (e - s) / 1e9
                    calls += 1
    return secs, calls


_OPCODE = re.compile(r"[\]\})]\s([a-z][a-z0-9-]*)\(")


def short_name(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12 (fusion)`: the
    instruction and its opcode, without the shapes."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(rhs)
    return f"{lhs.lstrip('%')} ({m.group(1)})" if m else lhs.lstrip("%")


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[name, seconds]] of the k operations that took most device time
    (summed over calls and devices; an operation nested in another, as
    a kernel in its while loop, counts in both)."""
    tot = defaultdict(float)
    for ops in trace.device_ops.values():
        for name, s, e in ops:
            s, e = max(s, trace.t0_ns), min(e, trace.t1_ns)
            if e > s:
                tot[short_name(name)] += (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:k]]


def _name(active) -> str:
    ours = [h for h in active if h[0].startswith(BENCH_SPAN)]
    other = [h for h in active if not h[0].startswith(BENCH_SPAN)]
    parts = [min(g, key=lambda h: h[2] - h[1])[0]
             for g in (ours, other) if g]
    return " > ".join(parts) if parts else "(no host span)"


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[[host activity, seconds]]: the idle time of the first traced
    device, summed by what the host was doing at each gap's midpoint
    (the innermost benchmark span, then the innermost other host
    event), the k largest."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = busy_intervals(ops, trace.t0_ns, trace.t1_ns)
    edges = [trace.t0_ns] + [x for iv in busy for x in iv] + [trace.t1_ns]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    hosts = sorted(trace.host_spans, key=lambda h: h[1])
    active, i, tot = [], 0, defaultdict(float)
    for s, e in gaps:  # ascending, so a sweep over the host events
        mid = (s + e) // 2
        while i < len(hosts) and hosts[i][1] <= mid:
            active.append(hosts[i])
            i += 1
        active = [h for h in active if h[2] > mid]
        tot[_name(active)] += (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:k]]


def host_union_s(trace: Trace, names) -> tuple:
    """(seconds, spans): the union of the host spans whose name is in
    `names`, clipped to the traced window, and how many there were.
    Nested spans (a jit traced inside another's tracing) count once."""
    spans = [(s, e) for n, s, e in trace.host_spans if n in names]
    ivs = busy_intervals([("", s, e) for s, e in spans],
                         trace.t0_ns, trace.t1_ns)
    return sum(e - s for s, e in ivs) / 1e9, len(spans)


def custom_calls(trace: Trace) -> set:
    """Short names of the device operations that are custom calls: on a
    TPU, the Mosaic kernels that Pallas emits."""
    return {short_name(name) for ops in trace.device_ops.values()
            for name, _, _ in ops if short_name(name).endswith(
                "(custom-call)")}


def kernel_share(run, pattern: str):
    """Percent of device-busy time spent in the kernel `pattern`
    matches; None where the trace holds no such kernel."""
    if run.trace is None:
        return None
    secs, calls = kernel_time(run.trace, pattern)
    busy = busy_s(run.trace) * len(run.trace.device_ops)
    if not calls or busy <= 0:
        return None
    return 100.0 * secs / busy


def kernel_roofline(run, pattern: str, work: str):
    """Percent of the kernel's device time that the chip's published
    peaks would need for the work the method needs: the larger of
    flops / peak FLOP/s and bytes / peak bytes/s, over the kernel's
    time. flops = events fired in the window x work.flops_per_event;
    bytes = kernel calls x work.bytes_per_call. None where the trace
    holds no such kernel or the device has no peaks."""
    if run.trace is None or run.peaks is None:
        return None
    secs, calls = kernel_time(run.trace, pattern)
    if not calls or secs <= 0:
        return None
    w = run.work(work)
    events = sum(sum(e.steps_per_window) for e in run.ensembles)
    lanes = int(run.cell.settings["n_lanes"])
    flops = events * w.flops_per_event(run.network)
    nbytes = calls * w.bytes_per_call(run.network, lanes)
    need = max(flops / run.peaks["flops_per_s"],
               nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / secs
