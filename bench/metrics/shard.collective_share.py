"""shard.collective_share (device trace): the share of each chip's busy
time spent in cross-chip collective operations, averaged over the
chips. The per-window psum of the statistics waits for the slowest
shard, so this is the exposed cost of lock-step across chips.

A collective is an operation whose HLO opcode, or whose instruction
name where the trace shows no opcode, starts with one of `OPCODES`, as
the chip's trace prints them: `%all-reduce.3 = f32[4,2]{1,0}
all-reduce(...)`, and the `-start`/`-done` halves of the asynchronous
forms. None where no collective ran on any chip."""
import re

from benchkit import trace

OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute")
_OPCODE = re.compile(r"[\]\})]\s([a-z][a-z0-9-]*)\(")


def is_collective(name: str) -> bool:
    lhs, _, rhs = name.partition(" = ")
    m = _OPCODE.search(rhs)
    return (m.group(1) if m else lhs.lstrip("%")).startswith(OPCODES)


def read(run):
    tr = run.trace
    if tr is None or not tr.device_ops:
        return None
    shares, seen = [], False
    for ops in tr.device_ops.values():
        busy = trace.busy_intervals(ops, tr.t0_ns, tr.t1_ns)
        coll = trace.busy_intervals(
            [o for o in ops if is_collective(o[0])], tr.t0_ns, tr.t1_ns)
        seen = seen or bool(coll)
        busy_ns = sum(e - s for s, e in busy)
        if busy_ns > 0:
            shares.append(sum(e - s for s, e in coll) / busy_ns)
    if not seen or not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
