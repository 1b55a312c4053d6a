"""shard.host_s (program spans): host seconds per ensemble spent placing
a new engine's pool, rates and group ids on the mesh
(`repro.dispatch.place`) and pulling the lane states back from every
shard (`repro.collect.gather`), from the profiler trace, nested spans
counted once. None where the trace holds neither span."""
from benchkit import trace

SPANS = ("repro.dispatch.place", "repro.collect.gather")


def read(run):
    if run.trace is None or not run.ensembles:
        return None
    secs, spans = trace.host_union_s(run.trace, SPANS)
    if not spans:
        return None
    return secs / len(run.ensembles)
