"""entry.compile_s (program spans): host seconds per ensemble spent
making the programs the ensemble runs, in the traced window: JAX's
tracing and lowering, from its own spans in the profiler trace (nested
spans counted once), plus XLA backend compile seconds, persistent-cache
reads included, from JAX's compile monitoring events. An engine that
makes its programs afresh pays its tracing, lowering and cache reads in
every ensemble; one that reuses them pays nothing, and reads 0 s.

0 means that nothing was traced, lowered or compiled in the window.
None where the trace holds none of these spans anywhere, the canary's
before the window included (`campaign.run_window` compiles one fresh
function in every traced run): JAX then no longer names them
`trace_to_jaxpr_dynamic`, `trace_to_jaxpr_nounits` and
`lower_sharding_computation` (jax 0.9), and the harness fails a traced
run in which a listed metric reads nothing, so that the names here
follow JAX's."""
from benchkit import trace

SPANS = ("trace_to_jaxpr_dynamic", "trace_to_jaxpr_nounits",
         "lower_sharding_computation")


def read(run):
    if run.trace is None or not run.ensembles:
        return None
    secs, spans = trace.host_union_s(run.trace, SPANS)
    if not spans:
        return None
    return (secs + run.window.compile_s) / len(run.ensembles)
