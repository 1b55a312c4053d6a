"""kernel.ssa_dense_roofline (device trace): the dense exact SSA window
kernel's share of its roofline (benchkit.trace.kernel_roofline, with
the counts of work/ssa_dense.py)."""
from benchkit import trace

#: the kernel's operations in the device trace (TPU v5e, jax 0.9): the
#: Mosaic custom call carries the name of the jitted wrapper around its
#: pallas_call, e.g. `%ssa_window_call.7 = (f32[65536,2]...) custom-call(...)`
PATTERN = r"(?s)%ssa_window_call(\.\d+)? = .*"


def read(run):
    return trace.kernel_roofline(run, PATTERN, "ssa_dense")
