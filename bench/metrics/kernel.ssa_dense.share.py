"""kernel.ssa_dense.share (device trace): the dense exact SSA window
kernel's device time over the device's busy time."""
from benchkit import spec, trace

PATTERN = spec.load_module("metrics", "kernel.ssa_dense_roofline").PATTERN


def read(run):
    return trace.kernel_share(run, PATTERN)
