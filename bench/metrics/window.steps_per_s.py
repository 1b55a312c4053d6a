"""window.steps_per_s (program counter: Telemetry.steps_per_window):
SSA events fired over the window, per second of the window."""


def read(run):
    steps = sum(sum(e.steps_per_window) for e in run.ensembles)
    return steps / run.window.window_s
