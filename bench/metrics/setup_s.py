"""setup_s (host clock): process start to the start of the window:
imports, device start-up, engine build, compile or cache reads, and the
warm-up block."""


def read(run):
    return run.window.setup_s
