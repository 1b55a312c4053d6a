"""traj_per_s (host clock): trajectories simulated to the horizon and
reduced into the delivered window records, over all ensembles the
window completed, per second of the window."""


def read(run):
    return sum(e.replicas for e in run.ensembles) / run.window.window_s
