"""The control: the plain reference put in the program's place,
computed in the nearest precision below the configuration's (bfloat16
for float32), whose readings must fail the check.

`as_delivered` turns a reference ensemble into what the timed path
delivers (per-window records, final lane states in the program's
column order, events per window), with the records computed in the
same precision, so that `check` reads it exactly as it reads the
program. `fault` plants one of the faults the check must catch, in the
reference put in the program's place:

    unchanged  every step returns its state unchanged: lanes stay at x0
               and fire nothing
    half       half of the lanes left out of the records, the mean and
               variance taken over the rest
    shifted    an answer altered where it is produced: each window's
               record carries the previous window's statistics
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchkit.campaign import EnsembleRun


FAULTS = ("unchanged", "half", "shifted")


def as_delivered(samples, net, species_order, obs_names, dtype,
                 fault: str | None = None) -> EnsembleRun:
    ix = net.index()
    order = [ix[s] for s in species_order]
    x = samples.x[:, :, order]  # (W, n, S)
    events = samples.events.sum(axis=1)
    n = x.shape[1]
    if fault == "unchanged":
        x0 = net.arrays()[4][order]
        x = np.broadcast_to(x0, x.shape).copy()
        events = np.zeros_like(events)
    col = {s: i for i, s in enumerate(species_order)}
    cols = [[col[s] for s in net.observables[o]] for o in obs_names]
    obs = np.stack([x[:, :, c].sum(axis=2) for c in cols], axis=2)
    if fault == "half":
        obs = obs[:, : n // 2]
    obs = jnp.asarray(obs, dtype)  # (W, lanes reduced, n_obs)
    mean = np.asarray(jnp.mean(obs, axis=1).astype(dtype), np.float64)
    var = np.asarray(jnp.var(obs, axis=1, ddof=1).astype(dtype),
                     np.float64)
    if fault == "shifted":
        mean, var = np.roll(mean, 1, axis=0), np.roll(var, 1, axis=0)
    return EnsembleRun(
        seed=0, replicas=n, wall_s=0.0,
        obs_names=tuple(obs_names), mean=mean, var=var,
        n=np.full(x.shape[0], float(obs.shape[1])), final=x[-1],
        steps_per_window=tuple(int(v) for v in events),
        completed=True)
