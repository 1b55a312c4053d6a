"""How `correct` is decided: what the timed path delivered, against the
plain reference (`reference.py`), number by number, each beside its
limit.

Every ensemble of the window, exactly:

    n_short      largest |n - replicas| over the windows' records: every
                 replica is in every window's statistics (limit 0)
    bad_states   final lane populations that are not finite non-negative
                 integers (limit 0)
    reduce_gap   largest gap between the last window's record (mean and
                 variance of each observable) and the same statistic of
                 the delivered final lane states, computed here in
                 float64, relative to max(|statistic|, 1)

One ensemble drawn from the seed, against the reference's own ensemble
of `reference.replicas` lanes on the same window grid:

    z_stats      largest |mean_program - mean_reference| / standard
                 error, over every window and observable of the records
                 and every species of the final lane states, and the
                 same for the variances (the standard error of each
                 sample variance from the reference's fourth moment).
                 The variances carry what the means may not: a network
                 started at its steady state, as Lotka's, keeps its
                 mean there while its lanes spread
    z_events     the same for the reactions fired per lane over the whole
                 horizon (the program's Telemetry.steps_per_window summed,
                 / replicas). The program reports totals only, so the
                 spread of its lanes is taken as the reference's; summed
                 over the horizon, the rare lanes with many late events
                 (Lotka-Volterra prey that outlive their predators) are a
                 negligible share of it, as they are not of one late
                 window
"""
from __future__ import annotations

import numpy as np

#: compared numbers, in the order they are printed
NAMES = ("n_short", "bad_states", "reduce_gap", "z_stats", "z_events")


def obs_columns(net, species_order, obs_names):
    """For each program observable, the program's species columns that
    the reference sums into it (matched by name)."""
    col = {s: i for i, s in enumerate(species_order)}
    missing = [o for o in obs_names if o not in net.observables]
    if missing:
        raise KeyError(f"observables {missing} have no reference "
                       f"definition")
    return [[col[s] for s in net.observables[o]] for o in obs_names]


def exact_numbers(ens, obs_cols) -> dict:
    """n_short, bad_states and reduce_gap of one ensemble."""
    n_short = float(np.max(np.abs(ens.n - ens.replicas)))
    f = ens.final
    bad = int(np.sum(~np.isfinite(f)) + np.sum(f < 0)
              + np.sum(np.isfinite(f) & (f != np.round(f))))
    obs = np.stack([f[:, c].sum(axis=1) for c in obs_cols], axis=1)
    m = obs.mean(axis=0)
    v = obs.var(axis=0, ddof=1) if obs.shape[0] > 1 else np.zeros_like(m)
    gap = max(np.max(np.abs(ens.mean[-1] - m) / np.maximum(np.abs(m), 1)),
              np.max(np.abs(ens.var[-1] - v) / np.maximum(np.abs(v), 1)))
    return {"n_short": n_short, "bad_states": float(bad),
            "reduce_gap": float(gap)}


def _z(m_p, v_p, n_p, m_r, v_r, n_r):
    """|difference of means| over its standard error; a difference
    smaller than one count in one reference lane reads 0."""
    se = np.sqrt(np.maximum(v_p, 0) / n_p + np.maximum(v_r, 0) / n_r)
    d = np.abs(m_p - m_r)
    z = np.where(d * n_r < 1, 0.0, d / np.maximum(se, 1.0 / n_r))
    return float(np.max(z)) if z.size else 0.0


def _z_var(v_p, n_p, v_r, m4_r, n_r):
    """|difference of sample variances| over its standard error, the
    variance of each sample variance taken from the reference's fourth
    central moment m4: (m4 - v^2 (n - 3) / (n - 1)) / n. A difference
    smaller than one count in one reference lane reads 0."""
    def se2(n):
        return np.maximum(m4_r - v_r ** 2 * (n - 3) / (n - 1), 0) / n

    se = np.sqrt(se2(n_p) + se2(n_r))
    d = np.abs(v_p - v_r)
    z = np.where(d * n_r < 1, 0.0, d / np.maximum(se, 1.0 / n_r))
    return float(np.max(z)) if z.size else 0.0


def _m4(a, axis):
    return np.mean((a - a.mean(axis=axis, keepdims=True)) ** 4, axis=axis)


def reference_numbers(ens, ref, net, species_order, obs_cols) -> dict:
    """z_stats and z_events of one ensemble against reference samples
    `ref` (reference.Samples on the same grid)."""
    ix = net.index()
    order = [ix[s] for s in species_order]
    x_r = ref.x[:, :, order]  # (W, n_r, S) in the program's order
    n_r = x_r.shape[1]
    obs_r = np.stack([x_r[:, :, c].sum(axis=2) for c in obs_cols],
                     axis=2)  # (W, n_r, n_obs)
    v_r = obs_r.var(axis=1, ddof=1)
    z_rec = _z(ens.mean, ens.var, ens.n[:, None], obs_r.mean(axis=1),
               v_r, n_r)
    zv_rec = _z_var(ens.var, ens.n[:, None], v_r, _m4(obs_r, 1), n_r)
    f = ens.final
    vf_r = x_r[-1].var(axis=0, ddof=1)
    z_fin = _z(f.mean(axis=0), f.var(axis=0, ddof=1), f.shape[0],
               x_r[-1].mean(axis=0), vf_r, n_r)
    zv_fin = _z_var(f.var(axis=0, ddof=1), f.shape[0], vf_r,
                    _m4(x_r[-1], 0), n_r)
    ev_p = float(np.sum(ens.steps_per_window)) / ens.replicas
    ev_r = ref.events.sum(axis=0).astype(np.float64)  # (n_r,) per lane
    v_e = ev_r.var(ddof=1)
    z_ev = _z(np.asarray([ev_p]), v_e, ens.replicas,
              np.asarray([ev_r.mean()]), v_e, n_r)
    return {"z_stats": max(z_rec, z_fin, zv_rec, zv_fin), "z_events": z_ev}


def judge(numbers: dict, limits: dict) -> bool:
    """Correct when every number is at or under its limit (a missing or
    non-finite number is not)."""
    for name in NAMES:
        v = numbers.get(name)
        if v is None or not np.isfinite(v) or v > limits[name]:
            return False
    return True


def worst(per_ensemble: list) -> dict:
    """The largest reading of each number over the checked ensembles."""
    out = {}
    for nums in per_ensemble:
        for k, v in nums.items():
            prev = out.get(k)
            if prev is None or np.isnan(v) or v > prev:  # NaN stays
                out[k] = prev if prev is not None and np.isnan(prev) else v
    return out


def checks_line(numbers: dict, limits: dict) -> dict:
    """{name: [number, limit]} in the printed order."""
    return {k: [numbers.get(k), limits[k]] for k in NAMES}
