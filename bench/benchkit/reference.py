"""Plain reference: Gillespie's direct method in straightforward jax.numpy.

It imports nothing of the simulator under test. A configuration's plain
reference module (`configs/<name>.py`) builds a `Network` from the
parameters in its JSON file; `simulate` runs `n` independent replicas
of it over a window grid, with a random stream of its own.

One step, for every lane that has not reached the window's horizon:

    a_j  = k_j * prod_i C(x_i, c_ij)         mass-action propensities
    a0   = sum_j a_j
    tau  = -log(u1) / a0                     exponential waiting time
    j*   = first j with cumsum(a)_j >= u2*a0 (only reactions with a_j > 0)
    x   += delta[j*]   if t + tau <= horizon, else the lane stops at the
                       horizon (the exponential is memoryless, so the
                       next window draws afresh)

`dtype` is the arithmetic of the whole run: populations, propensities,
clocks and the per-window statistics. float32 is the reference;
bfloat16 is the control that a lower precision must fail.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Network:
    """A reaction network in plain terms.

    species: names, in the simulator's labelling, so that its columns
    can be matched by name; reactions: (reactants, products, rate) with
    reactants/products as {species name: count}; x0: {species: count};
    observables: {observable name: [species names summed]}.
    """

    species: tuple
    reactions: tuple
    x0: dict
    observables: dict

    @property
    def n_species(self) -> int:
        return len(self.species)

    @property
    def n_reactions(self) -> int:
        return len(self.reactions)

    def index(self) -> dict:
        return {s: i for i, s in enumerate(self.species)}

    def arrays(self):
        """(reactant index (R, M), reactant count (R, M), delta (R, S),
        rates (R,), x0 (S,)) as numpy arrays; padding has count 0."""
        ix = self.index()
        m = max(1, max(len(r) for r, _, _ in self.reactions))
        r_n, s_n = self.n_reactions, self.n_species
        idx = np.zeros((r_n, m), np.int32)
        cnt = np.zeros((r_n, m), np.int32)
        delta = np.zeros((r_n, s_n), np.int32)
        rates = np.zeros(r_n, np.float64)
        for j, (lhs, rhs, k) in enumerate(self.reactions):
            for q, (s, c) in enumerate(sorted(lhs.items())):
                idx[j, q], cnt[j, q] = ix[s], c
                delta[j, ix[s]] -= c
            for s, c in rhs.items():
                delta[j, ix[s]] += c
            rates[j] = k
        x0 = np.zeros(s_n, np.float64)
        for s, c in self.x0.items():
            x0[ix[s]] = c
        return idx, cnt, delta, rates, x0

    def dependency_degree(self) -> np.ndarray:
        """(R,) number of reactions whose propensity changes when
        reaction j fires (j itself included where it changes its own
        reactants): the direct method's per-event recompute set."""
        idx, cnt, delta, _, _ = self.arrays()
        reads = [set(int(i) for i, c in zip(idx[j], cnt[j]) if c > 0)
                 for j in range(self.n_reactions)]
        out = np.zeros(self.n_reactions, np.int64)
        for j in range(self.n_reactions):
            changed = set(np.nonzero(delta[j])[0].tolist())
            out[j] = sum(1 for r in reads if r & changed)
        return out


@dataclass
class Samples:
    """One simulated ensemble, on the host: x (W, n, S) populations at
    each window's horizon, events (W, n) reactions fired per window,
    capped (W,) whether any lane hit the per-window step cap."""

    x: np.ndarray
    events: np.ndarray
    capped: np.ndarray


def _comb(x, c, max_c: int):
    """C(x, c) for integer-valued x >= 0 and 0 <= c <= max_c."""
    num = jnp.ones_like(x)
    den = jnp.ones_like(x)
    for i in range(max_c):
        on = c > i
        num = jnp.where(on, num * jnp.maximum(x - i, 0), num)
        den = jnp.where(on, den * (i + 1), den)
    return num / den


@partial(jax.jit, static_argnames=("dtype", "cap", "max_c"))
def _window(x, t, events, key, horizon, idx, cnt, delta, rates, *,
            dtype, cap: int, max_c: int):
    """Advance every lane to `horizon`; at most `cap` steps."""

    def live(t):
        return t < horizon

    def cond(c):
        x, t, ev, key, it = c
        return (it < cap) & jnp.any(live(t))

    def body(c):
        x, t, ev, key, it = c
        key, sub = jax.random.split(key)
        u = jax.random.uniform(sub, (x.shape[0], 2), jnp.float32,
                               minval=np.float32(2.0 ** -24),
                               maxval=1.0).astype(dtype)
        a = rates[None, :] * jnp.prod(
            _comb(x[:, idx], cnt[None], max_c), axis=2)  # (n, R)
        a = a.astype(dtype)
        a0 = jnp.sum(a, axis=1)
        act = live(t)
        tau = -jnp.log(u[:, 0]) / jnp.where(a0 > 0, a0, 1)
        tn = (t + tau).astype(dtype)
        fire = act & (a0 > 0) & (tn <= horizon)
        cum = jnp.cumsum(a, axis=1)
        pos = jnp.arange(a.shape[1])
        ok = (a > 0) & (cum >= (u[:, 1] * a0)[:, None])
        last = jnp.max(jnp.where(a > 0, pos, 0), axis=1)
        j = jnp.where(jnp.any(ok, axis=1),
                      jnp.argmax(ok, axis=1), last)
        x = jnp.where(fire[:, None], (x + delta[j]).astype(dtype), x)
        t = jnp.where(fire, tn, jnp.where(act, horizon, t)).astype(dtype)
        return x, t, ev + fire.astype(jnp.int32), key, it + 1

    x, t, events, key, it = jax.lax.while_loop(
        cond, body, (x, t, events, key, jnp.int32(0)))
    return x, t, events, key, jnp.any(live(t))


def simulate(net: Network, n: int, grid, seed_words, *, dtype="float32",
             cap: int = 1 << 20) -> Samples:
    """Run `n` replicas of `net` to each horizon of `grid` (ascending).

    seed_words: a tuple of non-negative ints from which the stream is
    derived; cap: steps per window at most (a lane still short of the
    horizon then is recorded where it stopped, and `capped` says so)."""
    dt = jnp.dtype(dtype)
    idx, cnt, delta, rates, x0 = net.arrays()
    max_c = int(cnt.max())
    ss = np.random.SeedSequence(list(seed_words))
    key = jax.random.PRNGKey(int(ss.generate_state(1)[0]))
    x = jnp.broadcast_to(jnp.asarray(x0, dt), (n, net.n_species))
    t = jnp.zeros((n,), dt)
    idx_d, cnt_d = jnp.asarray(idx), jnp.asarray(cnt, dt)
    delta_d, rates_d = jnp.asarray(delta, dt), jnp.asarray(rates, dt)
    xs, evs, capped = [], [], []
    for h in grid:
        ev = jnp.zeros((n,), jnp.int32)
        x, t, ev, key, short = _window(
            x, t, ev, key, jnp.asarray(h, dt), idx_d, cnt_d, delta_d,
            rates_d, dtype=dt, cap=cap, max_c=max_c)
        # a capped lane jumps to the horizon, as the simulator's own
        # budgeted windows would not: the record is of where it stopped
        t = jnp.maximum(t, jnp.asarray(h, dt))
        xs.append(x)
        evs.append(ev)
        capped.append(short)
    x_h, ev_h, cap_h = jax.device_get((jnp.stack(xs), jnp.stack(evs),
                                       jnp.stack(capped)))
    return Samples(x=np.asarray(x_h, np.float64),
                   events=np.asarray(ev_h, np.int64),
                   capped=np.asarray(cap_h, bool))
