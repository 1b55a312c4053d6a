"""Work the dense direct method needs per event, and bytes per window
kernel call, from a network's shapes (reference.Network).

Per event (one lane, one reaction fired), in floating-point operations:

    propensities   every reaction: one multiply by the rate per reactant
                   factor, and 3 (c - 1) more for a factor C(x, c) with
                   c > 1 (subtract, multiply, divide)
    total a0       R - 1 adds
    waiting time   log, divide, add to the clock: 3
    selection      threshold u * a0: 1; prefix sums R - 1; compares R
    update         one add per species the fired reaction changes
                   (mean over reactions)

Bytes per kernel call, for `lanes` lanes: each lane's state read and
written once (S populations, clock, dead flag, two counter words, as
4-byte words, both ways), its two key words read, its step count
written; plus the network's tables once (reactant index and count,
stoichiometry, rates).
"""
import numpy as np


def propensity_flops(net) -> np.ndarray:
    """(R,) operations to compute each reaction's propensity."""
    idx, cnt, _, _, _ = net.arrays()
    per = []
    for row in cnt:
        cs = [int(c) for c in row if c > 0]
        per.append(len(cs) + sum(3 * (c - 1) for c in cs))
    return np.asarray(per, np.float64)


def update_flops(net) -> float:
    _, _, delta, _, _ = net.arrays()
    return float(np.mean(np.count_nonzero(delta, axis=1)))


def flops_per_event(net) -> float:
    r = net.n_reactions
    return float(propensity_flops(net).sum() + (r - 1) + 3
                 + 1 + (r - 1) + r + update_flops(net))


def table_bytes(net) -> int:
    idx, cnt, delta, rates, _ = net.arrays()
    return 4 * (idx.size + cnt.size + delta.size + rates.size)


def bytes_per_call(net, lanes: int) -> int:
    per_lane = 4 * (2 * (net.n_species + 4) + 2 + 1)
    return lanes * per_lane + table_bytes(net)
