"""Plain reference network of `lotka77`: Gillespie's Lotka reactions
(J. Phys. Chem. 81:2340, 1977) as an n-species chain, with n = 2 the
source's prey/predator pair.

    s0            -> 2 s0      k_reproduce   (X-bar + Y1 -> 2 Y1, X-bar fixed)
    s(i-1) + s(i) -> 2 s(i)    k_eat         (Y1 + Y2 -> 2 Y2)
    s(n-1)        -> 0         k_die         (Y2 -> Z)

Species carry the simulator's labels (`⊤/s0`, ...) so that its columns
can be matched by name; every species is an observable. Middle species
of a longer chain start at 100.
"""
from benchkit.reference import Network


def network(args: dict) -> Network:
    n = int(args["n_species"])
    names = [f"⊤/s{i}" for i in range(n)]
    reactions = [({names[0]: 1}, {names[0]: 2}, float(args["k_reproduce"]))]
    for i in range(1, n):
        reactions.append(({names[i - 1]: 1, names[i]: 1}, {names[i]: 2},
                          float(args["k_eat"])))
    reactions.append(({names[-1]: 1}, {}, float(args["k_die"])))
    x0 = {names[0]: int(args["prey0"]), names[-1]: int(args["pred0"])}
    for s in names[1:-1]:
        x0[s] = 100
    return Network(species=tuple(names), reactions=tuple(reactions), x0=x0,
                   observables={s: [s] for s in names})
