"""Each work/ count against a count made by hand."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit import spec  # noqa: E402

LV2 = {"n_species": 2, "k_reproduce": 1.0, "k_eat": 0.005, "k_die": 0.6,
       "prey0": 1000, "pred0": 1000}


def net(config: str, args: dict):
    return spec.load_module("configs", config).network(args)


def test_dense_lv2_by_hand():
    w = spec.load_module("work", "ssa_dense")
    # propensities: reproduce k*s0 (1), eat k*s0*s1 (2), die k*s1 (1);
    # a0 2 adds; waiting time 3; threshold 1; prefix 2; compares 3;
    # update: reproduce 1, eat 2, die 1 species -> mean 4/3
    assert w.flops_per_event(net("lotka77", LV2)) == pytest.approx(
        4 + 2 + 3 + 1 + 2 + 3 + 4 / 3)
    # per lane: 2 populations + t, dead, ctr, ctr_hi both ways (12
    # words), key read (2), steps written (1) = 15 words = 60 bytes;
    # tables: index 3x2, count 3x2, delta 3x2, rates 3 = 21 words
    assert w.bytes_per_call(net("lotka77", LV2), 100) == 100 * 60 + 84


def test_dense_lv3_chain_by_hand():
    w = spec.load_module("work", "ssa_dense")
    n = net("lotka77", dict(LV2, n_species=3))
    # propensities: reproduce 1, eat1 (s0*s1) 2, eat2 (s1*s2) 2, die 1;
    # a0 3 adds; waiting time 3; threshold 1; prefix 3; compares 4;
    # update: reproduce 1, eat1 2, eat2 2, die 1 species -> mean 6/4
    assert w.flops_per_event(n) == pytest.approx(
        6 + 3 + 3 + 1 + 3 + 4 + 6 / 4)
    # per lane: 3 populations + t, dead, ctr, ctr_hi both ways (14
    # words), key read (2), steps written (1) = 17 words = 68 bytes;
    # tables: index 4x2, count 4x2, delta 4x3, rates 4 = 32 words
    assert w.bytes_per_call(n, 100) == 100 * 68 + 128
