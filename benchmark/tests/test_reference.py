"""The plain reference on cases worked by hand, and the controls: each has
to come out as not correct through the cell's own comparison."""

import numpy as np
import pytest

from benchmark import control, run
from benchmark.reference import divide


def _one(replicas, avail, prev=None, fresh=False, cand=None):
    c = len(avail)
    out, uns = divide.divide_dynamic(
        np.array([replicas]), np.array([cand or [True] * c]),
        np.array([avail]), np.array([prev or [0] * c]), np.array([fresh]))
    return out[0].tolist(), bool(uns[0])


def test_first_placement_is_largest_remainder():
    # 10 over weights 5,3,2 -> exact 5,3,2
    assert _one(10, [5, 3, 2]) == ([5, 3, 2], False)
    # 4 over 3,3,3: floors 1,1,1, the one left goes to the lowest index
    assert _one(4, [3, 3, 3]) == ([2, 1, 1], False)


def test_scale_up_keeps_previous_and_dispenses_the_delta():
    # held 2+1, asked 5: the 2 new go by availability 1,9,0
    assert _one(5, [1, 9, 0], prev=[2, 1, 0]) == ([2, 3, 0], False)


def test_scale_down_divides_over_the_full_previous_result():
    # held 4+2 (second no longer a candidate), asked 3: weights 4,2
    assert _one(3, [9, 9, 9], prev=[4, 2, 0],
                cand=[True, False, True]) == ([2, 1, 0], False)


def test_fresh_credits_what_is_held():
    # avail 1,2 + held 3,0 -> weights 4,2; 3 replicas -> 2,1
    assert _one(3, [1, 2], prev=[3, 0], fresh=True) == ([2, 1], False)


def test_not_enough_capacity_is_unschedulable():
    assert _one(5, [1, 1]) == ([0, 0], True)


def test_estimate_and_merge():
    cap = np.array([[4000, 8 << 30, 10], [-5, 8 << 30, 10]])
    req = np.array([[1000, 1 << 30, 1], [0, 0, 0]])
    est = divide.estimate(cap, req)
    assert est.tolist() == [[4, 0], [divide.MAX_INT32] * 2]
    assert divide.merge(np.array([7, 7]), est).tolist() == [[4, 0], [7, 7]]
    est = divide.estimate(cap, req, np.array([True, False]))
    assert divide.merge(np.array([3, 3]), est)[0].tolist() == [4, 3]


@pytest.mark.parametrize("cell", [
    "rebalance-100kx100.drift", "fed-100c.rebalance"])
def test_control_comes_out_not_correct(cell):
    for seed in (2147483659, 17, 400000000123):
        checks = control.control_checks(cell, seed, waves=13, rehearse=True)
        checks.pop("_failed", None)
        assert not run.verdict(checks), (cell, seed, checks)
