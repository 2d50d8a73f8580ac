"""The comparison that decides ``correct``, on hand-made readings."""
import math

import numpy as np
import pytest

from bench import check
from bench.reference import Readings


def test_worst_leaf_is_measured_against_the_larger_of_leaf_and_median():
    ref = np.array([1.0, 2.0, 4.0, 1e-6])
    prog = np.array([1.1, 2.0, 4.0, 2e-6])
    # leaf 0: 0.1 / max(1, median 1.5) ; leaf 3: 1e-6 / 1.5, not 1.0
    assert check.worst_leaf(prog, ref) == pytest.approx(0.1 / 1.5)


def test_median_leaf_is_the_median_of_the_same_gaps():
    ref = np.array([1.0, 2.0, 4.0, 1e-6, 3.0])
    prog = np.array([1.1, 2.2, 4.0, 2e-6, 3.0])
    # gaps over max(leaf, median 2): 0.05, 0.1, 0, 5e-7, 0 -> median 5e-7
    assert check.median_leaf(prog, ref) == pytest.approx(5e-7)
    assert check.median_leaf(prog * 1.5, ref) == pytest.approx(0.5)


def test_gaps_leave_out_leaves_with_a_negligible_gradient():
    ref = Readings(losses=[2.0, 3.0, 4.0], grad1=np.array([1.0, 1.0, 1e-9]),
                   dparam=np.array([0.5, 0.5, 1e-3]))
    prog = Readings(losses=[2.0, 3.3, 4.0], grad1=np.array([1.0, 1.0, 0.0]),
                    dparam=np.array([0.5, 0.5, 0.2]))
    g = check.gaps(prog, ref)
    assert g["loss1"] == 0 and g["loss2"] == pytest.approx(0.1)
    assert g["grad1"] == pytest.approx(1e-9)
    assert g["dparam3"] == 0  # the third leaf is left out


def test_a_state_left_unchanged_reads_one():
    ref = Readings(losses=[1.0] * 3, grad1=np.array([1.0, 2.0]),
                   dparam=np.array([0.3, 0.4]))
    prog = Readings(losses=[1.0] * 3, grad1=np.zeros(2), dparam=np.zeros(2))
    g = check.gaps(prog, ref)
    assert g["grad1"] == pytest.approx(1.0) and g["dparam3"] == pytest.approx(1.0)
    # leaves at or above the median read 1, the others their share of it
    assert g["grad1_median"] == pytest.approx((1.0 / 1.5 + 1.0) / 2)
    assert g["dparam3_median"] == pytest.approx((0.3 / 0.35 + 1.0) / 2)


def test_judge_compares_only_numbers_with_a_limit():
    values = {"loss1": 0.5, "loss2": 0.1, "loss3": math.inf, "grad1": 0.01,
              "dparam3": 0.02, "grad1_median": 0.3, "dparam3_median": 0.01}
    limits = {"loss1": None, "loss2": 0.2, "loss3": None, "grad1": 0.05,
              "dparam3": 0.05, "grad1_median": None, "dparam3_median": 0.05}
    ok, checks = check.judge(values, limits)
    assert ok and checks["loss1"] == {"value": 0.5, "limit": None}
    ok, _ = check.judge({**values, "grad1": 0.06}, limits)
    assert not ok
    ok, _ = check.judge({**values, "loss2": math.nan}, limits)
    assert not ok
