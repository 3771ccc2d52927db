"""The client_eval work function against a hand count at the paper's
shapes, and the peaks table."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import roofline  # noqa: E402

K, W = 22, 5        # the paper's pool and clients per round


def test_eflfg_lane_round_by_hand():
    # eq.-(5) softmax over K: max, sub, exp, sum, div = 5K = 110
    # losses: (p - y), square, / 4, min 1, sum over W = 5KW = 550
    # the mixture's matvec: 2KW = 220
    # the ensemble: (yhat - y), square, sum, / n, / 4, min = 6W + 1 = 31
    ops, nbytes = roofline.client_eval_work(K, W, with_grad=False)
    assert ops == 110 + 550 + 220 + 31
    # in: the (K, W) tile 110, W targets, K weights, K flags, 2 scalars;
    # out: the K mixture, 2 ensemble scalars, K losses; 4 bytes each
    assert nbytes == 4 * (110 + 5 + 22 + 22 + 2) + 4 * (22 + 2 + 22)


def test_fedboost_lane_round_by_hand():
    # linear mixing w / sum(w): 2K = 44; the gradient: residual W,
    # product and sum 2KW, scale K = 5 + 220 + 22
    ops, nbytes = roofline.client_eval_work(K, W, with_grad=True)
    assert ops == 44 + 550 + 220 + 31 + 5 + 220 + 22
    assert nbytes == 4 * (110 + 5 + 22 + 22 + 2) + 4 * (22 + 2 + 22 + 22)


def test_the_bytes_bound_a_lane_round_on_a_v5e():
    peak = roofline.peaks("TPU v5 lite")
    ops, nbytes = roofline.client_eval_work(K, W, with_grad=False)
    t, bound = roofline.least_time_s(ops, nbytes, peak)
    assert bound == "bytes"
    assert t == pytest.approx(828 / 819e9)


def test_a_device_not_in_the_table_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9000")
