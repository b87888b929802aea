"""The recount's arithmetic against naive versions."""

import numpy as np

from lib import recount, shapes, trace_reduce


def test_weighted_order_stat_equals_expansion():
    rng = np.random.default_rng(5)
    svc = rng.integers(0, 7, 400).astype(np.uint64)
    val = rng.lognormal(3.0, 1.0, 400)
    w = rng.integers(1, 6, 400)
    for q in (0.5, 0.95, 0.99):
        uq, n, hi, lo = recount.weighted_order_stat(svc, val, w, q)
        for i, s in enumerate(uq):
            m = svc == s
            full = np.sort(np.repeat(val[m], w[m]))
            assert n[i] == len(full)
            r = int(np.clip(np.ceil(q * len(full) - 1e-4), 1, len(full)))
            assert hi[i] == full[r - 1]
            assert lo[i] >= hi[i]


def test_numbers_decide_correct():
    num = recount.Numbers()
    assert not num.correct                     # nothing compared: not correct
    num.add("a", 0, 0)
    num.add("b", 0.03, 0.0367)
    assert num.correct
    num.add("c", float("nan"), 1.0)
    assert not num.correct
    assert num.table()["b"] == [0.03, 0.0367]


def test_union_of_intervals():
    s = np.array([0.0, 1.0, 5.0, 5.5, 9.0])
    e = np.array([2.0, 1.5, 6.0, 7.0, 9.5])
    us, ue = trace_reduce._union(s, e)
    assert us.tolist() == [0.0, 5.0, 9.0] and ue.tolist() == [2.0, 7.0, 9.5]


def test_fold_needs_scale_with_lanes():
    eng = {"cms_depth": 2}
    a = shapes.fold_needs(eng, 32768, 65536)
    b = shapes.fold_needs(eng, 16384, 32768)
    assert a["bytes"] == 2 * b["bytes"] and a["bytes"] > 0
    least, bound = shapes.least_seconds(
        a, {"hbm_gb_per_s": 819.0, "bf16_tflop_per_s": 197.0})
    assert bound == "memory" and 1e-6 < least < 1e-4
