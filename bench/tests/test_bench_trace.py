"""The trace reductions on hand-built traces."""
import pytest

from bench import trace as tr


def test_merge_gaps_and_length():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.length(merged) == 6
    assert tr.gaps(merged, 0, 12) == [(3, 5), (8, 12)]
    assert tr.gaps(merged, -2, 6) == [(-2, 0), (3, 5)]
    assert tr.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]


def test_idle_share_from_the_union_of_intervals():
    # two overlapping ops and one apart in a window of 10: busy 6, idle 40 %
    ops = [("fusion.1", 0, 4, ""), ("fusion.2", 2, 5, ""),
           ("convolution.3", 7, 8, "")]
    t = tr.chip_times(ops, 0, 10)
    assert t.busy == 6 and t.compute == 6 and t.collective == 0
    assert 1 - t.busy / 10 == pytest.approx(0.4)
    # the window cuts the ops that cross its edges
    assert tr.chip_times(ops, 3, 7.5).busy == pytest.approx(2.5)


def test_collective_time_and_its_exposed_part():
    # an all-reduce from 4 to 10, a compute op from 2 to 6 overlapping it:
    # 6 of collective time, 2 of it hidden, 4 exposed
    ops = [("fusion.1", 2, 6, ""), ("all-reduce.7", 4, 10, ""),
           ("all-gather-start.2", 12, 13, ""),
           ("fusion.9", 14, 15, "non-fusion elementwise")]
    t = tr.chip_times(ops, 0, 20)
    assert t.collective == 7
    assert t.exposed == 5
    assert t.compute == 5
    assert t.busy == 10


def test_nested_ops_count_once_and_leaves_decide_what_overlaps():
    # a while loop from 0 to 10 holds two body ops and an all-reduce; the
    # all-reduce (6 to 8) overlaps no body op, so all of it is exposed
    ops = [("while.3", 0, 10, ""), ("fusion.1", 0, 4, ""),
           ("convolution.2", 4, 6, ""), ("all-reduce.5", 6, 8, ""),
           ("fusion.4", 12, 13, "")]
    assert [o[0] for o in tr.leaves(ops)] == [
        "fusion.1", "convolution.2", "all-reduce.5", "fusion.4"]
    t = tr.chip_times(ops, 0, 20)
    assert t.busy == 11 and t.collective == 2 and t.exposed == 2
    assert t.compute == 9
    top = tr.top_ops(tr.Trace(ops={0: ops}, spans=[]), 0, 20)
    assert [n for n, _ in top] == ["fusion.1", "convolution.2",
                                   "all-reduce.5", "fusion.4"]


def test_op_names_from_tpu_event_names():
    text = ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), "
            "kind=kLoop")
    assert tr.op_name(text) == "fusion.12"
    assert not tr.is_collective(tr.op_name(text))
    assert tr.op_name("all-gather-start.2") == "all-gather-start.2"


def test_collective_by_category():
    assert tr.is_collective("fusion.12", "all-reduce")
    assert tr.is_collective("reduce-scatter.1")
    assert not tr.is_collective("convolution.4", "convolution")


def test_mean_over_chips_top_ops_and_labelled_gaps():
    t = tr.Trace(
        ops={0: [("a", 0, 4, ""), ("all-gather.1", 4, 6, "")],
             1: [("a", 0, 2, ""), ("b", 3, 6, "")]},
        spans=[("dispatch", 0, 1), ("sync", 1, 8), ("metrics_get", 8, 10)])
    m = tr.mean_chip_times(t, 0, 10)
    assert m.busy == pytest.approx((6 + 5) / 2)
    assert m.collective == pytest.approx(1.0)
    assert tr.top_ops(t, 0, 10)[0] == ["a", pytest.approx(3e-9)]
    # chip 0 is idle from 6 to 10: 2 under sync, 2 under metrics_get
    g = tr.idle_gaps(t, 0, 10)
    assert len(g) == 1 and g[0][1] == pytest.approx(4e-9)
    assert g[0][0] in ("sync", "metrics_get")
    t.spans = [("metrics_get", 7, 10)]
    assert tr.idle_gaps(t, 0, 10)[0][0] == "metrics_get"


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.mean_chip_times(tr.Trace(ops={}, spans=[]), 0, 1)
