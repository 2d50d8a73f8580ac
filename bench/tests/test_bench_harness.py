"""A whole run of each cell at a test's size on the CPU, past the look for
a chip: the first steps through the timed step, the window, the trace and
the check against the plain reference."""
import time

import pytest

from bench import harness, peaks
from bench.tests import tiny

CELLS = ["resnet50.b256.4chip", "internlm2_1_8b_3l.s4k.1chip"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(workload):
    cell = tiny.cell(workload, chips=1)
    r = harness.run(cell, 2**31 + 7, 0.5, False, time.perf_counter(),
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 3
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(r)[-1] == "checks"
    assert r["checks"]["compilations_in_window"]["value"] == 0


def test_a_traced_run_reports_the_per_layer_metrics(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = tiny.cell("internlm2_1_8b_3l.s4k.1chip")
    r = harness.run(cell, 5, 0.5, True, time.perf_counter(),
                    log=lambda m: None)
    assert r["correct"], r["checks"]
    names = {e["name"] for e, _ in cell.per_layer}
    assert set(r["metrics"]) <= names
    assert {"compile_s", "input_wait_ms", "idle_share", "mfu"} <= set(
        r["metrics"])
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert 0 <= r["metrics"]["idle_share"]["value"] < 100
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_the_same_seed_gives_the_same_inputs_and_weights():
    from bench import traffic

    cell = tiny.cell("internlm2_1_8b_3l.s4k.1chip")
    a = traffic.make_ring("tokens", cell.sizes, cell.mix, 2**31 + 99)
    b = traffic.make_ring("tokens", cell.sizes, cell.mix, 2**31 + 99)
    c = traffic.make_ring("tokens", cell.sizes, cell.mix, 2**31 + 98)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert not (a[0]["tokens"] == c[0]["tokens"]).all()
    # the rows of the checked steps all differ
    rows = {r.tobytes() for x in a[:3] for r in x["tokens"]}
    assert len(rows) == 3 * cell.mix["global_batch"]
