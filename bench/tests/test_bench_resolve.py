"""The harness finds every cell, configuration, mix, limit and metric that
BENCHMARK.json names, by name, and fails on a missing one."""
import copy
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.resolve(workload)
    assert cell.chips in (1, 4)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for entry, reader in cell.per_layer:
        assert callable(reader.read), entry["name"]
    assert cell.ref.INPUT in ("images", "tokens")
    assert cell.mix["global_batch"] % (cell.chips * cell.mix["microbatches"]) == 0


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        sizes = json.loads((ROOT / c["file"]).read_text())
        assert sizes["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m and m["workloads"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(BENCH["workloads"]) == len(cells)


def test_an_unknown_cell_fails():
    with pytest.raises(KeyError, match="no workload"):
        harness.resolve("no_such_model.no_such_mix")


@pytest.mark.parametrize("missing", ["traffic", "limits", "metric", "config"])
def test_a_missing_file_fails(tmp_path, missing):
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = copy.deepcopy(BENCH)
    w = bench["workloads"][0]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    if missing == "traffic":
        (root / "traffic" / f"{w['traffic']}.json").unlink()
    elif missing == "limits":
        (root / "limits" / f"{w['name']}.json").unlink()
    elif missing == "metric":
        (root / "metrics" / "idle_share.py").unlink()
    else:
        (tmp_path / cfg["file"]).with_suffix(".py").unlink()
    with pytest.raises(FileNotFoundError):
        harness.resolve(w["name"], bench=bench, root=root)
