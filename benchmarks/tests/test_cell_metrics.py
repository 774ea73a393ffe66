"""Which metrics a cell reports.  An end-to-end metric with ``workloads``
is only those cells'; a per-layer metric follows its own ``workloads`` or,
without the key, the end-to-end metric it ``moves``.  ``criteo_lr.skew``
holds only ``step_ms_p95`` (and ``setup_s``) end to end and reports every
other quantity per layer under ``<metric>.p95only`` (``PERF.md``, section
2), each read by the code that reads ``<metric>``."""

import json
import os

import pytest

from benchmarks.harness import cell as cell_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
SPLIT = sorted(
    m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".p95only")
)
WINDOW = ["examples_per_s.p95only", "step_ms_p50.p95only"]


def run_of(cell):
    return cell_lib.resolve(BENCH, cell, seed=1, seconds=1.0, trace=1,
                            dry_run=True)


def test_the_unsteady_cell_holds_only_the_tail_end_to_end():
    held = {
        cell: [e["name"] for e in BENCH["end_to_end"]
               if cell_lib.reports(BENCH, cell, e["name"])]
        for cell in CELLS
    }
    assert held["criteo_lr.skew"] == ["step_ms_p95", "setup_s"]
    assert held["dlrm_emb.skew.x4"] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]


@pytest.mark.parametrize("cell", CELLS)
def test_every_per_layer_metric_moves_a_metric_its_cell_reports(cell):
    mine = cell_lib.layer_metrics_for(run_of(cell))
    assert mine and len({m["name"] for m in mine}) == len(mine)
    for m in mine:
        assert cell_lib.reports(BENCH, cell, m["moves"]), m
    # the same 22 quantities in both cells, under the cell's own names
    split = cell == "criteo_lr.skew"
    names = {m["name"] for m in mine}
    assert all(n.endswith(".p95only") == split for n in names)
    assert {n.removesuffix(".p95only") for n in names} - {
        "examples_per_s", "step_ms_p50"
    } == {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}


@pytest.mark.parametrize("name", [n for n in SPLIT if n not in WINDOW])
def test_a_split_metric_is_read_by_its_base(name):
    base = cell_lib.load_module("layer_metrics", name.removesuffix(".p95only"))
    mod = cell_lib.load_module("layer_metrics", name)
    assert mod.read is base.read
    assert mod.check is getattr(base, "check", None)
    entry = {m["name"]: m for m in BENCH["per_layer"]}
    mine, its = entry[name], entry[base.NAME]
    assert mine["workloads"] == ["criteo_lr.skew"]
    assert mine["moves"] == "step_ms_p95"
    assert [mine[k] for k in ("unit", "better", "source", "layer")] == [
        its[k] for k in ("unit", "better", "source", "layer")
    ]


@pytest.mark.parametrize("name", WINDOW)
def test_a_metric_taken_from_the_bounds_is_the_window_s_own_number(name):
    run = run_of("criteo_lr.skew")
    mod = cell_lib.load_module("layer_metrics", name)
    assert mod.read(run) is None  # no window yet: nothing to read
    run.window = {"examples_per_s": 154029.5, "step_ms_p50": 209.4}
    assert mod.read(run) == run.window[name.removesuffix(".p95only")]
