"""Which metrics a cell reports.  An end-to-end metric with ``workloads``
is only those cells'; a per-layer metric follows its own ``workloads`` or,
without the key, the end-to-end metric it ``moves``.  ``criteo_lr.skew``
holds only ``step_ms_p95`` (and ``setup_s``) end to end and reports every
other quantity per layer under ``<metric>.p95only`` (``PERF.md``, section
2), each read by the code that reads ``<metric>``.  A cell's per-layer
names are the entries without ``workloads`` (criteo: their twins) and the
entries that list it: a body added with its own entries changes no other
cell's set (PR 39)."""

import copy
import json
import os
import types

import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import host_cpu, model_scopes, program_spans

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


def the_rule(bench, cell):
    """The names a cell reports: the entries without ``workloads`` (in the
    cell that holds only the tail, their ``.p95only`` twins) and the entries
    whose ``workloads`` list it."""
    common = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    if cell == "criteo_lr.skew":
        common = {f"{n}.p95only" for n in common}
    return common | {
        m["name"] for m in bench["per_layer"] if cell in m.get("workloads", ())
    }


def check_cell(bench, cell):
    mine = cell_lib.layer_metrics_for(cell_lib.resolve(
        bench, cell, seed=1, seconds=1.0, trace=1, dry_run=True))
    names = {m["name"] for m in mine}
    assert mine and len(names) == len(mine)
    for m in mine:
        assert cell_lib.reports(bench, cell, m["moves"]), m
    assert names == the_rule(bench, cell)
    assert all(n.endswith(".p95only") == (cell == "criteo_lr.skew") for n in names)
    return names


@pytest.mark.parametrize("cell", CELLS)
def test_every_per_layer_metric_moves_a_metric_its_cell_reports(cell):
    check_cell(BENCH, cell)


def test_a_fourth_body_with_an_entry_of_its_own_changes_no_other_cell():
    """What a new body's PR adds: a configuration, a cell, an entry of its
    own, and its name in the ``workloads`` of the entries it shares; every
    other cell reports what it reported."""
    bench = copy.deepcopy(BENCH)
    bench["configs"].append(dict(
        next(c for c in bench["configs"] if c["name"] == "laguna_xs2"),
        name="body4"))
    bench["workloads"].append({"name": "body4.pretrain8k", "config": "body4",
                               "traffic": "skew", "chips": 1, "why": "test"})
    shared = ("examples_per_s", "step_ms_p50", "mfu_pct", "body_ms_p50")
    for entry in bench["end_to_end"] + bench["per_layer"]:
        if entry["name"] in shared and "workloads" in entry:
            entry["workloads"] = entry["workloads"] + ["body4.pretrain8k"]
    bench["per_layer"].append({
        "name": "bd_attn_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model kernels",
        "moves": "step_ms_p50", "workloads": ["body4.pretrain8k"]})
    for cell in CELLS:
        assert check_cell(bench, cell) == check_cell(BENCH, cell)
    mine = check_cell(bench, "body4.pretrain8k")
    assert {"bd_attn_roofline", "mfu_pct", "body_ms_p50"} <= mine
    assert "full_attn_roofline" not in mine


def test_a_fourth_body_is_read_through_its_driver_with_no_edit(tmp_path, monkeypatch):
    """The one body reader finds a new body's scopes, kernels and counts
    through the driver its configuration names, and a reader file of the
    four lines every body reader has reads it."""
    (tmp_path / "drivers").mkdir()
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "drivers" / "body4.py").write_text(
        "import types\n"
        "body = types.SimpleNamespace(\n"
        "    ROOT_SCOPE='ps.model.body4', PER_LAYER={'bd_attn': 'bd'},\n"
        "    KERNELS={'bd_attn': ('ps.model.bd.attn',)},\n"
        "    layer_kinds=lambda cfg: [('bd', 'dense'), ('bd', 'dense')],\n"
        "    work=lambda cfg, sequences, seq_len, held_slots=None: {\n"
        "        'bd_attn': {'flops': 19.7e12, 'bytes': 8.19e9}},\n"
        "    step_flops=lambda cfg, sequences, seq_len: 39.4e12)\n"
    )
    (tmp_path / "layer_metrics" / "bd_attn_roofline.py").write_text(
        open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                          "full_attn_roofline.py")).read()
        .replace('"full_attn_roofline"', '"bd_attn_roofline"')
    )
    acc = types.SimpleNamespace(
        path="body4", window=(0.0, 2.0), window_s=2.0,
        durations_ms=lambda n: [],
        scope_s={"ps.model.body4": 1.9, "ps.model.bd.attn": 0.8},
    )
    monkeypatch.setattr(program_spans, "for_run", lambda run: acc)
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [])
    run = types.SimpleNamespace(
        config={"driver": "body4", "generator_params": {"sequences": 2}},
        sizes={"batch": 16384}, peaks={"flops": 197e12, "hbm_bytes_per_s": 819e9},
        moe=None, window={"examples_per_s": 8192.0}, bench_dir=str(tmp_path),
        steps=[types.SimpleNamespace(start=0.5 * i, end=0.5 * (i + 1), ok=True)
               for i in range(10)],
    )
    reader = cell_lib.load_module("layer_metrics", "bd_attn_roofline", str(tmp_path))
    # 0.1 s of operations over the scope's 0.2 s a step (4 steps traced)
    assert reader.read(run) == pytest.approx(50.0)
    got = model_scopes.for_run(run)
    assert got["bd_attn_ms"] == pytest.approx(100.0)  # 200 ms over 2 layers
    assert got["mfu_pct"] == pytest.approx(10.0)  # 39.4 TFLOP x 0.5 a second
    assert reader.check(100.5) and not reader.check(99.9)


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


#: the readings of PR 39's entries: the model body's and PR 37's nine
BODY = sorted(
    m["name"] for m in BENCH["per_layer"]
    if m["layer"] in ("model body", "model kernels")
)
HOST = sorted(
    m["name"] for m in BENCH["per_layer"]
    if not m["name"].endswith(".p95only")
    and m["name"] in host_cpu.METRICS
)


def _account_without_scopes():
    """A window with one span that no reader reads: no server, no worker
    step, no turn, no ``ps.model.*`` scope."""
    send = program_spans.Span("ps.van.send", "W", 0.01, 0.02, {"cpu_us": 10})
    return program_spans.Account(
        "without scopes", (0.0, 0.1), 1, [send], {"ps.van.send": [send]},
        {}, {}, 0.0, {}, {}, {}, {},
    )


@pytest.mark.parametrize("cell", ["criteo_lr.skew", "laguna_xs2.pretrain8k"])
@pytest.mark.parametrize("name", BODY + HOST)
def test_a_new_reader_reads_nothing_in_a_cell_without_its_scopes(
        name, cell, monkeypatch):
    acc = _account_without_scopes()
    monkeypatch.setattr(program_spans, "for_run", lambda run: acc)
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [])
    run = run_of(cell)
    run.peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    run.steps = [types.SimpleNamespace(start=0.5 * i, end=0.5 * (i + 1), ok=True)
                 for i in range(10)]
    run.window = {"examples_per_s": 14000.0}
    reader = cell_lib.load_module("layer_metrics", name)
    model_scopes._CACHE.clear()
    if name == "mfu_pct" and cell != "criteo_lr.skew":
        # the whole step's share needs no scope: the body and the window's rate
        assert reader.read(run) > 0
        run.window = {}
        model_scopes._CACHE.clear()
    assert reader.read(run) is None


@pytest.mark.parametrize("name", [n for n in BODY if n.endswith(("_roofline", "mfu_pct"))])
def test_a_share_of_a_peak_over_100_fails_the_run(name):
    check = cell_lib.load_module("layer_metrics", name).check
    assert check(100.0) == [] and check(12.1) == []
    (fail,) = check(100.01)
    assert name in fail and "above 100" in fail


@pytest.mark.parametrize("command", ["model_scopes", "lfm2_scopes", "laguna_scopes"])
def test_the_three_commands_are_the_one_reader(command, tmp_path, capsys):
    """Each body's command is ``model_scopes.main``: on a traced run's
    series, trace and ``.moe.json`` it prints the readings as one JSON
    object (here a stored trace of a program without a body's scopes: only
    what the series and the counts give)."""
    mod = __import__(f"benchmarks.harness.{command}", fromlist=["main"])
    assert mod.main is model_scopes.main
    series = tmp_path / "series.json"
    series.write_text(json.dumps({
        "cell": "laguna_xs2.pretrain8k",
        "steps": [[0, i, 1.2 * i, 1.2 * (i + 1), True, []] for i in range(10)],
        "window": {"examples_per_s": 16384 / 1.2},
        "result": {"device": {"kind": "TPU v5 lite"}},
    }))
    moe = tmp_path / "x.moe.json"
    moe.write_text(json.dumps({"held_slots_mean": 43000.0,
                               "load_max_over_mean_p50": 16.4}))
    trace = os.path.join(ROOT, "benchmarks", "tests", "data", "program.xplane.pb")
    assert model_scopes.main([str(series), trace, str(moe)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["moe_load_max_over_mean"] == 16.4
    assert 15 < out["mfu_pct"] < 20 and "full_attn_ms" not in out
