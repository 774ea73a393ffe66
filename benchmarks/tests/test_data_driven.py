"""The harness is driven by data: a configuration, a traffic mix, a driver,
a generator, per-layer metrics and a cell are added to a temporary copy as
NEW files plus NEW entries of ``BENCHMARK.json``, and the cell runs (dry, on
the CPU) with no edit to any file that was there.  The same copy shows that
a metric's ``check`` decides ``correct``, and that a table localised by
identity (a dense vocabulary: the hybrid path's embedding) is such an
addition: the configurations that are there plus ``"localizer":
"identity"``, under other names."""

import hashlib
import json
import os
import re
import shutil

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def digest(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        if os.sep + "out" in d or "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), tmp / "benchmarks",
        ignore=shutil.ignore_patterns("out", "__pycache__"),
    )
    before = digest(tmp / "benchmarks")
    bdir = tmp / "benchmarks"

    cfg = json.load(open(bdir / "configs" / "criteo_lr.json"))
    cfg.update(name="tiny_lr", driver="lr_again", generator="ctr_again",
               source="a test's own deployment")
    (bdir / "configs" / "tiny_lr.json").write_text(json.dumps(cfg))
    (bdir / "traffic" / "uniform.json").write_text(json.dumps({
        "name": "uniform", "loop": "closed", "key_dist": "uniform",
        "pool_seed": 7, "cycle_batches": 32, "warmup_cycles": 1,
        "dry_run": {"cycle": 3},
    }))
    (bdir / "drivers" / "lr_again.py").write_text(
        "from benchmarks.harness.cell import load_module\n"
        "import os\n"
        "_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))\n"
        "Driver = load_module('drivers', 'lr_elastic', _HERE).Driver\n"
    )
    (bdir / "generators" / "ctr_again.py").write_text(
        "from benchmarks.harness.cell import load_module\n"
        "import os\n"
        "_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))\n"
        "_ctr = load_module('generators', 'ctr', _HERE)\n"
        "make, keys_of = _ctr.make, _ctr.keys_of\n"
    )
    for name, base in IDENTITY.items():
        cfg = json.load(open(bdir / "configs" / f"{base}.json"))
        cfg.update(name=name, source="a test's own deployment")
        cfg["table"]["localizer"] = "identity"
        (bdir / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (bdir / "layer_metrics" / "unique_rows.py").write_text(
        "def read(run):\n    return run.unique_rows_per_step\n"
    )
    (bdir / "layer_metrics" / "steps.in-window.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n"
    )
    (bdir / "layer_metrics" / "nothing_to_read.py").write_text(
        "def read(run):\n    return None\n"
    )
    (bdir / "layer_metrics" / "fake_roofline.py").write_text(
        "def read(run):\n    return 296.0\n\n"
        "def check(v):\n    return ['above 100'] if v > 100 else []\n"
    )
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({
        "name": "tiny_lr", "source": "a test", "reduced": [], "why": "test",
        "file": "benchmarks/configs/tiny_lr.json"})
    for name in IDENTITY:
        bench["configs"].append({
            "name": name, "source": "a test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    for cell in ("tiny_lr.uniform", "tiny_lr.over", "dlrm_emb.uniform",
                 *(f"{name}.uniform" for name in IDENTITY)):
        bench["workloads"].append({
            "name": cell, "config": cell.split(".")[0], "traffic": "uniform",
            "chips": 1, "why": "test"})
    # an end-to-end metric that only some cells hold lists them: a new cell
    # that reports it adds its name there
    new_cells = [w["name"] for w in bench["workloads"][-5:]]
    for entry in bench["end_to_end"]:
        if "workloads" in entry:
            entry["workloads"] = entry["workloads"] + new_cells
    for name, cells in (("steps.in-window", ["tiny_lr.uniform"]),
                        ("nothing_to_read", ["tiny_lr.uniform"]),
                        ("fake_roofline", ["tiny_lr.over"]),
                        ("unique_rows", [f"{n}.uniform" for n in IDENTITY])):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "worker step",
            "moves": "examples_per_s", "workloads": cells})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, before


#: new configuration -> the one it copies, but for ``table.localizer``
IDENTITY = {"ident_lr": "criteo_lr", "ident_emb": "dlrm_emb"}
SEED = 2147483999


def run_cell(tmp, cell, capsys, stderr=False):
    from benchmarks import run as run_py

    rc = run_py.main(
        ["--workload", cell, "--dry-run", "--seconds", "0.5", "--trace", "1",
         "--seed", str(SEED)],
        bench_dir=str(tmp / "benchmarks"),
    )
    assert rc == 0
    captured = capsys.readouterr()
    out = json.loads(captured.out.strip().splitlines()[-1])
    return (out, captured.err) if stderr else out


def test_new_files_and_entries_are_found_and_run(copy, capsys):
    tmp, before = copy
    out = run_cell(tmp, "tiny_lr.uniform", capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    m = out["metrics"]
    assert m["steps.in-window"]["value"] >= out["attempted"]
    assert "nothing_to_read" not in m and "fake_roofline" not in m
    # the cell reports the shared metrics too, and no compile in its window
    assert m["compiles_in_window"]["value"] == 0 and "pull_ms_p50" in m
    after = digest(tmp / "benchmarks")
    assert {k: after[k] for k in before} == before  # nothing there was edited
    assert len(after) == len(before) + 10  # the files written above


def test_a_metric_s_check_decides_correct(copy, capsys):
    tmp, _ = copy
    out = run_cell(tmp, "tiny_lr.over", capsys)
    assert out["metrics"]["fake_roofline"]["value"] == 296.0
    assert out["correct"] is False


def test_the_measured_dlrm_step_agrees_with_numpy_where_products_are_float32(
    copy, capsys
):
    """On the CPU jax's default matrix product is float32, so the step the
    window runs has to agree with the plain reference to rounding: the
    formula is held here, the chip's precision by the configuration's
    ``grad_check`` limits.  The cell is one more entry over files that are
    there (``dlrm_emb`` x the copy's ``uniform``)."""
    tmp, _ = copy
    out, err = run_cell(tmp, "dlrm_emb.uniform", capsys, stderr=True)
    assert out["correct"] is True
    line = [ln for ln in err.splitlines() if ln.startswith("[grad_check] ")][-1]
    info = json.loads(line[len("[grad_check] "):])
    assert info["worst"] < 1e-5 and info["loss"] < 1e-6


@pytest.mark.parametrize("config", sorted(IDENTITY))
def test_an_identity_localised_table_is_new_files_and_entries(
    copy, capsys, config
):
    """The generators emit keys in ``[0, key_space)`` and both drivers step
    the workers the cluster hands them, so the configuration's one key is
    the whole addition.  The reference check draws rows of the table and
    agrees with NumPy AdaGrad; the byte model counts a row a distinct key
    (hashed into as few rows, some keys would share one)."""
    tmp, before = copy
    out, err = run_cell(tmp, f"{config}.uniform", capsys, stderr=True)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    info = json.loads(re.search(
        r"^\[setup\] reference checks at .*?: (\{.*?\}) \[\]$", err, re.M
    ).group(1))
    assert info["localizer"] == "identity" and info["rows"] == 3000
    assert 0 < info["max_abs_err"] < 1e-5

    from benchmarks.harness import cell as cell_lib

    bdir = str(tmp / "benchmarks")
    run = cell_lib.resolve(
        json.load(open(tmp / "BENCHMARK.json")), f"{config}.uniform",
        seed=SEED, seconds=0.5, trace=1, dry_run=True, bench_dir=bdir,
    )
    gen = cell_lib.load_module("generators", run.config["generator"], bdir)
    sz = run.sizes
    batches = gen.make(
        dict(run.config["generator_params"], key_space=sz["rows"]),
        run.traffic, seed=SEED, n_workers=sz["workers"], cycle=sz["cycle"],
        batch=sz["batch"],
    )
    distinct = [np.unique(gen.keys_of(b)).size for b in batches[0]]
    assert out["metrics"]["unique_rows"]["value"] == float(np.mean(distinct))
    after = digest(tmp / "benchmarks")
    assert {k: after[k] for k in before} == before  # nothing there was edited


def test_an_unknown_localizer_stops_the_run_with_the_file_s_name(copy, capsys):
    tmp, _ = copy
    bad = tmp / "benchmarks" / "configs" / "ident_lr.json"
    good = bad.read_text()
    cfg = json.loads(good)
    cfg["table"]["localizer"] = "modulo"
    bad.write_text(json.dumps(cfg))
    try:
        with pytest.raises(
            ValueError, match=r"benchmarks/configs/ident_lr\.json.*'modulo'"
        ):
            run_cell(tmp, "ident_lr.uniform", capsys)
    finally:
        bad.write_text(good)
