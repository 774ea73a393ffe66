"""Finding a cell's pieces by name.  ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; the configuration names its driver and
its generator; each per-layer metric is a file of its own.  Nothing here
lists what exists: a new file plus a new entry is found."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``benchmarks/<kind>/<name>.py`` as a module, by path: a name may hold
    dots and dashes, which an import statement could not spell."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    modname = "benchmarks.%s.%s" % (kind, name.replace(".", "_").replace("-", "_"))
    if modname in sys.modules and getattr(
        sys.modules[modname], "__file__", None
    ) == path:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    """One run of one cell: what the drivers and the readers are handed."""

    name: str
    config_name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: int
    dry_run: bool
    sizes: dict
    bench: dict  # BENCHMARK.json
    bench_dir: str = BENCH_DIR
    # filled as the run goes
    device: dict = dataclasses.field(default_factory=dict)
    peaks: Optional[dict] = None
    window: dict = dataclasses.field(default_factory=dict)
    steps: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)  # window deltas
    hists: dict = dataclasses.field(default_factory=dict)  # window deltas
    compiles_in_window: Optional[int] = None
    memory_peak_bytes: int = 0
    trace_reduced: Optional[dict] = None
    unique_rows_per_step: Optional[float] = None
    planes: int = 1
    #: a driver that runs a model with experts: the held experts' load over
    #: the run's steps (``held_slots_mean``, ``load_max_over_mean_p50``)
    moe: Optional[dict] = None


def resolve(bench: dict, workload: str, *, seed: int, seconds: float,
            trace: int, dry_run: bool, bench_dir: str = BENCH_DIR) -> Run:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}"
        )
    cell = cells[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    root = os.path.dirname(bench_dir)
    config = load_json(os.path.join(root, cfgs[cell["config"]]["file"]))
    traffic = load_json(
        os.path.join(bench_dir, "traffic", f"{cell['traffic']}.json")
    )
    chips = int(cell["chips"])
    topo = config["topology_by_chips"][str(chips)]
    size = dict(
        rows_per_chip=config["rows_per_chip"],
        batch=config["batch_per_worker"],
        cycle=traffic["cycle_batches"],
    )
    if dry_run:  # tiny sizes: finds wrong paths, proves nothing
        size.update(config["dry_run"])
        size.update(traffic.get("dry_run", {}))
    sizes = dict(
        workers=topo["workers"], servers=topo["servers"],
        rows=size["rows_per_chip"] * chips, batch=size["batch"],
        cycle=size["cycle"],
        warmup=size["cycle"] * traffic["warmup_cycles"],
    )
    return Run(
        name=workload, config_name=cell["config"], config=config,
        traffic=traffic, chips=chips, seed=seed, seconds=seconds, trace=trace,
        dry_run=dry_run, sizes=sizes, bench=bench, bench_dir=bench_dir,
    )


def reports(bench: dict, cell: str, metric: str) -> bool:
    """Whether ``cell`` reports the end-to-end metric ``metric``: an entry
    without ``workloads`` is every cell's, one with the key only theirs."""
    return any(
        e["name"] == metric and ("workloads" not in e or cell in e["workloads"])
        for e in bench["end_to_end"]
    )


def layer_metrics_for(run: Run) -> list:
    """The per-layer entries of ``BENCHMARK.json`` this cell reports: those
    that list it under ``workloads``, and of those without the key the ones
    whose ``moves`` is an end-to-end metric the cell reports."""
    return [
        m for m in run.bench["per_layer"]
        if (run.name in m["workloads"] if "workloads" in m
            else reports(run.bench, run.name, m["moves"]))
    ]


def base_reader(path: str):
    """The reader ``<metric>.py`` beside ``<metric>.<split>.py``: a quantity
    split because its cells report different end-to-end metrics (its
    ``moves`` differs) is read by one piece of code."""
    here, name = os.path.split(os.path.abspath(path))
    base = name[: -len(".py")].rsplit(".", 1)[0]
    return load_module(os.path.basename(here), base, os.path.dirname(here))
