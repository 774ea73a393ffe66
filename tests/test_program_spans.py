"""The ``ps.`` spans and scopes of ISSUE 25, where they are made: host spans
read back from a real ``jax.profiler`` trace of a loopback cluster, and the
device scopes read from the lowered table programs.  (How the scopes reach a
TPU trace only a chip run shows: ``benchmarks/harness/program_spans.py``.)"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils import trace as trace_lib
from parameter_server_tpu.utils.trace import NULL_TRACER

#: every span the direct push_sync / pull_sync path takes
PATH_SPANS = {
    "ps.worker.pull", "ps.worker.push", "ps.worker.localize",
    "ps.worker.combine", "ps.worker.submit", "ps.worker.wait",
    "ps.worker.assemble", "ps.van.send", "ps.van.deliver", "ps.server.pull",
    "ps.server.push", "ps.server.h2d", "ps.server.dispatch", "ps.server.d2h",
}
STEPS = 3


def _cfgs():
    return {
        "w": TableConfig(
            name="w", rows=500, dim=2,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=1.0),
        )
    }


def _host_events(logdir):
    """``[(thread line, name, start_ns, end_ns, stats)]`` of the ``ps.``
    events of the one trace under ``logdir``."""
    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ps."):
                    out.append((
                        i, ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats),
                    ))
    return out


def _inside(inner, outer):
    return (
        inner[0] == outer[0]
        and outer[2] <= inner[2]
        and inner[3] <= outer[3]
    )


def test_spans_of_a_loopback_cluster_land_in_the_profilers_trace(tmp_path):
    van = MeteredVan(LoopbackVan())
    try:
        cfgs = _cfgs()
        for i in range(2):
            KVServer(Postoffice(f"S{i}", van), cfgs, i, 2)
        worker = KVWorker(Postoffice("W0", van), cfgs, 2, min_bucket=16)
        keys = np.arange(40, dtype=np.uint64)
        grads = np.ones((40, 2), np.float32)
        worker.push_sync("w", keys, grads, timeout=30)  # compiles outside
        worker.pull_sync("w", keys, timeout=30)
        with jax.profiler.trace(str(tmp_path)):
            for step in range(STEPS):
                # another order of the same keys: a batch the worker has to
                # localize, with the row counts the programs were compiled for
                keys = np.roll(keys, step + 1)
                worker.push_sync("w", keys, grads, timeout=30)
                worker.pull_sync("w", keys, timeout=30)
    finally:
        van.close()
    events = _host_events(str(tmp_path))
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[1], []).append(ev)
    assert PATH_SPANS <= set(by_name), PATH_SPANS - set(by_name)
    assert set(by_name) <= trace_lib.SPANS
    assert all("cpu_us" in ev[4] for ev in events)

    roots = by_name["ps.worker.pull"] + by_name["ps.worker.push"]
    assert len(roots) == 2 * STEPS
    assert all(r[4]["table"] == "w" and r[4]["keys"] == 40 for r in roots)
    # a step's keys are localized once: where the first request computes
    assert len(by_name["ps.worker.localize"]) == STEPS
    assert {r[4]["localize"] for r in by_name["ps.worker.push"]} == {"computed"}
    assert {r[4]["localize"] for r in by_name["ps.worker.pull"]} == {"reused"}
    # one submit a root here (no fence, no defer), nested in it
    submits = {}
    for sub in by_name["ps.worker.submit"]:
        assert sub[4]["legs"] == 2
        assert sub[4]["req"] not in submits
        submits[sub[4]["req"]] = sub
        assert sum(_inside(sub, r) for r in roots) == 1
    assert len(submits) == 2 * STEPS
    # the wait of a request carries its id, after its submit, in its root
    for wait in by_name["ps.worker.wait"]:
        sub = submits[wait[4]["req"]]
        assert wait[2] >= sub[3] and wait[4]["retry"] == 0
    # every server span joins one submit by ``req``, and through it one root
    for kind, op in (("pull", "pull"), ("push", "push")):
        spans = by_name[f"ps.server.{kind}"]
        assert len(spans) == 2 * STEPS  # two servers
        for sp in spans:
            sub = submits[sp[4]["req"]]
            (root,) = [r for r in roots if _inside(sub, r)]
            assert root[1] == f"ps.worker.{kind}"
            assert sp[0] != root[0]  # on a server's recv thread
            assert sp[4]["rows"] <= sp[4]["bucket"]
            if kind == "push":  # ids the apply visits: not the worker's pads
                assert 0 < sp[4]["real"] <= sp[4]["rows"]
            inner = [e for e in events if e is not sp and _inside(e, sp)]
            assert {"ps.server.h2d", "ps.server.dispatch"} <= {
                e[1] for e in inner
            }
            assert [e[4]["op"] for e in inner if e[1] == "ps.server.dispatch"] == [op]
    assert all(sp[4]["members"] == 1 for sp in by_name["ps.server.push"])
    for d2h in by_name["ps.server.d2h"]:
        assert sum(_inside(d2h, p) for p in by_name["ps.server.pull"]) == 1
        assert d2h[4]["bytes"] > 0
    # the van: every delivery says how long its message waited, and a
    # server's handler runs inside the delivery of its request
    for dl in by_name["ps.van.deliver"]:
        assert dl[4]["wait_us"] >= 0
        assert dl[4]["req"] in submits
    for sp in by_name["ps.server.pull"] + by_name["ps.server.push"]:
        (dl,) = [d for d in by_name["ps.van.deliver"] if _inside(sp, d)]
        assert dl[4]["req"] == sp[4]["req"] and dl[4]["is_request"] == 1
        assert dl[4]["verb"] == sp[1].rsplit(".", 1)[1].upper()
    # requests and replies: 2 legs each way a request
    assert len(by_name["ps.van.send"]) == 2 * 2 * 2 * STEPS
    assert all(s[4]["bytes"] > 0 for s in by_name["ps.van.send"])


def test_no_session_no_record():
    assert not TraceAnnotation.is_enabled()
    with NULL_TRACER.span("ps.worker.pull", table="w") as sp:
        sp.set(req="W0/kv/1")
    assert sp is trace_lib._NULL_SPAN
    assert NULL_TRACER.spans() == [] and NULL_TRACER.summary() == {}


FUSED = {"ps.table.apply", "ps.apply.fused", "ps.apply.trash_reset"}
#: (jitted program, ``fused_apply``) -> the scopes its lowering names
TABLE_SCOPES = {
    ("_pull_fn", True): {"ps.table.pull", "ps.gather"},
    ("_push_fn", True): FUSED,
    ("_push_fn", False): {
        "ps.table.apply", "ps.apply.gather", "ps.apply.optimizer",
        "ps.apply.scatter", "ps.apply.trash_reset",
    },
    ("_push_batch_fn", True): FUSED | {"ps.table.stack"},
    ("_push_combined_fn", True): FUSED | {"ps.table.stack"},
}


@pytest.mark.parametrize("fn,fused", sorted(TABLE_SCOPES))
def test_lowered_table_programs_name_their_scopes(fn, fused):
    cfg = dataclasses.replace(_cfgs()["w"], fused_apply=fused)
    table = KVTable(cfg, rows=64)
    ids = jnp.arange(16, dtype=jnp.int32)
    args = {
        "_pull_fn": (ids,),
        "_push_fn": (ids, jnp.ones((16, 2))),
        "_push_batch_fn": (ids, ids, jnp.ones((2, 8, 2))),
        "_push_combined_fn": (ids, ids, jnp.ones((2, 8, 2))),
    }[fn]
    text = getattr(table, fn).lower(
        table.value, table.state, *args
    ).as_text(debug_info=True)
    for scope in TABLE_SCOPES[fn, fused]:
        assert f"/{scope}/" in text, scope
    if fn != "_pull_fn":  # the inner scopes nest in the outer one
        assert "/ps.table.apply/ps.apply.trash_reset/" in text
