"""The stages of a server request as the profiler's trace holds them (ISSUE
37): ``ps.server.localize``, ``.h2d``, ``.dispatch``, (``.d2h``,) ``.ack``
in that order, on the single path (a loopback cluster's recv threads) and on
the bundle path (``handle_request_batch``, where a member is localized
before its group is known, so the localization precedes the span)."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils import trace as trace_lib

ROWS, DIM, STEPS = 500, 2, 2
STAGES = {
    "ps.server.localize", "ps.server.h2d", "ps.server.dispatch",
    "ps.server.d2h", "ps.server.ack",
}


def _cfgs():
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=DIM,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=1.0),
        )
    }


def _member(kind, ids, ts, vals=None):
    """A request as a bundle's frame hands it over, from a sender no worker
    of the cluster has (its spans are told apart by ``req``)."""
    return Message(
        task=Task(kind, "kv", time=ts, payload={"table": "w"}),
        sender="W9", recver="S0", keys=np.asarray(ids, dtype=np.int32),
        values=[] if vals is None else [np.asarray(vals, np.float32)],
    )


def _bundle(ts):
    """[push a, push b, pull, push c]: a group of two, the pull that
    flushes it, a group of one flushed at the end, the deferred read-back.
    The last id of ``a`` is a pad (the table's global row count)."""
    a, b, c = [1, 2, 3, ROWS], [2, 5], [7]
    return [
        _member(TaskKind.PUSH, a, ts, np.ones((4, DIM))),
        _member(TaskKind.PUSH, b, ts + 1, np.ones((2, DIM))),
        _member(TaskKind.PULL, [1, 2, 5], ts + 2),
        _member(TaskKind.PUSH, c, ts + 3, np.ones((1, DIM))),
    ]


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    """``[(thread, name, start_ns, end_ns, stats)]`` of the ``ps.`` events
    of one profiler session over both paths, sorted by start."""
    logdir = str(tmp_path_factory.mktemp("trace"))
    van = MeteredVan(LoopbackVan())
    try:
        cfgs = _cfgs()
        servers = [KVServer(Postoffice(f"S{i}", van), cfgs, i, 2) for i in range(2)]
        worker = KVWorker(Postoffice("W0", van), cfgs, 2, min_bucket=16)
        keys = np.arange(40, dtype=np.uint64)
        grads = np.ones((40, DIM), np.float32)
        worker.push_sync("w", keys, grads, timeout=30)  # compiles outside
        worker.pull_sync("w", keys, timeout=30)
        replies = servers[0].handle_request_batch(_bundle(100))
        assert not any("__error__" in r.task.payload for r in replies)
        with jax.profiler.trace(logdir):
            for _ in range(STEPS):
                worker.push_sync("w", keys, grads, timeout=30)
                worker.pull_sync("w", keys, timeout=30)
            replies = servers[0].handle_request_batch(_bundle(200))
        assert not any("__error__" in r.task.payload for r in replies)
    finally:
        van.close()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("ps."):
                    out.append((
                        (plane.name, i), ev.name, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats),
                    ))
    return sorted(out, key=lambda e: (e[2], -e[3]))


def _held(events, outer):
    """The events ``outer`` holds on its thread, in order."""
    return [
        e for e in events
        if e is not outer and e[0] == outer[0]
        and outer[2] <= e[2] and e[3] <= outer[3]
    ]


def _of(events, name, sender):
    return [
        e for e in events
        if e[1] == name and str(e[4].get("req", "")).startswith(sender + "/")
    ]


def test_every_stage_is_a_registered_span(events):
    names = {e[1] for e in events}
    assert STAGES <= names and names <= trace_lib.SPANS
    assert all("cpu_us" in e[4] for e in events)


@pytest.mark.parametrize("kind,stages", [
    ("push", ["ps.server.localize", "ps.server.h2d", "ps.server.dispatch",
              "ps.server.ack"]),
    ("pull", ["ps.server.localize", "ps.server.h2d", "ps.server.dispatch",
              "ps.server.d2h", "ps.server.ack"]),
])
def test_a_single_request_nests_its_stages_in_order(events, kind, stages):
    spans = _of(events, f"ps.server.{kind}", "W0")
    assert len(spans) == 2 * STEPS  # two servers
    legs = {}
    for sp in spans:
        held = _held(events, sp)
        assert [e[1] for e in held] == stages
        # the stages follow one another: none holds another
        assert all(a[3] <= b[2] for a, b in zip(held, held[1:]))
        loc, ack = held[0][4], held[-1][4]
        assert loc["keys"] == sp[4]["rows"] and loc["segs"] == 1
        assert ack["kind"] == kind
        legs.setdefault(sp[4]["req"], []).append(loc)
    # a request's two legs hold the worker's bucket of 64 slots; what is
    # real of them is what the push's span counts up to its last real id
    # (the ids arrive sorted, the pads last)
    want = sorted(
        (sp[4]["rows"], sp[4]["real"])
        for sp in _of(events, "ps.server.push", "W0")[:2]
    )
    assert len(legs) == STEPS
    for pair in legs.values():
        assert sorted((a["keys"], a["real"]) for a in pair) == want
        assert sum(a["keys"] for a in pair) == 64
        assert 32 < sum(a["real"] for a in pair) <= 40


def test_a_bundle_s_members_are_localized_before_their_group_s_span(events):
    # the bundle was handed over on the test's thread, no recv thread's
    (thread,) = {e[0] for e in _of(events, "ps.server.pull", "W9")}
    mine = [
        e for e in events if e[0] == thread and e[1].startswith("ps.server.")
    ]
    names = [e[1].removeprefix("ps.server.") for e in mine]
    assert names == [
        "localize", "localize", "localize",  # a, b, the pull
        "push", "h2d", "dispatch", "dispatch", "ack",  # the group of two
        "pull", "h2d", "dispatch",
        "localize",  # c
        "push", "h2d", "dispatch", "ack",  # the group of one
        "d2h", "ack",  # the bundle's one read-back, then the pull's reply
    ]
    locs = [e[4] for e in mine if e[1] == "ps.server.localize"]
    assert [(a["keys"], a["real"], a["segs"]) for a in locs] == [
        (4, 3, 1), (2, 2, 1), (3, 3, 1), (1, 1, 1),
    ]
    group, pull, single = (
        e for e in mine if e[1] in ("ps.server.push", "ps.server.pull")
    )
    assert group[4]["members"] == 2 and single[4]["members"] == 1
    # id 2 is in both members: two rounds, each a dispatch, one ack span
    assert [e[1] for e in _held(events, group)] == [
        "ps.server.h2d", "ps.server.dispatch", "ps.server.dispatch",
        "ps.server.ack",
    ]
    assert [e[1] for e in _held(events, single)] == [
        "ps.server.h2d", "ps.server.dispatch", "ps.server.ack",
    ]
    assert [e[1] for e in _held(events, pull)] == [
        "ps.server.h2d", "ps.server.dispatch",
    ]
    acks = [e[4]["kind"] for e in mine if e[1] == "ps.server.ack"]
    assert acks == ["push", "push", "pull"]
