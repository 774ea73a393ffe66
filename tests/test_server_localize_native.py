"""A server's localization of a request's leg (ISSUE 38): the one native pass
(``native/src/keymap.cc::ps_localize_shard``) is held to the NumPy body
(``KVServer._localize_numpy``, the definition) output for output, and the
server answers the same requests the same way on either engine."""

import numpy as np
import pytest

from parameter_server_tpu import native
from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.routing import FENCED_KEY, RoutingTable
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.utils import keys as keys_lib
from parameter_server_tpu.utils.trace import Tracer

ROWS = 1 << 17  # global rows: a pad is any key >= ROWS
PAD = ROWS


def _cfgs(rows=ROWS):
    return {
        "w": TableConfig(
            name="w", rows=rows, dim=1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=1.0),
        )
    }


#: the shard maps of server 0 the cases run against: its owned segments
MAPS = {
    "one": [(0, ROWS // 2)],
    # three non-adjacent segments (a shard after migrations, both ways)
    "three": [(100, 400), (1000, 1500), (70000, ROWS)],
    "none": [],
}


@pytest.fixture(scope="module")
def servers():
    """Server 0 of two under each map of ``MAPS``, on the native engine."""
    if keys_lib._keymap_lib() is None:
        pytest.skip("no toolchain: the keymap library did not build")
    van = LoopbackVan()
    out = {}
    for name, owned in MAPS.items():
        routing = RoutingTable.uniform(_cfgs(), 2).move("w", 0, ROWS, 1)
        for lo, hi in owned:
            routing = routing.move("w", lo, hi, 0)
        srv = KVServer(Postoffice(f"S0.{name}", van), _cfgs(), 0, 2, routing=routing)
        assert [tuple(map(int, se)) for se in zip(*srv._shard_maps["w"][:2])] == owned
        out[name] = srv
    yield out
    van.close()


def _edges(owned):
    """Every segment's first and last row."""
    return [g for lo, hi in owned for g in (lo, hi - 1)]


def _cell_leg(n, real, seed):
    """A leg a cell's shape: ``real`` sorted distinct keys of the last
    shard's range, then the worker's bucket pads up to ``n``."""
    rng = np.random.default_rng(seed)
    ks = np.sort(rng.choice(np.arange(70000, ROWS), size=real, replace=False))
    return np.concatenate([ks, np.full(n - real, PAD)])


#: (case, map, keys, fences)
CASES = [
    ("empty_leg", "one", [], False),
    ("empty_leg_three", "three", [], False),
    ("all_pads", "one", [PAD, PAD, PAD + 7, 2**31 - 1], False),
    ("all_pads_three", "three", [PAD] * 5, False),
    ("one_segment", "one", [3, 4, 9, 500, 40000, PAD, PAD], False),
    ("one_segment_edges", "one", _edges(MAPS["one"]) + [PAD], False),
    ("three_segments", "three", [150, 399, 1200, 70001, 99999, PAD], False),
    ("three_segments_edges", "three", _edges(MAPS["three"]) + [PAD], False),
    ("one_of_three_touched", "three", [1000, 1001, 1499, PAD], False),
    ("first_and_last_touched", "three", [100, ROWS - 1], False),
    ("duplicates", "three", [150, 150, 1200, 1200, 1200], False),
    ("gap_between_segments", "three", [150, 700, 1200], True),
    ("gap_on_an_end", "three", [150, 400, 1200], True),
    ("below_the_first", "three", [99, 150], True),
    ("below_the_first_alone", "three", [0], True),
    ("past_the_owned_half", "one", [5, ROWS // 2], True),
    ("negative", "one", [5, -1, 9], True),
    ("negative_three", "three", [-(2**31)], True),
    ("unsorted", "three", [99999, PAD, 150, 1200, 399, PAD, 100, 70000], False),
    ("unsorted_pads_first", "three", [PAD, PAD, 1499, 101], False),
    ("unsorted_fence_last", "three", [99999, 150, 1200, 500], True),
    ("empty_map_pads", "none", [PAD, PAD], False),
    ("empty_map_empty_leg", "none", [], False),
    ("empty_map_real_key", "none", [PAD, 5], True),
    ("cell_leg_7690", "three", _cell_leg(7690, 7000, 1), False),
    ("cell_leg_45700", "three", _cell_leg(45700, 20500, 2), False),
]


def _same(a, b):
    """Two localizations agree output for output, dtypes included."""
    if a is None or b is None:
        return a is None and b is None
    ids_a, segs_a, real_a, upto_a = a
    ids_b, segs_b, real_b, upto_b = b
    return (
        ids_a.dtype == ids_b.dtype == np.int32
        and ids_a.shape == ids_b.shape and np.array_equal(ids_a, ids_b)
        and segs_a.dtype == segs_b.dtype == np.int64
        and np.array_equal(segs_a, segs_b)
        and (real_a, upto_a) == (real_b, upto_b)
        and isinstance(real_a, int) and isinstance(upto_a, int)
    )


@pytest.mark.parametrize("dtype", [np.int32, np.int64], ids=["int32", "int64"])
@pytest.mark.parametrize(
    "which,keys,fences", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_native_pass_is_the_numpy_body(servers, which, keys, fences, dtype):
    srv = servers[which]
    k = np.asarray(keys, dtype=dtype)
    before = (srv.localize_requests, srv.localize_native)
    got = srv._localize_request("w", k)
    want = srv._localize_numpy("w", k)
    assert (srv.localize_requests, srv.localize_native) == (
        before[0] + 1, before[1] + 1,
    )
    assert (got is None) == fences
    assert _same(got, want)
    if got is not None:
        ids, segs, real, upto = got
        trash = srv.tables["w"].rows
        assert real == int(np.count_nonzero(ids != trash))
        assert np.all(ids[upto:] == trash) and (upto == 0 or ids[upto - 1] != trash)
        assert np.all(np.diff(segs) > 0)  # sorted, distinct


@pytest.mark.parametrize("form", ["uint64", "strided", "readonly", "list", "2d"])
def test_keys_in_another_form_localize_the_same(servers, form):
    """Keys that are not a contiguous int32 / int64 vector are compared as
    the ``int64`` NumPy makes of them, as the definition does."""
    srv = servers["three"]
    base = np.asarray([150, 399, 1200, 70001, PAD, PAD], dtype=np.int64)
    k = {
        "uint64": base.astype(np.uint64),
        "strided": np.repeat(base, 2).astype(np.int32)[::2],
        "readonly": np.frombuffer(base.astype(np.int32).tobytes(), np.int32),
        "list": [int(x) for x in base],
        "2d": base.astype(np.int32).reshape(2, 3),
    }[form]
    got = srv._localize_request("w", k)
    assert got is not None and got[2:] == (4, 4)
    assert _same(got, srv._localize_numpy("w", k))


# -- the server on either engine ----------------------------------------------

def _msg(kind, ids, ts, vals=None):
    return Message(
        task=Task(kind, "kv", time=ts, payload={"table": "w"}),
        sender="W9", recver="S0", keys=np.asarray(ids, dtype=np.int32),
        values=[] if vals is None else [np.asarray(vals, np.float32)],
    )


def _drive(srv):
    """A push, a pull of what it wrote, a fenced pull and a bundle on
    ``srv``: what each answered, as plain values."""
    rows = 1000
    out = []
    ids = [3, 7, 250, rows, rows]  # two bucket pads in the tail
    r = srv.handle_request(_msg(TaskKind.PUSH, ids, 1, np.ones((5, 1))))
    out.append(dict(r.task.payload))
    r = srv.handle_request(_msg(TaskKind.PULL, ids, 2))
    out.append((dict(r.task.payload), np.asarray(r.values[0]).tolist()))
    r = srv.handle_request(_msg(TaskKind.PULL, [3, 600], 3))  # 600: S1's row
    out.append(bool(r.task.payload.get(FENCED_KEY)))
    replies = srv.handle_request_batch([
        _msg(TaskKind.PUSH, [3, 9], 4, np.ones((2, 1))),
        _msg(TaskKind.PUSH, [9, rows], 5, np.ones((2, 1))),
        _msg(TaskKind.PULL, [3, 9, 499], 6),
        _msg(TaskKind.PUSH, [-1], 7, np.ones((1, 1))),
    ])
    out.append([np.asarray(v).tolist() for v in replies[2].values])
    out.append([bool(r.task.payload.get(FENCED_KEY)) for r in replies])
    return out


def _server(van, node, tracer):
    return KVServer(Postoffice(node, van), _cfgs(1000), 0, 2, tracer=tracer)


def test_either_engine_answers_the_same(monkeypatch):
    if keys_lib._keymap_lib() is None:
        pytest.skip("no toolchain: the keymap library did not build")
    van = LoopbackVan()
    try:
        tracer = Tracer(enabled=True)
        nat = _server(van, "S0", tracer)
        answers = _drive(nat)
        c = nat.counters()
        # a push, two pulls and the bundle's four members
        assert c["localize_requests"] == c["localize_native"] == 7
        assert c["fenced_rejects"] == 2
        spans = [a for *_, a in tracer.spans("ps.server.localize")]
        assert len(spans) == 7 and {a["engine"] for a in spans} == {"native"}
        # the first push: five keys, three of them rows of the shard's one
        # owned segment; a fenced request's span says the engine alone
        assert (spans[0]["keys"], spans[0]["real"], spans[0]["segs"]) == (5, 3, 1)
        assert set(spans[2]) == {"engine"}
        push = tracer.spans("ps.server.push")[0][-1]
        assert (push["rows"], push["real"]) == (5, 3)

        # the library is what ``native.load`` finds when the process starts
        # with PS_NO_NATIVE set
        monkeypatch.setenv("PS_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_cache", {})
        tracer = Tracer(enabled=True)
        plain = _server(van, "S0n", tracer)
        assert plain._keymap is None
        assert _drive(plain) == answers
        c = plain.counters()
        assert (c["localize_requests"], c["localize_native"]) == (7, 0)
        spans = [a for *_, a in tracer.spans("ps.server.localize")]
        assert {a["engine"] for a in spans} == {"numpy"}
        assert (spans[0]["keys"], spans[0]["real"], spans[0]["segs"]) == (5, 3, 1)
    finally:
        van.close()


def test_dirty_rows_of_an_open_window_are_the_written_keys():
    """``_ack_push`` builds the ``int64`` keys only while a migration or a
    snapshot is open, and tracks the same rows it did."""
    van = LoopbackVan()
    try:
        srv = _server(van, "S0", Tracer(enabled=False))
        srv.handle_request(_msg(TaskKind.PUSH, [3, 7, 1000], 1, np.ones((3, 1))))
        srv._snapshots["s"] = {"dirty": {}}
        srv._migrations["m"] = {
            "table": "w", "lo": 5, "hi": 300, "to": 1, "dirty": set(),
        }
        srv.handle_request(_msg(TaskKind.PUSH, [4, 8, 250, 1000], 2, np.ones((4, 1))))
        assert srv._snapshots["s"]["dirty"] == {"w": {4, 8, 250}}
        assert srv._migrations["m"]["dirty"] == {8, 250}
    finally:
        van.close()
