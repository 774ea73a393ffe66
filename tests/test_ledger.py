"""ApplyLedger (ISSUE 12): retire-exactly-once accounting, donation
censoring, backlog edges + ``__busy__`` backpressure, reaper lifecycle,
and the deterministic backlog-breach e2e (ledger -> telemetry -> SLO ->
pstop) the device-plane observability layer promises.

The ledger's contract is bookkeeping-only on the ack path (the AST half
lives in ``tools/check_wrappers.py::LEDGER_SYNC_FREE_FUNCS``); these tests
pin the BEHAVIORAL half: acks land while the device apply is provably
still running, and every submitted apply retires exactly once even under
seeded retransmission/duplication chaos.
"""

import json
import pathlib
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.config import (
    LedgerConfig,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core import flightrec
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.coalesce import CoalescingVan
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.telemetry import (
    TelemetryAggregator,
    TelemetryPublisher,
)
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.ledger import ApplyLedger
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.slo import SloEngine, device_plane_specs

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tools"))
import pstop  # noqa: E402

DIM = 4
ROWS = 64

#: fast reaper cadence for the fake (non-jax) refs below.
_FAST = dict(reap_interval_s=0.002, idle_stop_s=0.2)


class _Ref:
    """Controllable stand-in for a dispatched jax result array: ``dead`` is
    the donated-away buffer (every query raises, ``is_deleted`` says why)."""

    def __init__(self, ready=False, dead=False):
        self.ready = ready
        self.dead = dead

    def is_deleted(self):
        return self.dead

    def is_ready(self):
        if self.dead:
            raise RuntimeError("buffer donated away")
        return self.ready

    def block_until_ready(self):
        if self.dead:
            raise RuntimeError("buffer donated away")
        time.sleep(_FAST["reap_interval_s"])


def _drained(ledger, timeout=5.0):
    assert ledger.drain(timeout), ledger.counters()


# ------------------------------------------------------------ unit: ledger


def test_submit_retires_exactly_once_with_attribution_digests():
    rec = flightrec.FlightRecorder(capacity=64)
    led = ApplyLedger("S0", LedgerConfig(**_FAST), recorder=rec)
    try:
        tok = led.begin("w", members=2, rows=12)
        tok.mark_host()
        tok.mark_h2d()
        ref = _Ref(ready=False)
        led.submit(tok, ref, fallback=lambda: ref)
        c = led.counters()
        assert c["inflight_bundles"] == 1 and c["inflight_rows"] == 12
        assert c["applies_submitted"] == 1 and c["applies_retired"] == 0
        ref.ready = True
        _drained(led)
        c = led.counters()
        assert c["inflight_bundles"] == 0 and c["inflight_rows"] == 0
        assert c["applies_retired"] == 1 and c["applies_censored"] == 0
        digs = led.latency_digests()
        assert set(digs) == {
            "apply.w", "apply_host.w", "apply_h2d.w", "apply_dev.w"
        }
        assert all(d["count"] == 1 for d in digs.values())
        kinds = [e["kind"] for e in rec.events()]
        assert kinds == ["apply.submit", "apply.done"]
        done = rec.events()[-1]
        assert done["rows"] == 12 and done["members"] == 2
        assert done["ms"] >= done["host_ms"] >= 0
    finally:
        led.close()


def test_donated_ref_retires_via_fallback_and_is_censored():
    led = ApplyLedger("S0", LedgerConfig(**_FAST))
    try:
        tok = led.begin("w", 1, 4)
        led.submit(tok, _Ref(dead=True), fallback=lambda: _Ref(ready=True))
        _drained(led)
        c = led.counters()
        assert c["applies_retired"] == 1 and c["applies_censored"] == 1
    finally:
        led.close()


def test_backlog_edge_events_and_overloaded_level():
    rec = flightrec.FlightRecorder(capacity=64)
    led = ApplyLedger(
        "S0", LedgerConfig(backlog_bundles=2, **_FAST), recorder=rec
    )
    try:
        refs = [_Ref() for _ in range(3)]
        for r in refs:
            led.submit(led.begin("w", 1, 1), r, fallback=lambda r=r: r)
        assert led.overloaded()  # 3 > 2: level-triggered hint is up
        edges = [e for e in rec.events() if e["kind"] == "apply.backlog"]
        assert [e["state"] for e in edges] == ["enter"]  # edge, not level
        assert edges[0]["inflight_bundles"] == 3
        for r in refs:
            r.ready = True
        _drained(led)
        assert not led.overloaded()
        edges = [e for e in rec.events() if e["kind"] == "apply.backlog"]
        assert [e["state"] for e in edges] == ["enter", "clear"]
    finally:
        led.close()


def test_reaper_self_stops_when_idle_and_restarts_on_submit():
    led = ApplyLedger("S0", LedgerConfig(**_FAST))
    try:
        led.submit(led.begin("w", 1, 1), _Ref(ready=True), lambda: None)
        _drained(led)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            reaper = led._reaper
            if reaper is None or not reaper.is_alive():
                break
            time.sleep(0.01)
        else:
            pytest.fail("reaper did not self-stop after idle_stop_s")
        led.submit(led.begin("w", 1, 1), _Ref(ready=True), lambda: None)
        _drained(led)
        assert led.counters()["applies_retired"] == 2
    finally:
        led.close()


# ------------------------------------------- behavioral: sync-free + ledger


def _entangle_fn():
    """Jitted identity whose output depends on ~300 ms of device work the
    compiler cannot elide (0.0 * finite is exact-zero but data-dependent),
    so 'did anything wait for the device?' is directly observable."""

    @jax.jit
    def entangle(v):
        z = jnp.full((1300, 1300), jnp.float32(1e-3)) + v[0, 0]
        for _ in range(6):
            z = jnp.tanh(z @ z)
        return v + 0.0 * z[: v.shape[0], : v.shape[1]]

    return entangle


def _slow_table(tbl):
    """Entangle every apply on ``tbl`` into ~300 ms of device work, keeping
    the push return value (the ledger's readiness ref) on the slow chain."""
    entangle = _entangle_fn()
    orig_push, orig_batch = tbl.push, tbl.push_batch

    def slow_push(ids, vals, n=None):
        orig_push(ids, vals, n)
        tbl.value = entangle(tbl.value)
        return tbl.value

    def slow_push_batch(ids, positions, vals, n=None):
        orig_batch(ids, positions, vals, n)
        tbl.value = entangle(tbl.value)
        return tbl.value

    tbl.push, tbl.push_batch = slow_push, slow_push_batch


def _push_msg(rng, n=5):
    ids = np.sort(rng.choice(np.arange(ROWS), size=n, replace=False))
    vals = rng.normal(size=(n, DIM)).astype(np.float32)
    return Message(
        task=Task(TaskKind.PUSH, "kv", payload={"table": "w"}),
        sender="W0",
        recver="S0",
        keys=np.asarray(ids, dtype=np.int32),
        values=[vals.reshape(-1, DIM)],
    )


def test_ack_lands_while_ledger_entry_still_in_flight():
    """The sync-free contract WITH the ledger attached: the push ack
    returns while ``is_ready()`` is still False AND the ledger still
    carries the apply in flight — registration happened on the ack path
    without observing the device, retirement strictly after."""
    van = LoopbackVan()
    try:
        cfg = TableConfig(
            name="w", rows=ROWS, dim=DIM,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
        srv = KVServer(Postoffice("S0", van), {"w": cfg}, 0, 1)
        assert srv.ledger is not None  # on by default
        tbl = srv.tables["w"]
        _slow_table(tbl)
        rng = np.random.default_rng(8)

        srv.handle_request(_push_msg(rng))  # warm-up: compile apply+entangle
        jax.block_until_ready(tbl.value)
        _drained(srv.ledger)
        c0 = srv.ledger.counters()

        t0 = time.perf_counter()
        reply = srv.handle_request(_push_msg(rng))
        ack_s = time.perf_counter() - t0
        assert "__error__" not in reply.task.payload
        assert not tbl.value.is_ready(), "ack waited for the device apply"
        c1 = srv.ledger.counters()
        assert c1["applies_submitted"] == c0["applies_submitted"] + 1
        assert c1["applies_retired"] == c0["applies_retired"]  # not yet
        assert c1["inflight_bundles"] == 1

        jax.block_until_ready(tbl.value)
        device_s = time.perf_counter() - t0
        assert ack_s < device_s, (ack_s, device_s)
        _drained(srv.ledger)
        c2 = srv.ledger.counters()
        assert c2["applies_retired"] == c2["applies_submitted"]
        assert c2["inflight_bundles"] == 0
    finally:
        van.close()


# --------------------------------------------------- e2e: chaos accounting


def test_every_apply_retires_exactly_once_under_seeded_chaos():
    """Full production stack — coalesced bundles, retransmission over
    seeded drop/duplication chaos, grouped device applies — and the
    ledgers still balance: every submitted apply retires exactly once, no
    entry leaks, no entry double-retires (inflight would go negative and
    retired would overshoot submitted)."""
    chaos = ChaosVan(LoopbackVan(), seed=2, drop=0.05, duplicate=0.05)
    rel = ReliableVan(chaos, timeout=0.05, backoff=1.0, max_retries=60, seed=2)
    van = CoalescingVan(rel)
    try:
        cfgs = {
            "w": TableConfig(
                name="w", rows=1 << 10, dim=DIM,
                optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
            )
        }
        servers = [
            KVServer(Postoffice(f"S{s}", van), cfgs, s, 2) for s in range(2)
        ]
        worker = KVWorker(Postoffice("W0", van), cfgs, 2)
        rng = np.random.default_rng(11)
        for _ in range(3):
            pool = rng.choice(1 << 10, size=96, replace=False).astype(np.uint32)
            k1, k2 = np.sort(pool[:64]), np.sort(pool[32:])
            g1 = rng.normal(size=(64, DIM)).astype(np.float32)
            g2 = rng.normal(size=(64, DIM)).astype(np.float32)
            with worker.coalesce_window():
                t1 = worker.push("w", k1, g1)
                t2 = worker.push("w", k2, g2)
            assert worker.wait(t1, timeout=60) and worker.wait(t2, timeout=60)
        assert van.flush(30)
        assert chaos.injected_drops + chaos.injected_dups > 0
        for srv in servers:
            _drained(srv.ledger, timeout=15.0)
            c = srv.ledger.counters()
            assert c["applies_submitted"] > 0
            assert c["applies_retired"] == c["applies_submitted"], c
            assert c["inflight_bundles"] == 0 and c["inflight_rows"] == 0, c
    finally:
        van.close()


# ------------------------------------- e2e: backlog breach, busy, pstop


def test_backlog_breach_fires_live_slo_busy_hints_and_pstop(
    tmp_path, capsys
):
    """The ISSUE-12 acceptance walk: a slow-apply server drives its
    backlog over the device-plane SLO bound; the live stream fires
    ``slo.breach``, the server stamps ``__busy__`` into acks (worker sees
    the hint), and the breach shows up in both ``pstop.snapshot()`` and
    the ``--json`` CLI output over the aggregator's JSONL spill — then
    everything clears once the device catches up.

    Slowness is injected at the ledger's own seam: the monkeypatched push
    returns a gate ref whose readiness the test controls, so the backlog
    depth is exact (real device chains throttle in the CPU dispatch queue
    and cap the pile-up nondeterministically).  The ack path underneath
    stays the real one — real applies, real replies, real busy stamps."""
    flightrec.configure(clear=True)
    rec = flightrec.FlightRecorder(capacity=256)
    van = LoopbackVan()
    try:
        cfgs = {
            "w": TableConfig(
                name="w", rows=ROWS, dim=DIM,
                optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
            )
        }
        srv = KVServer(
            Postoffice("S0", van), cfgs, 0, 1,
            devobs=LedgerConfig(enabled=True, backlog_bundles=2, **_FAST),
        )
        worker = KVWorker(Postoffice("W0", van), cfgs, 1)
        tbl = srv.tables["w"]
        orig_push, gates = tbl.push, []

        def gated_push(ids, vals, n=None):
            orig_push(ids, vals, n)
            gates.append(_Ref(ready=False))
            return gates[-1]

        tbl.push = gated_push

        path = str(tmp_path / "telemetry.jsonl")
        eng = SloEngine(
            device_plane_specs("w", apply_p99_ms=1e9, backlog_bundles=2),
            recorder=rec,
        )
        agg = TelemetryAggregator(slo=eng, jsonl_path=path)
        pub = TelemetryPublisher("S0", van, sources=[srv])
        rng = np.random.default_rng(3)
        keys = np.sort(
            rng.choice(ROWS, size=8, replace=False)
        ).astype(np.uint32)

        def push():
            g = rng.standard_normal((8, DIM)).astype(np.float32)
            assert worker.wait(worker.push("w", keys, g), timeout=60)

        push()  # healthy phase: one apply, retired immediately
        gates[-1].ready = True
        _drained(srv.ledger)
        agg.ingest("S0", pub.frame())
        assert eng.healthy("S0")
        assert worker.busy_hints == 0

        # acks keep landing while nothing retires — the backlog climbs
        # deterministically past the bound of 2
        for _ in range(4):
            push()
        assert srv.ledger.counters()["inflight_bundles"] == 4
        assert srv.ledger.overloaded()
        assert worker.busy_hints > 0, "ack never carried the __busy__ hint"
        assert worker.server_busy("S0")

        agg.ingest("S0", pub.frame())  # the live stream carries the gauge
        assert not eng.healthy("S0")
        breaches = [e for e in rec.events() if e["kind"] == "slo.breach"]
        assert breaches and breaches[0]["slo"] == "apply-backlog"
        assert breaches[0]["node"] == "S0"

        latest = pstop.load_rows(path)
        snap = pstop.snapshot(latest)
        assert snap["breached"] == ["S0"]
        assert snap["nodes"]["S0"]["counters"]["inflight_bundles"] == 4
        assert "BREACH:apply-backlog" in "\n".join(pstop.render(latest))
        assert pstop.main(["--json", "--once", path]) == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["breached"] == ["S0"]

        # open the gates: everything retires, SLO clears on the next
        # frame, acks stop carrying the hint
        for g in gates:
            g.ready = True
        _drained(srv.ledger)
        assert not srv.ledger.overloaded()
        agg.ingest("S0", pub.frame())
        assert eng.healthy("S0")
        assert [e["kind"] for e in rec.events()].count("slo.clear") == 1
        hints_before = worker.busy_hints
        push()
        gates[-1].ready = True
        _drained(srv.ledger)
        assert worker.busy_hints == hints_before
    finally:
        van.close()
        flightrec.configure(clear=True)
