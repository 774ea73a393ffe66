"""Quantized wire plane with per-key error feedback (ISSUE 14 tentpole).

Acceptance anchors:

1. fp8 (e4m3/e5m2) numpy bit-trick codec: roundtrip error bounds, the
   seeded stochastic-rounding rng contract (unseeded refusal), and
   seed-replay determinism;
2. ``QuantizingFilter`` as the ``CoalescingVan`` codec: single-message
   and bundle roundtrips, PUSH-requests-only scope, ``FLAG_COMPRESSED``
   on the wire frame, MeteredVan raw-vs-wire byte accounting;
3. convergence parity — int8+EF training tracks the uncompressed run
   under seeded chaos across a LIVE migration, while plain int8 (no
   error feedback) measurably stalls on a dominant-magnitude gradient;
4. residual lifecycle — accumulators drop on ``adopt_routing`` (new
   routing epoch) and on a same-id restart (incarnation advance), never
   replaying stale error into a rebalanced/recovered fleet;
5. observability — ``cmpr_pct`` rides telemetry rows into pstop's CMPR%
   column, the compression SLO pair breaches on a bad ratio, the
   ``compress.*`` events are registered.
"""

import pathlib
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from parameter_server_tpu.config import (
    OptimizerConfig,
    TableConfig,
    WireCompressionConfig,
)
from parameter_server_tpu.core import coalesce, flightrec, frame
from parameter_server_tpu.core import filters as filters_mod
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.coalesce import CoalescingVan
from parameter_server_tpu.core.filters import (
    QuantizingFilter,
    _resolve_per_row,
    find_quantizers,
    quantizer_from_tables,
)
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.telemetry import (
    TelemetryAggregator,
    TelemetryPublisher,
)
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticCTR
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.models import linear
from parameter_server_tpu.ops.quantize import (
    FP8_FORMATS,
    dequantize_fp8,
    quantize_fp8,
)
from parameter_server_tpu.utils.metrics import transport_counters
from parameter_server_tpu.utils.slo import SloEngine, compression_plane_specs

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import pstop  # noqa: E402

ROWS = 1 << 10
NUM_SERVERS = 2
STEPS = 12


def _int8_ef(**kw):
    return WireCompressionConfig(codec="int8", error_feedback=True, **kw)


def _table_cfgs(compression=None):
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
            compression=compression,
        )
    }


def _push_msg(keys, values, table="w"):
    return Message(
        task=Task(TaskKind.PUSH, "kv", payload={"table": table}),
        sender="W0",
        recver="S0",
        keys=keys,
        values=list(values),
    )


# ------------------------------------------------------------ constants


def test_bundle_constants_match_coalesce():
    """filters.py mirrors the bundle literals to avoid an import cycle;
    this is the tripwire if coalesce.py ever renames them."""
    assert filters_mod._BUNDLE_CUSTOMER == coalesce.BUNDLE_CUSTOMER
    assert filters_mod._BUNDLE_KEY == coalesce.BUNDLE_KEY


# ------------------------------------------------------------------ fp8


@pytest.mark.parametrize("fmt,bound", [("e4m3", 0.0625), ("e5m2", 0.125)])
def test_fp8_roundtrip_relative_error_bound(fmt, bound):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 4)).astype(np.float32)
    q, s = quantize_fp8(x, fmt=fmt)
    got = dequantize_fp8(q, s, fmt=fmt)
    # normal-range values (>= ~1.6% of absmax for e4m3) carry a relative
    # error bounded by half an ulp: 2^-4 (3 mantissa bits) / 2^-3 (2 bits);
    # the near-zero tail falls into the scaled format's subnormal region,
    # where only the ABSOLUTE step (scale * min subnormal) is bounded
    amax = float(np.abs(x).max())
    normal = np.abs(x) >= amax / 32.0
    rel = np.abs(got - x) / np.maximum(np.abs(x), 1e-9)
    assert normal.sum() > 100
    assert float(rel[normal].max()) <= bound
    assert float(np.abs(got - x)[~normal].max()) <= amax / 32.0


@pytest.mark.parametrize("fmt", sorted(FP8_FORMATS))
def test_fp8_zeros_and_dynamic_range(fmt):
    q, s = quantize_fp8(np.zeros((8,), np.float32), fmt=fmt)
    np.testing.assert_array_equal(dequantize_fp8(q, s, fmt=fmt), 0.0)
    # four decades spanning the scaled format's finite range stay finite,
    # distinct, and ordered (no wraparound through the NaN/inf codes)
    x = np.array([0.01, 0.1, 1.0, 10.0, 100.0], np.float32)
    q, s = quantize_fp8(x, fmt=fmt)
    got = dequantize_fp8(q, s, fmt=fmt)
    assert np.all(np.isfinite(got)) and np.all(np.diff(got) > 0)


def test_fp8_stochastic_needs_seed_and_replays_deterministically():
    x = np.linspace(-2, 2, 97).astype(np.float32)
    with pytest.raises(ValueError, match="needs rng= or seed="):
        quantize_fp8(x, stochastic=True)
    a, _ = quantize_fp8(x, stochastic=True, seed=7)
    b, _ = quantize_fp8(x, stochastic=True, seed=7)
    c, _ = quantize_fp8(x, stochastic=True, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_fp8_stochastic_rounding_is_unbiased():
    # a value midway between representables must average out to itself
    x = np.array([1.0, 0.30], np.float32)  # scale pinned by the 1.0
    rng = np.random.default_rng(3)
    draws = [
        dequantize_fp8(*quantize_fp8(x, stochastic=True, rng=rng))[1]
        for _ in range(2000)
    ]
    assert abs(float(np.mean(draws)) - 0.30) < 0.005


# ------------------------------------------------- per_row config plumbing


def test_per_row_resolution():
    wide = np.zeros((4, 32), np.float32)
    narrow = np.zeros((4, 1), np.float32)
    assert _resolve_per_row("auto", wide) is True
    assert _resolve_per_row("auto", narrow) is False
    assert _resolve_per_row(True, narrow) is True
    assert _resolve_per_row(False, wide) is False


def test_fixing_float_per_row_config_changes_precision():
    """Rows with wildly different magnitudes: per-row scales quantize the
    small row finely; a forced per-tensor scale flattens it to the shared
    grid.  The explicit config knob must be observable end to end."""
    from parameter_server_tpu.core.filters import FixingFloatFilter

    x = np.vstack([
        np.full((1, 32), 100.0, np.float32),
        np.full((1, 32), 0.1, np.float32),
    ])
    per_row = FixingFloatFilter(config=WireCompressionConfig(per_row=True))
    per_tensor = FixingFloatFilter(config=WireCompressionConfig(per_row=False))
    got_row = per_row.decode(per_row.encode(_push_msg(None, [x]))).values[0]
    got_tensor = (
        per_tensor.decode(per_tensor.encode(_push_msg(None, [x]))).values[0]
    )
    err_row = np.abs(got_row[1] - 0.1).max()
    err_tensor = np.abs(got_tensor[1] - 0.1).max()
    assert err_row < 0.001  # 0.1/127 grid
    assert err_tensor > 0.01  # 100/127 grid rounds 0.1 to 0


# ------------------------------------------------------- QuantizingFilter


def test_quantizing_filter_single_push_roundtrip_and_flag():
    codec = QuantizingFilter(default=_int8_ef())
    keys = np.arange(32, dtype=np.int64)
    vals = np.linspace(-1, 1, 32).astype(np.float32).reshape(32, 1)
    enc = codec.encode(_push_msg(keys, [vals]))
    assert enc.values[0].dtype == np.int8
    assert frame.COMPRESSED_KEY in enc.task.payload
    # the frame codec stamps the compressed flag from the payload marker
    info = frame.peek(frame.encode(enc))
    assert info.flags & frame.FLAG_COMPRESSED
    dec = codec.decode(enc)
    assert frame.COMPRESSED_KEY not in dec.task.payload
    assert dec.values[0].dtype == np.float32
    np.testing.assert_allclose(dec.values[0], vals, atol=1.0 / 127 + 1e-6)
    c = codec.counters()
    assert c["compress_raw_bytes"] > c["compress_wire_bytes"] > 0


def test_quantizing_filter_scopes_to_push_requests_only():
    codec = QuantizingFilter(default=_int8_ef())
    vals = [np.ones((8, 1), np.float32)]
    pull = Message(
        task=Task(TaskKind.PULL, "kv", payload={"table": "w"}),
        sender="W0", recver="S0", keys=np.arange(8), values=list(vals),
    )
    assert codec.encode(pull) is pull
    reply = _push_msg(np.arange(8), vals)
    reply.is_request = False
    assert codec.encode(reply) is reply
    # tables routed to codec "none" pass through untouched
    off = QuantizingFilter(
        default=WireCompressionConfig(),
        per_table={"w": WireCompressionConfig()},
    )
    msg = _push_msg(np.arange(8), vals)
    assert off.encode(msg) is msg


def test_error_feedback_recovers_sub_step_gradients():
    """The EF physics: a plane whose absmax is ~300x the interesting
    values rounds them to ZERO every push; error feedback accumulates the
    loss and emits it once it crosses a quant step."""
    keys = np.arange(2, dtype=np.int64)
    g = np.array([[100.0], [0.3]], np.float32)

    def total(codec):
        out = np.zeros((2, 1), np.float32)
        for _ in range(10):
            dec = codec.decode(codec.encode(_push_msg(keys, [g.copy()])))
            out += dec.values[0]
        return out

    ef = total(QuantizingFilter(default=_int8_ef()))
    plain = total(
        QuantizingFilter(
            default=WireCompressionConfig(codec="int8", error_feedback=False)
        )
    )
    assert abs(ef[1, 0] - 3.0) < 100.0 / 127  # within one quant step
    assert plain[1, 0] == 0.0  # every push rounded the 0.3 away
    assert abs(ef[0, 0] - 1000.0) < 1e-3


def test_quantizer_from_tables_accepts_dicts_and_gates_on_config():
    assert quantizer_from_tables(_table_cfgs(None)) is None
    codec = quantizer_from_tables(_table_cfgs(_int8_ef()))
    assert isinstance(codec, QuantizingFilter)
    assert codec.per_table["w"].codec == "int8"


# ------------------------------------------------ cluster: bytes + parity


def _codec_stack(compression, *, seed=0, drop=0.0):
    """CoalescingVan(ReliableVan(ChaosVan(LoopbackVan)), codec=...) —
    the codec runs once per bundle ABOVE the reliability layer, so
    retransmits resend the already-quantized frame (no double EF)."""
    chaos = ChaosVan(LoopbackVan(), seed=seed, drop=drop)
    rel = ReliableVan(
        chaos, timeout=0.1, backoff=1.0, max_retries=60, seed=seed
    )
    codec = quantizer_from_tables(
        _table_cfgs(compression)
    ) if compression is not None else None
    van = CoalescingVan(MeteredVan(rel), codec=codec)
    return van, rel, codec


@pytest.mark.parametrize(
    "compression",
    [
        _int8_ef(),
        WireCompressionConfig(codec="fp8", fp8_format="e4m3"),
        WireCompressionConfig(codec="fp8", fp8_format="e5m2"),
    ],
    ids=["int8_ef", "fp8_e4m3", "fp8_e5m2"],
)
def test_cluster_roundtrip_and_metered_raw_bytes(compression):
    cfgs = _table_cfgs(compression)
    van, _rel, codec = _codec_stack(compression)
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        rng = np.random.default_rng(0)
        keys = np.sort(rng.choice(ROWS, 200, replace=False)).astype(np.int64)
        vals = rng.normal(size=(keys.size, 1)).astype(np.float32)
        worker.push_sync("w", keys, vals, timeout=60)
        got = worker.pull_sync("w", keys, timeout=60)
        # adagrad lr=0.1 applied the dequantized push: within one int8 step
        assert np.all(np.isfinite(got)) and float(np.abs(got).max()) > 0
        c = transport_counters(van)
        assert c["compress_raw_bytes"] > c["compress_wire_bytes"] > 0
        # a float32 value plane goes out as one byte an element and a scale
        assert c["compress_raw_bytes"] >= 3 * c["compress_wire_bytes"]
        # satellite 2: MeteredVan books what the frame WOULD have weighed
        assert c["wire_raw_bytes"] > c["wire_bytes"] > 0
        saved = c["wire_raw_bytes"] - c["wire_bytes"]
        assert saved == c["compress_raw_bytes"] - c["compress_wire_bytes"]
        assert len(find_quantizers(van)) == 1
        assert servers  # keep the recv handlers alive until close
    finally:
        van.close()


@pytest.mark.chaos
def test_plain_int8_stalls_where_error_feedback_converges():
    """Dominant-magnitude gradient through a REAL cluster under seeded
    chaos: per-tensor int8 rounds the small coordinates to zero every
    step, so without EF they never move; with EF the carried residual
    crosses the quant step and the accumulated update converges.  One
    server so the dominant coordinate shares every wire message."""
    pushes = 12
    cfgs = {
        "w": TableConfig(
            name="w", rows=64, dim=1,
            optimizer=OptimizerConfig(kind="sgd", learning_rate=1.0),
        )
    }

    def run(compression):
        chaos = ChaosVan(LoopbackVan(), seed=1, drop=0.05)
        rel = ReliableVan(
            chaos, timeout=0.1, backoff=1.0, max_retries=60, seed=1
        )
        codec = QuantizingFilter(default=compression) if compression else None
        van = CoalescingVan(rel, codec=codec)
        try:
            cfg = {
                "w": TableConfig(
                    name="w", rows=64, dim=1,
                    optimizer=cfgs["w"].optimizer, compression=compression,
                )
            }
            server = KVServer(Postoffice("S0", van), cfg, 0, 1)
            worker = KVWorker(Postoffice("W0", van), cfg, 1)
            keys = np.arange(40, dtype=np.int64)
            g = np.full((keys.size, 1), -0.3, np.float32)
            g[0, 0] = -100.0  # pins the per-tensor scale at ~100/127
            for _ in range(pushes):
                worker.push_sync("w", keys, g.copy(), timeout=60)
            w = worker.pull_sync("w", keys, timeout=60)
            assert server.pushes >= pushes
            return np.asarray(w, np.float32).reshape(-1)
        finally:
            van.close()

    exact = run(None)
    ef = run(_int8_ef())
    plain = run(WireCompressionConfig(codec="int8", error_feedback=False))
    # HashLocalizer folds keys into 64 slots, so colliding keys SUM their
    # gradients: the exact arm is the per-slot ground truth.  Slots hit by
    # exactly one small key accumulated pushes * 0.3 = 3.6 — those are the
    # sub-quant-step coordinates plain int8 must keep rounding to zero
    # (0.3 / (100/127) = 0.38 -> rint 0), while multi-key collisions can
    # legitimately cross the step.
    single = np.isclose(exact, pushes * 0.3, atol=1e-3)
    assert single.sum() >= 5
    # EF arm: every coordinate within ONE quant step of the exact run
    assert float(np.abs(ef - exact).max()) <= 100.0 / 127 + 1e-5
    # plain int8: the single-key small coordinates never moved
    assert float(np.abs(plain[single]).max()) == 0.0


@pytest.mark.chaos
@pytest.mark.migration
def test_training_parity_int8_ef_under_chaos_across_live_migration():
    """Real sparse-LR training, uncompressed vs int8+EF, both under the
    SAME seeded chaos, with a live migration (move + adopt_routing, which
    resets residuals) in the middle of the compressed run.  Final losses
    must agree within a tight tolerance."""

    def run(compression, migrate):
        van, _rel, codec = _codec_stack(compression, seed=2, drop=0.05)
        cfgs = _table_cfgs(compression)
        try:
            servers = [
                KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
                for s in range(NUM_SERVERS)
            ]
            worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
            data = SyntheticCTR(
                key_space=4 * ROWS, nnz=8, batch_size=128, seed=3
            )
            batches = [data.next_batch() for _ in range(STEPS)]
            mig = ShardMigrator(Postoffice("M0", van), chunk_rows=256)
            losses = []
            for i, (keys, labels) in enumerate(batches):
                if migrate and i == STEPS // 2:
                    new_routing = mig.migrate(
                        worker.routing, "w", 768, ROWS, 0
                    )
                    assert worker.adopt_routing(new_routing)
                    if codec is not None:
                        assert codec.resets >= 1
                w_pos = worker.pull_sync("w", keys, timeout=60)
                g, _gb, loss = linear.grad_rows(
                    jnp.asarray(w_pos), jnp.asarray(labels)
                )
                worker.push_sync(
                    "w", keys, np.asarray(g) / labels.shape[0], timeout=60
                )
                losses.append(float(loss))
            assert servers
            return losses
        finally:
            van.close()

    ref = run(None, migrate=False)
    comp = run(_int8_ef(), migrate=True)
    assert ref[-1] < ref[0]  # the reference actually learned
    assert abs(comp[-1] - ref[-1]) < 0.03
    assert abs(float(np.mean(comp[-3:])) - float(np.mean(ref[-3:]))) < 0.03


# ------------------------------------------------------ residual lifecycle


@pytest.mark.migration
def test_residuals_reset_on_adopt_routing():
    flightrec.configure(enabled=True, clear=True)
    cfgs = _table_cfgs(_int8_ef())
    codec = quantizer_from_tables(cfgs)
    van = CoalescingVan(LoopbackVan(), codec=codec)
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        rng = np.random.default_rng(4)
        keys = np.sort(rng.choice(ROWS, 100, replace=False)).astype(np.int64)
        worker.push_sync(
            "w", keys, rng.normal(size=(100, 1)).astype(np.float32),
            timeout=60,
        )
        assert codec._residuals and codec.resets == 0
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=256)
        new_routing = mig.migrate(worker.routing, "w", 768, ROWS, 0)
        assert worker.adopt_routing(new_routing)
        assert codec.resets >= 1 and not codec._residuals
        events = [
            e for e in flightrec.get().events()
            if e["kind"] == "compress.residual_reset"
        ]
        assert events and events[-1]["reason"] == "adopt_routing"
        assert servers
    finally:
        van.close()
        flightrec.configure(enabled=True, clear=True)


def test_residuals_reset_on_same_id_restart():
    """``restart_node`` (PR-4 same-id restart) advances the incarnation;
    the CoalescingVan ctor subscribed the codec to ReliableVan's
    incarnation-advance hook, so carried error dies with the old process."""
    cfgs = _table_cfgs(_int8_ef())
    codec = quantizer_from_tables(cfgs)
    rel = ReliableVan(
        LoopbackVan(), timeout=0.1, backoff=1.0, max_retries=60, seed=0
    )
    van = CoalescingVan(rel, codec=codec)
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        rng = np.random.default_rng(5)
        keys = np.sort(rng.choice(ROWS, 64, replace=False)).astype(np.int64)
        worker.push_sync(
            "w", keys, rng.normal(size=(64, 1)).astype(np.float32),
            timeout=60,
        )
        assert codec._residuals
        rel.restart_node("S0")
        assert codec.resets >= 1 and not codec._residuals
        assert servers
    finally:
        van.close()


# --------------------------------------------------------- observability


def test_cmpr_pct_rides_telemetry_into_pstop():
    class _Src:
        def counters(self):
            return {"wire_bytes": 300, "wire_raw_bytes": 1200}

    flightrec.configure(clear=True)
    try:
        rec = flightrec.FlightRecorder(capacity=16)
        pub = TelemetryPublisher("W0", None, recorder=rec, sources=[_Src()])
        agg = TelemetryAggregator()
        assert agg.ingest("W0", pub.frame(now=1.0), now=1.0)
        row = agg.latest()["W0"]
        assert row["cmpr_pct"] == 25.0
        out = "\n".join(pstop.render(agg.latest()))
        assert "CMPR%" in out and "25.0" in out
    finally:
        flightrec.configure(clear=True)


def test_compression_slo_breaches_on_bad_ratio():
    specs = compression_plane_specs(max_ratio_pct=50.0)
    assert [s.metric for s in specs] == [
        "compress_ratio_pct", "compress_residual_norm",
    ]
    eng = SloEngine(specs)
    eng.ingest_counters("W0", {"compress_ratio_pct": 80.0}, now=1.0)
    verdicts = eng.evaluate(now=1.5)
    assert not verdicts["W0"].healthy
    assert "compress-ratio" in verdicts["W0"].breaches
    eng.ingest_counters("W0", {"compress_ratio_pct": 26.0}, now=20.0)
    assert eng.evaluate(now=20.5)["W0"].healthy


def test_compress_events_registered_everywhere():
    kinds = {"compress.encode", "compress.decode", "compress.residual_reset"}
    assert kinds <= flightrec.EVENTS
    import check_wrappers  # tools/, via the sys.path insert above

    assert kinds <= set(check_wrappers.REQUIRED_EVENTS)
