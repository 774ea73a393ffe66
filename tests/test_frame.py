"""Flat wire frames (core/frame.py — ISSUE 7 tentpole).

Three layers of coverage:

1. **Codec unit matrix** — dtype round-trips (f32/f16/bf16/int8/bool/
   int64), empty values, keys=None, 0-row planes, oversized meta, and the
   typed-rejection contract: truncated buffers, garbled headers, and
   corrupted planes all raise :class:`FrameError`, never a bare struct/
   unicode error escaping on a recv thread.
2. **Header semantics** — transport stamps (``__rseq__``/``__rinc__``/
   ``__repoch__``/``__rcrc__``) lift into fixed header fields readable via
   :func:`frame.peek` alone (header-only dedup/fencing) and reinstate
   bitwise on decode; ``frame_nbytes`` sizes frames exactly without
   building them.
3. **Acceptance e2e** — LR training rides the REAL frame bytes
   (``FrameCodecVan`` under the full Coalesce+Metered+Reliable+Chaos
   stack) with seeded drop/duplication/corruption and a live mid-run
   migration: loss trajectory bitwise-equal to a clean run, exactly-once
   push accounting, corrupt frames caught by the resender's end-to-end
   CRC now carried in the header.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp
import ml_dtypes

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core import frame
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.coalesce import CoalescingVan
from parameter_server_tpu.core.frame import FrameCodecVan, FrameError
from parameter_server_tpu.core.messages import (
    INCARNATION_KEY,
    Message,
    NodeRole,
    Task,
    TaskKind,
)
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core import resender as resender_mod
from parameter_server_tpu.core.resender import ReliableVan, payload_crc32
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticCTR
from parameter_server_tpu.kv import routing as routing_mod
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.models import linear

ROWS = 1 << 10
NUM_SERVERS = 2
STEPS = 12


def _msg(**kw):
    defaults = dict(
        task=Task(TaskKind.PUSH, "t", payload={"table": "w"}),
        sender="W0",
        recver="S0",
        keys=np.arange(10, dtype=np.uint64),
        values=[np.arange(40, dtype=np.float32).reshape(10, 4)],
        is_request=True,
    )
    defaults.update(kw)
    return Message(**defaults)


def _roundtrip(msg):
    return frame.decode(frame.encode(msg))


def _assert_messages_equal(a: Message, b: Message):
    assert a.task.kind == b.task.kind
    assert a.task.customer == b.task.customer
    assert a.task.time == b.task.time
    assert a.task.wait_time == b.task.wait_time
    assert a.task.payload == b.task.payload
    assert a.sender == b.sender and a.recver == b.recver
    assert a.is_request == b.is_request
    if a.keys is None:
        assert b.keys is None
    else:
        assert a.keys.dtype == b.keys.dtype
        np.testing.assert_array_equal(a.keys, b.keys)
    assert len(a.values) == len(b.values)
    for va, vb in zip(a.values, b.values):
        assert va.dtype == vb.dtype and va.shape == vb.shape
        np.testing.assert_array_equal(
            np.asarray(va).view(np.uint8), np.asarray(vb).view(np.uint8)
        )


# ------------------------------------------------------- codec unit matrix


@pytest.mark.parametrize(
    "dtype",
    [
        np.float32,
        np.float16,
        ml_dtypes.bfloat16,
        np.int8,
        np.bool_,
        np.int64,
    ],
    ids=["f32", "f16", "bf16", "int8", "bool", "int64"],
)
def test_value_dtype_roundtrip(dtype):
    rng = np.random.default_rng(0)
    raw = rng.standard_normal((6, 3))
    vals = (raw > 0) if dtype is np.bool_ else raw.astype(dtype)
    msg = _msg(values=[np.ascontiguousarray(vals)])
    _assert_messages_equal(msg, _roundtrip(msg))


def test_empty_values_and_no_keys():
    msg = _msg(keys=None, values=[])
    got = _roundtrip(msg)
    _assert_messages_equal(msg, got)
    info = frame.peek(frame.encode(msg))
    assert info.n_arrays == 0 and info.planes_len == 0
    assert not info.flags & frame.FLAG_HAS_KEYS


def test_zero_row_plane_roundtrip():
    msg = _msg(
        keys=np.empty(0, dtype=np.uint64),
        values=[np.empty((0, 4), dtype=np.float32)],
    )
    got = _roundtrip(msg)
    _assert_messages_equal(msg, got)
    assert got.values[0].shape == (0, 4)


def test_scalar_plane_promotes_like_seed_codec():
    """0-d arrays frame as shape (1,) — np.ascontiguousarray's promotion,
    identical to the pickle codec this replaced (parity, not regression)."""
    got = _roundtrip(_msg(keys=None, values=[np.float32(3.5)]))
    assert got.values[0].shape == (1,)
    assert got.values[0][0] == np.float32(3.5)


def test_oversized_meta_roundtrip():
    msg = _msg(
        task=Task(
            TaskKind.CONTROL,
            "t",
            payload={"blob": "x" * 300_000, "ints": list(range(5000))},
        ),
        keys=None,
        values=[],
    )
    _assert_messages_equal(msg, _roundtrip(msg))


def test_decoded_planes_are_zero_copy_views():
    buf = frame.encode(_msg())
    got = frame.decode(buf)
    wire = np.frombuffer(buf, dtype=np.uint8)
    assert np.shares_memory(wire, got.keys)
    assert np.shares_memory(wire, got.values[0])
    assert not got.values[0].flags.writeable  # views of immutable bytes


def test_truncated_frame_is_typed_reject():
    buf = frame.encode(_msg())
    for cut in (0, 1, frame.HEADER_SIZE - 1, frame.HEADER_SIZE + 3,
                len(buf) - 1):
        with pytest.raises(FrameError):
            frame.decode(buf[:cut])


def test_garbled_header_is_typed_reject():
    buf = bytearray(frame.encode(_msg()))
    buf[5] ^= 0xFF  # inside the CRC-covered header region
    with pytest.raises(FrameError, match="header CRC"):
        frame.peek(bytes(buf))


def test_bad_magic_and_version_are_typed_rejects():
    good = frame.encode(_msg())
    with pytest.raises(FrameError):
        frame.decode(b"ZZ" + good[2:])  # magic AND header crc both wrong
    # random garbage entirely
    with pytest.raises(FrameError):
        frame.decode(b"\x00" * 64)


def test_corrupt_plane_is_typed_reject_and_verify_false_tolerates():
    buf = bytearray(frame.encode(_msg()))
    info = frame.peek(bytes(buf))
    buf[frame.HEADER_SIZE + info.meta_len + 7] ^= 0x10
    data = bytes(buf)
    assert not frame.verify_planes(data)
    with pytest.raises(FrameError, match="plane CRC"):
        frame.decode(data)
    got = frame.decode(data, verify=False)  # ChaosVan's injection path
    assert got.keys.shape == (10,)


def _fuzz_msg():
    """A frame whose meta exercises every decode path corruption can hit:
    strings, nested containers, a payload ndarray, and plane manifests."""
    return _msg(
        task=Task(
            TaskKind.PUSH,
            "t",
            payload={
                "table": "w",
                "scales": np.linspace(0.1, 1.0, 5, dtype=np.float32),
                "nested": (1, [2, "x"], b"\x00\xff"),
                "big": 1 << 80,
            },
        )
    )


def test_every_meta_bit_flip_is_typed_reject():
    """Single-bit flips in the meta section — which used to escape as
    OverflowError/ValueError off np.dtype/frombuffer and kill the recv
    thread — must ALL be caught, by the meta CRC, as FrameError."""
    good = frame.encode(_fuzz_msg())
    info = frame.peek(good)
    for off in range(frame.HEADER_SIZE, frame.HEADER_SIZE + info.meta_len):
        for bit in (0, 3, 7):
            buf = bytearray(good)
            buf[off] ^= 1 << bit
            with pytest.raises(FrameError):
                frame.decode(bytes(buf))


def test_fuzzed_frames_never_escape_frameerror():
    """Multi-bit garbling + truncation anywhere in the frame: decode either
    succeeds or raises FrameError — never any other exception type (the
    recv-thread survival contract)."""
    import random

    good = frame.encode(_fuzz_msg())
    rng = random.Random(7)
    for _ in range(400):
        buf = bytearray(good)
        for _ in range(rng.randint(1, 4)):
            buf[rng.randrange(len(buf))] ^= 1 << rng.randrange(8)
        if rng.random() < 0.25:
            buf = buf[: rng.randrange(len(buf))]
        try:
            frame.decode(bytes(buf))
        except FrameError:
            pass


def _refix_crcs(buf: bytearray) -> bytes:
    """Recompute meta+header CRCs so decode reaches the corrupted section
    (tests the structural validation BEHIND the CRC line of defense)."""
    import struct
    import zlib

    fields = list(frame.HEADER.unpack_from(buf, 0))
    meta_len = fields[11]
    meta = bytes(buf[frame.HEADER_SIZE : frame.HEADER_SIZE + meta_len])
    fields[10] = zlib.crc32(meta)  # meta_crc32
    frame.HEADER.pack_into(buf, 0, *fields[:-1], 0)
    struct.pack_into(
        "<I", buf, frame.HEADER_SIZE - 4,
        zlib.crc32(bytes(buf[: frame.HEADER_SIZE - 4])),
    )
    return bytes(buf)


def test_negative_manifest_dim_is_typed_reject():
    """A manifest claiming a negative shape dim must be a typed reject,
    not a silent mis-parse (frombuffer with negative count reads the whole
    remaining buffer; reshape treats a lone -N as -1)."""
    import struct

    buf = bytearray(frame.encode(_msg()))
    info = frame.peek(bytes(buf))
    # the last 8 meta bytes are the final dim of the last plane's shape
    # ((10, 4) float32 -> the 4)
    end = frame.HEADER_SIZE + info.meta_len
    assert struct.unpack_from("<q", buf, end - 8)[0] == 4
    struct.pack_into("<q", buf, end - 8, -4)
    with pytest.raises(FrameError, match="negative plane dim"):
        frame.decode(_refix_crcs(buf))


def test_negative_meta_ndarray_dim_is_typed_reject():
    """Same validation inside the tag codec's _T_NDARRAY branch (payload
    ndarrays: routing tables, q8 scales)."""
    import struct

    out = bytearray()
    frame._enc_obj(np.arange(6, dtype=np.float32).reshape(2, 3), out)
    # layout: tag(1) dlen(1) "float32"(7) ndim(1) dim0(8) dim1(8) data
    struct.pack_into("<q", out, 1 + 1 + 7 + 1, -2)
    with pytest.raises(FrameError, match="negative ndarray dim"):
        frame._dec_obj(bytes(out), 0)


def test_encode_overflowing_plane_count_is_typed_reject():
    """> 65535 planes cannot fit the u16 n_arrays field: typed FrameError
    at encode time, not a raw struct.error at send time."""
    msg = _msg(keys=None, values=[np.zeros(1, dtype=np.float32)] * 65600)
    with pytest.raises(FrameError, match="n_arrays"):
        frame.encode(msg)


# --------------------------------------------------- meta codec specifics


def test_meta_preserves_tuple_vs_list_and_bytes_and_bigint():
    payload = {
        "t": (1, 2, (3, "x")),
        "l": [1, 2, [3, "x"]],
        "b": b"\x00\xffraw",
        "big": 1 << 80,
        "neg": -(1 << 90),
        "f": 0.1,
        "none": None,
        "flag": True,
    }
    got = _roundtrip(_msg(task=Task(TaskKind.CONTROL, "t", payload=payload),
                          keys=None, values=[]))
    gp = got.task.payload
    assert gp == payload
    assert type(gp["t"]) is tuple and type(gp["l"]) is list
    assert type(gp["t"][2]) is tuple and type(gp["l"][2]) is list
    assert type(gp["b"]) is bytes


def test_meta_ndarray_payload_roundtrip():
    """q8 scale arrays and routing tables ride the payload as ndarrays."""
    scales = np.linspace(0.1, 2.0, 7, dtype=np.float32)
    got = _roundtrip(
        _msg(task=Task(TaskKind.PUSH, "t", payload={"q8_scales": scales}))
    )
    out = got.task.payload["q8_scales"]
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, scales)


def test_meta_np_scalars_decay_to_python_values():
    got = _roundtrip(
        _msg(task=Task(TaskKind.PUSH, "t",
                       payload={"n": np.int64(7), "x": np.float32(1.5),
                                "b": np.bool_(True)}),
             keys=None, values=[])
    )
    gp = got.task.payload
    assert gp["n"] == 7 and type(gp["n"]) is int
    assert gp["x"] == 1.5 and type(gp["x"]) is float
    assert gp["b"] is True


def test_meta_enums_decay_to_their_value_not_str():
    # the scheduler's node-table broadcast carries NodeRole entries;
    # receivers re-wrap with NodeRole(row["role"]) (core/manager.py), so
    # the wire value must be "scheduler", never str(obj)'s qualified name
    got = _roundtrip(
        _msg(task=Task(TaskKind.CONTROL, "mgr",
                       payload={"role": NodeRole.SCHEDULER,
                                "kind": TaskKind.PUSH}),
             keys=None, values=[])
    )
    gp = got.task.payload
    assert gp["role"] == "scheduler"
    assert NodeRole(gp["role"]) is NodeRole.SCHEDULER
    assert TaskKind(gp["kind"]) is TaskKind.PUSH


def test_meta_unknown_type_is_typed_reject():
    with pytest.raises(FrameError, match="cannot encode"):
        frame.encode(
            _msg(task=Task(TaskKind.PUSH, "t", payload={"fn": object()}))
        )


# ----------------------------------------------- header stamps + peek/dedup


def test_stamp_key_literals_match_their_owners():
    """frame.py repeats the stamp-key literals instead of importing their
    owner modules (keeps resender off the codec's import path); this pins
    the duplication."""
    assert frame.SEQ_KEY == resender_mod.SEQ_KEY
    assert frame.CRC_KEY == resender_mod.CRC_KEY
    assert frame.ROUTING_EPOCH_KEY == routing_mod.ROUTING_EPOCH_KEY


def test_stamps_lift_into_header_and_reinstate():
    payload = {
        "table": "w",
        resender_mod.SEQ_KEY: 7,
        INCARNATION_KEY: 2,
        routing_mod.ROUTING_EPOCH_KEY: 5,
        resender_mod.CRC_KEY: 123456,
    }
    msg = _msg(task=Task(TaskKind.PUSH, "t", payload=dict(payload)))
    buf = frame.encode(msg)

    # header-only visibility: dedup/fencing fields without any meta decode
    info = frame.peek(buf)
    assert info.seq == 7
    assert info.incarnation == 2
    assert info.epoch == 5
    assert info.e2e_crc == 123456
    assert info.is_request

    # the stamps rode the fixed header, not the meta section: the meta is
    # exactly as long as the same message without any stamps
    bare = _msg(task=Task(TaskKind.PUSH, "t", payload={"table": "w"}))
    assert info.meta_len == frame.peek(frame.encode(bare)).meta_len

    # ...and decode reinstates them bitwise
    got = frame.decode(buf)
    assert got.task.payload == payload


def test_encode_does_not_mutate_sender_payload():
    payload = {resender_mod.SEQ_KEY: 3, "table": "w"}
    msg = _msg(task=Task(TaskKind.PUSH, "t", payload=payload))
    frame.encode(msg)
    assert payload == {resender_mod.SEQ_KEY: 3, "table": "w"}


def test_non_int_stamp_values_ride_meta_not_header():
    msg = _msg(
        task=Task(TaskKind.PUSH, "t",
                  payload={resender_mod.SEQ_KEY: "not-an-int"})
    )
    buf = frame.encode(msg)
    info = frame.peek(buf)
    assert not info.flags & frame.FLAG_SEQ and info.seq is None
    assert frame.decode(buf).task.payload == {
        resender_mod.SEQ_KEY: "not-an-int"
    }


# ------------------------------------------- control-frame fast path


def _slow_encode(msg):
    """Force the general encoder (the fast path's ground truth)."""
    orig = frame._fast_encode
    frame._fast_encode = lambda m: None
    try:
        return frame.encode(msg)
    finally:
        frame._fast_encode = orig


def _ctl(payload, *, kind=TaskKind.CONTROL, is_request=False, time=0):
    return _msg(
        task=Task(kind, "t", time=time, payload=payload),
        keys=None,
        values=[],
        is_request=is_request,
    )


_FAST_ELIGIBLE = [
    _ctl({}),  # bare ack
    _ctl({resender_mod.SEQ_KEY: 7}),  # the resender ACK shape
    _ctl(
        {
            resender_mod.SEQ_KEY: 7,
            INCARNATION_KEY: 2,
            routing_mod.ROUTING_EPOCH_KEY: 5,
            resender_mod.CRC_KEY: 123456,
        }
    ),
    _ctl({"rows": 42, "step": -3}, kind=TaskKind.PUSH, is_request=True),
    _ctl({"n": (1 << 63) - 1, "m": -(1 << 63)}, time=-12345),  # i64 edges
]


def test_fast_path_is_byte_identical_to_general_encoder():
    """Every eligible no-plane control frame must encode to EXACTLY the
    general path's bytes — receivers (CRC checks, dedup peeks, goldens)
    can never tell which encoder ran."""
    for msg in _FAST_ELIGIBLE:
        fast = frame.encode(msg)
        assert frame._fast_encode(msg) is not None  # it really ran fast
        assert fast == _slow_encode(msg)
        _assert_messages_equal(frame.decode(fast), msg)


def test_fast_path_header_stamps_stay_peekable():
    buf = frame.encode(_FAST_ELIGIBLE[2])
    info = frame.peek(buf)
    assert info.seq == 7 and info.incarnation == 2
    assert info.epoch == 5 and info.e2e_crc == 123456


def test_fast_path_ineligible_payloads_fall_through():
    """Anything outside the meta-stable shape returns None from the fast
    encoder and rides the general path (which must still roundtrip)."""
    cases = [
        _ctl({"s": "text"}),  # non-int value
        _ctl({"b": True}),  # bool is not int (type-exact check)
        _ctl({"big": 1 << 70}),  # beyond the i64 slot
        _ctl({resender_mod.SEQ_KEY: 1 << 70}),  # out-of-range stamp
        _ctl({"nested": {"x": 1}}),
    ]
    for msg in cases:
        assert frame._fast_encode(msg) is None
        _assert_messages_equal(frame.decode(frame.encode(msg)), msg)


def test_fast_path_never_mutates_payload():
    payload = {resender_mod.SEQ_KEY: 3, "count": 9}
    msg = _ctl(dict(payload))
    frame.encode(msg)
    assert msg.task.payload == payload


def test_fast_cache_hit_reencodes_value_changes(monkeypatch):
    """Same signature, different slot values: the cached template must be
    re-patched per call, never replayed stale."""
    monkeypatch.setattr(frame, "_FAST_ENC_CACHE", {})
    a = _ctl({resender_mod.SEQ_KEY: 1, "n": 10}, time=5)
    b = _ctl({resender_mod.SEQ_KEY: 2, "n": -20}, time=6)
    ea, eb = frame.encode(a), frame.encode(b)
    assert len(frame._FAST_ENC_CACHE) == 1  # one signature, one template
    assert ea != eb
    assert ea == _slow_encode(a) and eb == _slow_encode(b)


def test_fast_cache_cap_bounds_memory_not_correctness(monkeypatch):
    monkeypatch.setattr(frame, "_FAST_ENC_CACHE", {})
    monkeypatch.setattr(frame, "_FAST_CACHE_CAP", 2)
    msgs = [_ctl({f"k{i}": i}) for i in range(4)]
    for m in msgs:
        assert frame.encode(m) == _slow_encode(m)  # overflow still correct
    assert len(frame._FAST_ENC_CACHE) == 2


def test_frame_nbytes_is_exact():
    cases = [
        _msg(),
        _msg(keys=None, values=[]),
        _msg(task=Task(TaskKind.PUSH, "t",
                       payload={"table": "w", resender_mod.SEQ_KEY: 9,
                                INCARNATION_KEY: 1,
                                resender_mod.CRC_KEY: 42}),
             values=[np.arange(40, dtype=np.float32).reshape(10, 4),
                     np.arange(3, dtype=np.int32)]),
        _msg(values=[np.zeros((5, 2), dtype=ml_dtypes.bfloat16)]),
        # out-of-range stamp values do NOT lift into the header — they ride
        # the meta section, and the estimate must include them (the filter
        # mirrors encode's _lift_int range checks, not just the key names)
        _msg(task=Task(TaskKind.PUSH, "t",
                       payload={"table": "w",
                                resender_mod.SEQ_KEY: 1 << 70,
                                resender_mod.CRC_KEY: 1 << 40,
                                INCARNATION_KEY: -(1 << 40),
                                routing_mod.ROUTING_EPOCH_KEY: 1 << 35})),
    ]
    for msg in cases:
        buf = frame.encode(msg)
        total, overhead = frame.frame_nbytes(msg)
        assert total == len(buf)
        assert overhead == frame.peek(buf).overhead


_STAMPS = {
    resender_mod.SEQ_KEY: 123457, INCARNATION_KEY: 2,
    resender_mod.CRC_KEY: 0xDEADBEEF,
}


@pytest.mark.parametrize(
    "msg,overhead",
    [
        (_msg(task=Task(TaskKind.PUSH, "kv", payload={"table": "w", **_STAMPS}),
              keys=np.arange(128, dtype=np.uint64),
              values=[np.zeros((128, 8), np.float32)]), 153),
        (_msg(task=Task(TaskKind.PULL, "kv", payload={"table": "w", **_STAMPS}),
              keys=np.arange(1024, dtype=np.uint64), values=[]), 128),
        (_msg(task=Task(TaskKind.CONTROL, "__resender__",
                        payload={resender_mod.ACK_KEY: 123457,
                                 INCARNATION_KEY: 2}),
              sender="S0", recver="W0", keys=None, values=[],
              is_request=False), 128),
    ],
    ids=["stamped_push", "stamped_pull_request", "resender_ack"],
)
def test_per_message_overhead_bytes(msg, overhead):
    """What a message costs on the wire beyond its planes (the README's
    Wire format section quotes these): the 52-byte header, the meta
    section, one (dtype, shape) record a plane; the resender's stamps ride
    the header and cost nothing."""
    buf = frame.encode(msg)
    info = frame.peek(buf)
    assert info.overhead == overhead == len(buf) - info.planes_len
    assert info.overhead == frame.HEADER_SIZE + info.meta_len


def test_payload_crc32_matches_header_plane_crc_for_plain_arrays():
    """Same bytes, two vantage points: the resender's zero-copy end-to-end
    CRC over (keys, values) equals the header's plane CRC when no filter
    rewrites the planes in between."""
    msg = _msg()
    assert payload_crc32(msg) == frame.peek(frame.encode(msg)).plane_crc


def test_frame_codec_van_counters():
    base = LoopbackVan()
    van = FrameCodecVan(base)
    try:
        got = []
        van.bind("S0", got.append)
        msg = _msg()
        assert van.send(msg)
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.01)  # loopback delivery rides a recv thread
        assert len(got) == 1
        _assert_messages_equal(msg, got[0])
        assert got[0] is not msg  # rode the wire bytes, not the reference
        c = van.counters()
        assert c["frames"] == 1 and c["frame_passthrough"] == 0
        assert c["frame_bytes"] == len(frame.encode(msg))
        assert c["frame_overhead_bytes"] == frame.peek(frame.encode(msg)).overhead
    finally:
        van.close()


# ----------------------------------------------------------- acceptance e2e


def _table_cfgs():
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }


def _batches():
    data = SyntheticCTR(key_space=4 * ROWS, nnz=8, batch_size=128, seed=3)
    return [data.next_batch() for _ in range(STEPS)]


def _train(worker, batches, on_step=None):
    losses = []
    for i, (keys, labels) in enumerate(batches):
        w_pos = worker.pull_sync("w", keys, timeout=60)
        g, _gb, loss = linear.grad_rows(jnp.asarray(w_pos), jnp.asarray(labels))
        worker.push_sync("w", keys, np.asarray(g) / labels.shape[0], timeout=60)
        losses.append(float(loss))
        if on_step is not None:
            on_step(i)
    return losses


def _clean_reference():
    van = LoopbackVan()
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        losses = _train(worker, _batches())
        return losses, sum(s.pushes for s in servers)
    finally:
        van.close()


def _framed_stack(*, seed=0, timeout=0.1, max_retries=60, **chaos_kw):
    """The full production wire plane over real frame bytes:

    Coalesce(Metered(Reliable(Chaos(FrameCodec(Loopback))))) — every
    message (bundles included) is encoded to a flat frame and decoded into
    frombuffer views before delivery, exactly as TcpVan would do it.
    """
    codec = FrameCodecVan(LoopbackVan())
    chaos = ChaosVan(codec, seed=seed, **chaos_kw)
    rel = ReliableVan(
        chaos, timeout=timeout, backoff=1.0, max_retries=max_retries,
        seed=seed,
    )
    metered = MeteredVan(rel, stamp=False)
    return CoalescingVan(metered), rel, chaos, codec, metered


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1])
def test_training_on_flat_frames_under_chaos_matches_clean_run(seed):
    """ISSUE 7 acceptance: bitwise training parity + exactly-once delivery
    with every message riding real frame bytes, under seeded drop,
    duplication AND corruption.  Corrupt planes re-framed by the chaos
    layer carry a self-consistent transport CRC, so they reach the
    resender — whose end-to-end ``__rcrc__`` (now a fixed header field)
    catches every flip: ``rejected_corrupt > 0`` and nothing is lost or
    double-applied."""
    ref_losses, ref_applied = _clean_reference()

    van, rel, chaos, codec, metered = _framed_stack(
        seed=seed, drop=0.05, duplicate=0.05, corrupt=0.05
    )
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        losses = _train(worker, _batches())

        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)
        assert sum(s.pushes for s in servers) == ref_applied  # exactly once
        assert van.flush(10)
        assert rel.gave_up == 0
        assert chaos.injected_drops + chaos.injected_dups > 0
        assert chaos.injected_corrupt > 0  # flips actually happened
        assert rel.rejected_corrupt > 0  # ...and the e2e CRC caught them

        c = codec.counters()
        assert c["frames"] > 0
        assert c["frame_passthrough"] == 0  # EVERY message framed
        assert c["frame_bytes"] > c["frame_overhead_bytes"] > 0

        # metering agrees with the codec about per-frame overhead existing
        mc = metered.counters()
        assert mc["wire_frame_bytes"] > mc["wire_bytes"]
        assert mc["wire_overhead_bytes"] > 0
    finally:
        van.close()


@pytest.mark.migration
def test_live_migration_rides_flat_frames():
    """Mid-run shard migration with the worker left stale: fence rejects
    (epoch riding the fixed header), refresh, convergence — on flat frames
    end to end, with the trajectory bitwise-equal to the clean run."""
    ref_losses, ref_applied = _clean_reference()

    van, rel, chaos, codec, _metered = _framed_stack(seed=3, drop=0.02)
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=256)
        moved = {}

        def on_step(i):
            if i != STEPS // 2:
                return
            # migrate WITHOUT informing the worker — it must discover the
            # new table from fence rejects alone, all on framed bytes
            moved["routing"] = mig.migrate(worker.routing, "w", 768, ROWS, 0)

        losses = _train(worker, _batches(), on_step=on_step)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)
        assert sum(s.pushes for s in servers) == ref_applied  # exactly once
        assert sum(s.fenced_rejects for s in servers) > 0
        assert worker.refresh_retries > 0
        assert worker.routing.epoch == moved["routing"].epoch  # converged
        assert codec.counters()["frame_passthrough"] == 0
        assert rel.gave_up == 0
        assert van.flush(10)
    finally:
        van.close()
