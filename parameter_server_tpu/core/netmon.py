"""MeteredVan: per-link wire accounting for any Van stack.

Reference analogue: ``system/network_usage.h`` feeding ``monitor.h`` [U] —
the per-node send/recv byte counters the scheduler dashboard aggregated.
Here the accounting is a Van decorator, so it meters whatever stack it
wraps: per directed link (sender -> recver) it records message counts,
payload bytes (keys + values nbytes), and two latency distributions in
mergeable :class:`~parameter_server_tpu.utils.trace.LatencyHistogram`\\ s:

- **send**: the wall time of the inner ``send`` call (serialization,
  filter passes, queue handoff — what the sending thread pays);
- **deliver**: send-stamp to receive-side delivery, measured by stamping
  ``time.monotonic()`` into ``Task.payload`` on the way out and reading it
  in a receive wrapper on the way in (the ``__rseq__`` pattern of
  ``core/resender.py``).  Over an in-process Van both ends share a clock,
  so this is true one-way latency; cross-host the raw difference embeds
  clock skew — feed :meth:`MeteredVan.set_clock_offset` with the
  heartbeat-RTT/2 estimates from ``Manager.sync_clock`` /
  ``FleetMonitor.relative_offset`` to correct it.

Stack position: OUTERMOST — ``MeteredVan(ReliableVan(ChaosVan(base)))`` —
so each LOGICAL message is counted exactly once (retransmits, ACKs, and
coalesced bundle frames happen in the layers below) and deliver latency
includes everything the stack added: chaos delays, retransmit waits,
bundle flushes.  That end-to-end per-link signal is what the
``core/fleet.py`` straggler detector consumes: a gray-failing node shows
up as elevated deliver latency on every link INTO it.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from parameter_server_tpu.core import flightrec, frame
from parameter_server_tpu.core.messages import Message, Task
from parameter_server_tpu.core.van import Van, VanWrapper
from parameter_server_tpu.utils.trace import LatencyHistogram, req_id, span

#: payload key carrying the send-side monotonic stamp (stripped on receive).
STAMP_KEY = "__mts__"


def payload_nbytes(msg: Message) -> int:
    """Payload bytes of one message: keys nbytes + each value's nbytes.

    ``nbytes`` is read straight off array values (numpy and jax.Array both
    expose it — no device sync); anything else is sized via ``np.asarray``.
    Task metadata (pickle overhead, payload dict) is intentionally NOT
    counted: the meter reports the tensor traffic the PS exists to move,
    which is what ``bytes_per_example`` should be built from.
    """
    total = 0
    if msg.keys is not None:
        total += int(msg.keys.nbytes)
    for v in msg.values:
        nb = getattr(v, "nbytes", None)
        if nb is None:
            nb = np.asarray(v).nbytes
        total += int(nb)
    return total


class _LinkStats:
    """Counters + histograms for one directed link."""

    __slots__ = ("msgs", "bytes", "raw_bytes", "frame_bytes",
                 "overhead_bytes", "verbs", "send", "deliver")

    def __init__(self) -> None:
        self.msgs = 0
        self.bytes = 0
        #: per-verb split of msgs/bytes (``{"PUSH": [msgs, bytes], ...}``):
        #: the request-COUNT-by-verb signal the hierarchical-push bench
        #: (ISSUE 15) reads to show inbound PUSH requests dropping with
        #: group size, and ``fleet.inbound_totals`` aggregates per node.
        self.verbs: Dict[str, list] = {}
        #: pre-compression payload bytes: ``bytes`` plus whatever the lossy
        #: wire codec saved (its payload marker's ``saved`` total).  Equal
        #: to ``bytes`` on uncompressed links; the per-link compression
        #: ratio is ``bytes / raw_bytes`` with no filter instrumentation.
        self.raw_bytes = 0
        #: exact flat-frame wire size (``core/frame.py``): payload planes
        #: PLUS the 52-byte fixed header and the encoded meta section —
        #: per-message framing tax, measured rather than modeled.
        self.frame_bytes = 0
        #: the non-plane share of ``frame_bytes`` (header + meta).
        self.overhead_bytes = 0
        self.send = LatencyHistogram()
        self.deliver = LatencyHistogram()


class MeteredVan(VanWrapper):
    """Wire-accounting Van decorator.  See module docstring.

    ``stamp=False`` disables the payload timestamp (and with it deliver
    latency) for stacks whose messages must round-trip byte-identical.
    """

    def __init__(self, inner: Van, *, stamp: bool = True) -> None:
        super().__init__(inner)
        self._stamp = stamp
        self._lock = threading.Lock()
        self._links: Dict[Tuple[str, str], _LinkStats] = {}
        self.undeliverable = 0
        #: per-sender clock correction (seconds): sender's monotonic clock
        #: minus the local receiver's, added to raw deliver latencies.
        self._clock_offsets: Dict[str, float] = {}

    def set_clock_offset(self, sender: str, offset_s: float) -> None:
        """Correct deliver latencies for frames FROM ``sender``.

        ``offset_s`` is the sender's monotonic clock minus this process's
        (i.e. :meth:`~parameter_server_tpu.core.fleet.FleetMonitor.relative_offset`
        of (sender, local node)).  Cross-host, ``recv_local - send_remote``
        embeds that offset; adding it back yields true one-way latency, so
        the gray-failure detector keeps working off loopback.  In-process
        stacks share one clock and never need this (offset 0).
        """
        with self._lock:
            if offset_s == 0.0:
                self._clock_offsets.pop(sender, None)
            else:
                self._clock_offsets[sender] = offset_s

    def _link(self, sender: str, recver: str) -> _LinkStats:
        st = self._links.get((sender, recver))
        if st is None:
            st = self._links[(sender, recver)] = _LinkStats()
        return st

    # -- send path -----------------------------------------------------------
    def send(self, msg: Message) -> bool:
        # the whole method, its own metering included
        with span("ps.van.send", verb=msg.task.kind.name) as sp:
            return self._send(msg, sp)

    def _send(self, msg: Message, sp) -> bool:
        nbytes = payload_nbytes(msg)
        saved = 0
        p = msg.task.payload
        if isinstance(p, dict):
            wc = p.get(frame.COMPRESSED_KEY)
            if isinstance(wc, dict):
                saved = int(wc.get("saved", 0))
        out = msg
        if self._stamp:
            # direct constructors, not dataclasses.replace: replace() pays
            # ~7 us of field introspection per call pair, and this is the
            # per-message hot path the --obs overhead guard holds to <= 3%
            t = msg.task
            out = Message(
                task=Task(
                    kind=t.kind, customer=t.customer, time=t.time,
                    wait_time=t.wait_time,
                    payload={**t.payload, STAMP_KEY: time.monotonic()},
                ),
                sender=msg.sender, recver=msg.recver, keys=msg.keys,
                values=msg.values, is_request=msg.is_request,
            )
        # exact wire framing for this message as sent (incl. the __mts__
        # stamp just added): plane bytes + 52-byte header + meta section.
        # ``frame_nbytes`` sizes the meta without building the frame and
        # without touching device values; resender stamps added below ride
        # the fixed header (lifted), so they contribute zero meta bytes and
        # the per-layer accounting composes exactly.
        try:
            fbytes, obytes = frame.frame_nbytes(out)
        except frame.FrameError:  # uncodable payload object (in-proc only)
            fbytes, obytes = nbytes + frame.HEADER_SIZE, frame.HEADER_SIZE
        sp.set(bytes=fbytes)
        t0 = time.perf_counter()
        ok = self.inner.send(out)
        dt = time.perf_counter() - t0
        verb = msg.task.kind.name
        with self._lock:
            st = self._link(msg.sender, msg.recver)
            st.msgs += 1
            st.bytes += nbytes
            st.raw_bytes += nbytes + saved
            st.frame_bytes += fbytes
            st.overhead_bytes += obytes
            vb = st.verbs.get(verb)
            if vb is None:
                vb = st.verbs[verb] = [0, 0]
            vb[0] += 1
            vb[1] += nbytes
            st.send.record(dt)
            if not ok:
                self.undeliverable += 1
        flightrec.record(
            "frame.send", node=msg.sender, recver=msg.recver,
            verb=verb, bytes=nbytes, ok=ok,
        )
        return ok

    # -- receive path --------------------------------------------------------
    def bind(self, node_id: str, handler: Callable[[Message], None]) -> None:
        def metered(msg: Message) -> None:
            payload = msg.task.payload
            ts = payload.get(STAMP_KEY) if isinstance(payload, dict) else None
            lat = None
            if ts is not None:
                # strip the stamp before delivery: replies share the Task
                # (msg.reply()), so a leaked stamp would time-travel into
                # the response leg and read as a negative latency.  Direct
                # constructors for the same hot-path reason as send().
                t = msg.task
                stripped = dict(payload)
                del stripped[STAMP_KEY]
                msg = Message(
                    task=Task(
                        kind=t.kind, customer=t.customer, time=t.time,
                        wait_time=t.wait_time, payload=stripped,
                    ),
                    sender=msg.sender, recver=msg.recver, keys=msg.keys,
                    values=msg.values, is_request=msg.is_request,
                )
                with self._lock:
                    correction = self._clock_offsets.get(msg.sender, 0.0)
                    lat = time.monotonic() - ts + correction
                    self._link(msg.sender, msg.recver).deliver.record(lat)
                flightrec.record(
                    "frame.recv", node=msg.recver, sender=msg.sender,
                    verb=msg.task.kind.name, deliver_ms=round(1e3 * lat, 3),
                )
            # a reply carries its request's ``req``: the requester is its
            # receiver.  The wait in the inbox began on the sender's thread
            # and cannot be backdated into a trace: it is an attribute.
            t = msg.task
            with span(
                "ps.van.deliver",
                req=req_id(
                    msg.sender if msg.is_request else msg.recver,
                    t.customer, t.time,
                ),
                verb=t.kind.name, sender=msg.sender,
                is_request=int(msg.is_request),
            ) as sp:
                if lat is not None:
                    sp.set(wait_us=int(1e6 * max(lat, 0.0)))
                handler(msg)

        self.inner.bind(node_id, metered)

    # -- accounting ----------------------------------------------------------
    def counters(self) -> dict:
        """Numeric totals for the ``transport_counters`` merge walk."""
        with self._lock:
            return {
                "wire_msgs": sum(st.msgs for st in self._links.values()),
                "wire_bytes": sum(st.bytes for st in self._links.values()),
                "wire_raw_bytes": sum(
                    st.raw_bytes for st in self._links.values()
                ),
                "wire_frame_bytes": sum(
                    st.frame_bytes for st in self._links.values()
                ),
                "wire_overhead_bytes": sum(
                    st.overhead_bytes for st in self._links.values()
                ),
                "wire_links": len(self._links),
                "wire_undeliverable": self.undeliverable,
            }

    def links(self) -> Dict[str, dict]:
        """Per-link digests keyed ``"sender->recver"`` (JSON-safe)."""
        with self._lock:
            return {
                f"{s}->{r}": {
                    "msgs": st.msgs,
                    "bytes": st.bytes,
                    "raw_bytes": st.raw_bytes,
                    "frame_bytes": st.frame_bytes,
                    "overhead_bytes": st.overhead_bytes,
                    "verbs": {
                        v: {"msgs": c[0], "bytes": c[1]}
                        for v, c in st.verbs.items()
                    },
                    "send": st.send.to_dict(),
                    "deliver": st.deliver.to_dict(),
                }
                for (s, r), st in self._links.items()
            }

    def node_digests(self, node_id: str) -> Dict[str, dict]:
        """The links ``node_id`` originated — its heartbeat contribution.

        Each node reports only what IT sent; deliver histograms for those
        links (recorded receive-side) ride along, so the fleet monitor can
        attribute inbound latency to each link's DESTINATION without any
        node reporting twice.
        """
        with self._lock:
            return {
                f"{s}->{r}": {
                    "msgs": st.msgs,
                    "bytes": st.bytes,
                    "raw_bytes": st.raw_bytes,
                    "frame_bytes": st.frame_bytes,
                    "overhead_bytes": st.overhead_bytes,
                    "verbs": {
                        v: {"msgs": c[0], "bytes": c[1]}
                        for v, c in st.verbs.items()
                    },
                    "send": st.send.to_dict(),
                    "deliver": st.deliver.to_dict(),
                }
                for (s, r), st in self._links.items()
                if s == node_id
            }


def find_metered(van) -> Optional[MeteredVan]:
    """First MeteredVan in a wrapper stack (``.inner`` walk), or None."""
    seen = set()
    v = van
    while v is not None and id(v) not in seen:
        seen.add(id(v))
        if isinstance(v, MeteredVan):
            return v
        v = getattr(v, "inner", None)
    return None
