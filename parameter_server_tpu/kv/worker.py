"""KVWorker: the classic Push/Pull facade with timestamps.

API parity with the reference worker (north-star requirement): ``push`` /
``pull`` return an integer timestamp; ``wait(ts)`` blocks; pulls deliver
values aligned with the request's key positions.  (Reference:
``src/parameter/parameter.h`` :: ``Parameter::Push/Pull/Wait`` [U].)

Pipeline per call (SURVEY.md §3.2 hot path, TPU mapping):

1. host: ``localize_to_slots`` — dedup keys, map to unique row slots
   (deterministic ``HashLocalizer`` for multi-worker consistency).
   Computed once a batch: :meth:`KVWorker._localize` keeps the newest
   localization of each table and hands it back when it is asked for the
   same keys again, so a step's push reuses what its pull computed.
2. device: ``segment_combine`` duplicate positions (push only) — the
   worker-side pre-reduction.  With a :class:`~parameter_server_tpu.kv.
   routing.WorkerGroup` (ISSUE 15) this is also where the GROUP
   pre-reduction hangs: members hand their combined planes to the elected
   leader, which reduces them (``core/coalesce.py::GroupReducer`` — an XLA
   ``psum`` over a shared mesh where one exists, a deterministic
   sorted-union merge over the loopback topology) so only ONE reduced
   tensor crosses the wire per group per step.
3. host: ``RoutingTable.slice_ids`` — split the sorted slot segment per
   OWNING server (the reference's ``Parameter::Slice``, but against the
   epoch-versioned routing table of PR 6, so ranges can move at runtime).
4. Van: one request per server; responses complete the timestamp.

Routing fences (PR 6): every wire leg is stamped with the worker's routing
epoch (``__repoch__``).  A server holding a different table generation
answers with a typed ``__fenced__`` error carrying its own table; the
``*_sync`` paths adopt the highest-epoch table seen and retry exactly the
rejected positions — **rejected, not lost**.  Fire-and-forget ``push()``
cannot observe replies, so during live migration use :meth:`push_sync`
(which is what ``learner/elastic.py`` trains through).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.config import GroupConfig, TableConfig, TraceConfig
from parameter_server_tpu.core import flightrec
from parameter_server_tpu.core.coalesce import GroupReducer
from parameter_server_tpu.core.tracectx import TRACE_KEY, sampled
from parameter_server_tpu.core.messages import (
    Message,
    Task,
    TaskKind,
    node_index,
    server_id,
)
from parameter_server_tpu.core.postoffice import Customer, Postoffice, VanError
from parameter_server_tpu.kv.cache import HotRowCache
from parameter_server_tpu.kv.partition import RangePartition
from parameter_server_tpu.kv.routing import (
    BUSY_KEY,
    CONSIST_STEP_KEY,
    FENCED_KEY,
    GROUP_KEY,
    READ_ONLY_KEY,
    ROUTING_EPOCH_KEY,
    ROUTING_KEY,
    VERSION_KEY,
    WAIT_KEY,
    RoutingTable,
    WorkerGroup,
)
from parameter_server_tpu.ops import scatter
from parameter_server_tpu.utils.keys import (
    HashLocalizer,
    flat_keys,
    leg_bucket,
    localize_engine,
    localize_to_slots,
)
from parameter_server_tpu.utils.platform import role_device
from parameter_server_tpu.utils.trace import (
    NULL_TRACER,
    LatencyHistogram,
    Tracer,
    req_id,
)


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _segment_combine(inverse, values, num_rows: int):
    with jax.named_scope("ps.worker.combine"):
        return scatter.segment_combine(values, inverse, num_rows)


@functools.partial(jax.jit, static_argnames=("n_slots", "dim", "dtype"))
def _assemble_device(positions, rows, inverse, *, n_slots: int, dim: int, dtype):
    """Rows of a pull's legs -> one row a requested position, on device:
    every leg's rows scattered to their slots, then gathered by
    ``inverse``.  A leg's rows arrive padded to its bucket; its positions
    are padded with ``n_slots``, which the scatter drops."""
    with jax.named_scope("ps.worker.assemble"):
        uniq = jnp.zeros((n_slots, dim), dtype)
        for pos, leg in zip(positions, rows):
            uniq = uniq.at[pos].set(
                leg.astype(dtype).reshape(-1, dim), mode="drop"
            )
        return jnp.take(uniq, inverse, axis=0)


@jax.jit
def _gather_rows(plane, inverse):
    """``plane[inverse]`` on device: a pull's unique rows -> one row a
    requested position, in ``inverse``'s shape.  One program a (bucketed
    plane, key shape) pair: the plane is never a leg's true row count."""
    with jax.named_scope("ps.worker.assemble"):
        return jnp.take(plane, inverse, axis=0, mode="clip")


@jax.jit
def _take_rows(plane, idx):
    """``plane[idx]`` on device, zeros where ``idx`` is past the plane: a
    leg of a device push, padded to its bucket."""
    with jax.named_scope("ps.worker.submit"):
        return jnp.take(plane, idx, axis=0, mode="fill", fill_value=0)


def _pad_index(idx: np.ndarray, size: int, fill: int) -> np.ndarray:
    out = np.full(size, fill, np.int32)
    out[: idx.shape[0]] = idx
    return out


class _Localized(NamedTuple):
    """What :meth:`KVWorker._localize` computed last for a table, and from
    what: a private copy of the flat keys, the localizer, ``min_bucket``."""

    keys: np.ndarray
    localizer: object
    min_bucket: int
    slots: np.ndarray
    inverse: np.ndarray


class KVWorker(Customer):
    def __init__(
        self,
        post: Postoffice,
        table_cfgs: Dict[str, TableConfig],
        num_servers: int,
        *,
        name: str = "kv",
        localizers: Optional[Dict[str, HashLocalizer]] = None,
        min_bucket: int = 256,
        tracer: Tracer = NULL_TRACER,
        retry_on_timeout: bool = True,
        routing: Optional[RoutingTable] = None,
        max_fence_retries: int = 8,
        fence_backoff: float = 0.02,
        cache: Optional[HotRowCache] = None,
        group: Optional[WorkerGroup] = None,
        group_cfg: Optional[GroupConfig] = None,
        trace: Optional[TraceConfig] = None,
    ) -> None:
        """``retry_on_timeout``: when a pull's deadline expires (dead or
        mid-promotion server), cancel the stuck task and re-issue it ONCE
        against the same server identity — by then
        :class:`~parameter_server_tpu.kv.replica.ReplicaSet` has typically
        rebound ``S{i}`` to the promoted standby, so the retry lands on live
        state and training continues without surfacing the death.

        ``routing``: initial routing table (defaults to the uniform epoch-0
        split).  The worker converges to newer tables lazily off fence
        rejects and eagerly off scheduler ROUTING broadcasts (wire either
        into :meth:`adopt_routing`).

        ``cache``: a :class:`~parameter_server_tpu.kv.cache.HotRowCache`
        turns this worker into a serving node (ISSUE 13): :meth:`pull_serve`
        answers hot keys locally, every stamped reply refreshes the cache's
        invalidation watermark, and routing adoption drops all entries.

        ``group``: a :class:`~parameter_server_tpu.kv.routing.WorkerGroup`
        this worker belongs to (ISSUE 15).  Pushes then pre-reduce across
        the group and only the elected leader's reduced tensor crosses the
        wire — see :meth:`push` / :meth:`push_sync`.  ``group_cfg`` tunes
        fallback/reduce behaviour (defaults to ``GroupConfig`` matched to
        the group's size and election mode)."""
        super().__init__(name, post)
        #: the chip this worker computes on (gradient step, duplicate
        #: pre-combine, device-side pull assembly): workers of an in-process
        #: cluster spread over the host's chips by their node index
        self.device = role_device(node_index(post.node_id))
        #: span recorder (``utils/trace.py``): its ``ps.worker.*`` spans reach
        #: a capturing profiler session whether or not it is enabled
        self.tracer = tracer
        self.table_cfgs = table_cfgs
        self.num_servers = num_servers
        self.min_bucket = min_bucket
        self.retry_on_timeout = retry_on_timeout
        self.max_fence_retries = max_fence_retries
        self.fence_backoff = fence_backoff
        self.routing = routing or RoutingTable.uniform(table_cfgs, num_servers)
        self._routing_lock = threading.Lock()
        #: legacy uniform split, kept for introspection/compat — routing
        #: decisions now go through ``self.routing``
        self.partitions = {
            t: RangePartition(cfg.rows, num_servers) for t, cfg in table_cfgs.items()
        }
        self.localizers = localizers or {
            t: HashLocalizer(cfg.rows) for t, cfg in table_cfgs.items()
        }
        #: the newest localization computed for each table
        #: (:meth:`_localize`).  One entry a table, swapped whole: a second
        #: thread sees the old or the new
        self._localized: Dict[str, _Localized] = {}
        #: localizations computed (of those, by the native pass of
        #: ``utils/keys.py``) / handed back from ``_localized``
        self.localize_computed = 0
        self.localize_native = 0
        self.localize_reused = 0
        #: per-timestamp reassembly info for pulls
        self._pull_plans: Dict[int, dict] = {}
        #: (n_slots, dim, dtype) -> [host plane, device arrays that last read
        #: it]: the staging plane host replies are laid into before their
        #: one upload (:meth:`_assemble_host_replies`), reused across pulls
        #: of a bucket so no pull faults fresh megabytes in
        self._stage: Dict[tuple, list] = {}
        self._stage_lock = threading.Lock()
        #: where pulls were assembled / how many pushes were combined from a
        #: gradient that arrived on the chip (:meth:`counters`)
        self.pull_assembled_device = 0
        self.pull_assembled_host = 0
        self.push_combined_from_device = 0
        #: deadline-retry counters (surfaced next to transport counters)
        self.pull_retries = 0
        self.push_retries = 0
        #: fence-driven routing refresh retries (the "rejected, not lost"
        #: loop re-submitting fenced positions under the adopted table)
        self.refresh_retries = 0
        #: cross-node trace ids (see :meth:`_trace_ctx`)
        self._trace_seq = itertools.count()
        # -- sampled request tracing (ISSUE 18) ------------------------------
        #: sampling policy; requests whose hashed id misses the 1-in-N
        #: sample carry NO trace context (zero wire bytes)
        self.trace = trace or TraceConfig()
        self._trace_lock = threading.Lock()
        #: tid -> [t0_mono, legs outstanding]; the span tree closes (and the
        #: e2e latency records) when the last leg's ack returns.  Bounded:
        #: oldest entries are evicted so a lost ack can never leak memory.
        self._trace_pending: Dict[str, list] = {}
        #: end-to-end request latency across sampled requests (submit ->
        #: last ack), exported as ``trace.e2e`` via :meth:`latency_digests`
        self._trace_e2e = LatencyHistogram()
        #: sampled requests stamped / span trees closed (Dashboard-mergeable)
        self.trace_samples = 0
        self.trace_closed = 0
        # -- staleness observability (ISSUE 10) ------------------------------
        #: highest server version this worker's own pushes have been acked
        #: at, per (table, server) — the baseline update lag is measured from
        self._last_push_version: Dict[Tuple[str, str], int] = {}
        #: update-lag distributions per (table, server), in VERSIONS (the
        #: histogram's seconds axis reused as a unitless count axis)
        self._staleness: Dict[Tuple[str, str], LatencyHistogram] = {}
        self._staleness_lock = threading.Lock()
        #: total lag samples recorded (Dashboard-mergeable gauge)
        self.staleness_samples = 0
        # -- device-plane backpressure (ISSUE 12) ----------------------------
        #: total ``__busy__``-hinted acks seen (Dashboard-mergeable)
        self.busy_hints = 0
        #: monotonic stamp of the last busy hint per server — the admission
        #: signal a throttling training loop polls via :meth:`server_busy`
        self._busy_last: Dict[str, float] = {}
        # -- read-heavy serving plane (ISSUE 13) -----------------------------
        #: hot-row cache; None = this worker does not serve reads
        self.cache = cache
        #: table -> (TableRouting identity, per-segment owner-code vector);
        #: memoizes the serve path's owner interning per adopted routing
        self._serve_codes: Dict[str, tuple] = {}
        # -- hierarchical push (ISSUE 15) ------------------------------------
        #: group membership; None (or size 1) = direct pushes
        self._group = group if (group is not None and group.size > 1) else None
        if self._group is not None:
            if self.post.node_id not in self._group.members:
                raise ValueError(
                    f"{self.post.node_id} is not a member of group "
                    f"{self._group.gid}"
                )
            if group_cfg is not None and group_cfg.size != self._group.size:
                raise ValueError(
                    f"group_cfg.size={group_cfg.size} != group size "
                    f"{self._group.size}"
                )
            self._group_cfg = group_cfg or GroupConfig(
                size=self._group.size, election=self._group.election
            )
            #: EF interaction with the quantized wire plane (ISSUE 14):
            #: rotation would move the residual owner every step, so group
            #: frames bypass the codec; fixed election pins one leader,
            #: whose (sender, table) store then owns the group's residual
            self._group_ef = (
                "leader" if self._group.election == "fixed" else "bypass"
            )
            #: every member carries a reducer — any of them can be elected
            self._group_reducer: Optional[GroupReducer] = GroupReducer(
                self._group.size,
                node=self.post.node_id,
                mode=self._group_cfg.reduce,
            )
        else:
            self._group_cfg = None
            self._group_ef = None
            self._group_reducer = None
        self._group_lock = threading.Lock()
        #: per-table local step counter keying leader election — members
        #: advance in lockstep (the data-parallel training contract); skew
        #: degrades to the timeout fallback, never to loss
        self._group_steps: Dict[str, int] = {}
        #: (table, step) -> Event set by the done notify (sync waiters)
        self._group_events: Dict[Tuple[str, int], threading.Event] = {}
        #: group counters (Dashboard-mergeable via :meth:`counters`)
        self.group_pushes = 0  # reduced wire pushes sent (as leader)
        self.group_reduced_fanin = 0  # member contributions those carried
        self.group_contribs = 0  # contributions sent (as member)
        self.group_fallbacks = 0  # degradations to direct push
        self.group_done_recv = 0  # done notifies applied
        self.group_handoffs = 0  # fence re-elections handed to a new leader
        # -- consistency plane (ISSUE 20) ------------------------------------
        #: per-table committed step — how many :meth:`push_sync` calls for
        #: the table fully completed.  This is the ``__cstep__`` value
        #: stamped onto gated PUSH/PULL traffic (tables whose
        #: ``TableConfig.consistency`` is set); servers fold it into their
        #: fleet vector clock and gate against the configured bound.
        self._consist_steps: Dict[str, int] = {}
        self._consist_lock = threading.Lock()
        #: ``__wait__`` defers received / pulls shed to the stale cache /
        #: requests forced through ungated past the gate deadline
        #: (Dashboard-mergeable via :meth:`counters`)
        self.consist_waits = 0
        self.consist_sheds = 0
        self.consist_forced = 0
        #: time parked on consistency gates (first defer -> admitted),
        #: exported as ``consist.gate_wait`` via :meth:`latency_digests` —
        #: the gate-wait-p99 SLO's series (utils/slo.py
        #: consistency_plane_specs)
        self._gate_hist = LatencyHistogram()

    def _serve_owner_codes(self, table: str, tr, cache) -> np.ndarray:
        """Owner :meth:`HotRowCache.server_code` per segment of ``tr``.

        Identity-keyed memo: :meth:`adopt_routing` replaces routing objects
        wholesale, so ``ent[0] is tr`` is exact — no epoch bookkeeping.
        """
        ent = self._serve_codes.get(table)
        if ent is not None and ent[0] is tr:
            return ent[1]
        codes = np.asarray(
            [cache.server_code(server_id(int(o))) for o in tr.owners],
            dtype=np.int32,
        )
        self._serve_codes[table] = (tr, codes)
        return codes

    # -- routing --------------------------------------------------------------
    def adopt_routing(self, routing) -> bool:
        """Adopt a routing table iff it is NEWER than what this worker holds.

        Accepts a :class:`RoutingTable` or its wire payload (the form riding
        fence replies and scheduler broadcasts).  Highest epoch wins without
        coordination: a fence carrying an older table — possible for a
        bounded moment mid-broadcast — is simply ignored, and the backoff in
        the retry loops outlasts the broadcast window.
        """
        if routing is None:
            return False
        if isinstance(routing, dict):
            routing = RoutingTable.from_payload(routing)
        with self._routing_lock:
            if routing.epoch <= self.routing.epoch:
                adopted = False
            else:
                self.routing = routing
                adopted = True
        if adopted and self.cache is not None:
            # serving plane: entries are keyed by owner, so most would miss
            # anyway (owner changed) — but a range that moved AND moved back
            # across epochs could alias, so adoption drops everything.
            self.cache.invalidate_all(reason="routing-epoch")
        if adopted:
            # quantized wire plane: error-feedback residuals describe error
            # owed to the OLD owners of each key range — after a migration
            # they would replay stale error into the new owner's rows.
            from parameter_server_tpu.core.filters import find_quantizers

            van = getattr(self.post, "van", None)
            if van is not None:
                for codec in find_quantizers(van):
                    codec.reset_residuals(
                        sender=self.post.node_id, reason="adopt_routing"
                    )
        return adopted

    def counters(self) -> dict:
        """Retry counters, Dashboard-mergeable (utils.metrics)."""
        out = {
            "pull_retries": self.pull_retries,
            "push_retries": self.push_retries,
            "refresh_retries": self.refresh_retries,
            "staleness_samples": self.staleness_samples,
            "busy_hints": self.busy_hints,
            "trace_samples": self.trace_samples,
            "trace_closed": self.trace_closed,
            "pull_assembled_device": self.pull_assembled_device,
            "pull_assembled_host": self.pull_assembled_host,
            "push_combined_from_device": self.push_combined_from_device,
            "localize_computed": self.localize_computed,
            "localize_native": self.localize_native,
            "localize_reused": self.localize_reused,
        }
        if self._group is not None:
            out.update(
                {
                    "group_pushes": self.group_pushes,
                    "group_reduced_fanin": self.group_reduced_fanin,
                    "group_contribs": self.group_contribs,
                    "group_fallbacks": self.group_fallbacks,
                    "group_done_recv": self.group_done_recv,
                    "group_handoffs": self.group_handoffs,
                }
            )
        if self.cache is not None:
            out.update(self.cache.counters())
        with self._consist_lock:
            if self.consist_waits or self._consist_steps:
                # consistency plane (ISSUE 20): defer/shed/force totals plus
                # the committed-step gauge (sum over gated tables)
                out["consist_waits"] = self.consist_waits
                out["consist_sheds"] = self.consist_sheds
                out["consist_forced"] = self.consist_forced
                # combined degradation counter: the shed-rate SLO watches
                # one cumulative series for "the gate deadline fired"
                out["consist_degraded"] = (
                    self.consist_sheds + self.consist_forced
                )
                out["consist_step"] = sum(self._consist_steps.values())
        return out

    def server_busy(self, server: str, within_s: float = 1.0) -> bool:
        """True if ``server`` stamped ``__busy__`` onto an ack within the
        last ``within_s`` seconds — the soft-backpressure poll a throttling
        training loop consumes (the hint is advisory: pushes were applied)."""
        with self._staleness_lock:
            t = self._busy_last.get(server)
        return t is not None and (time.monotonic() - t) <= within_s

    # -- staleness observability (ISSUE 10) -----------------------------------
    def _on_response(self, msg) -> None:
        """Tap every data reply for the server's ``__sver__`` version stamp.

        Runs on the recv thread for push AND pull replies — including
        fire-and-forget pushes whose bodies ``submit`` drops — so the
        version bookkeeping is uniform across sync and async training.
        PUSH acks advance this worker's last-pushed version for that
        (table, server); PULL replies record ``server_version -
        last_pushed_version`` — how many fleet updates the pulled ranges
        have seen since this worker last contributed — into a per-range
        histogram.  Cheap (two dict ops) and fail-safe: the super() call
        that completes the task always runs.
        """
        try:
            payload = msg.task.payload
            tctx = payload.get(TRACE_KEY)
            if tctx is not None and isinstance(tctx, dict):
                # sampled request tracing (ISSUE 18): the server echoed the
                # context back on this ack/reply — this leg's return closes
                # part of the span tree; the LAST leg records end-to-end
                # latency and emits the closure event postmortem anchors on
                tid = tctx.get("tid")
                done = e2e = None
                if tid is not None:
                    with self._trace_lock:
                        ent = self._trace_pending.get(tid)
                        if ent is not None:
                            ent[1] -= 1
                            if ent[1] <= 0:
                                self._trace_pending.pop(tid, None)
                                e2e = time.monotonic() - ent[0]
                                self._trace_e2e.record(max(e2e, 0.0))
                                self.trace_closed += 1
                                done = True
                    if done:
                        flightrec.record(
                            "trace.ack",
                            tid=tid,
                            node=self.post.node_id,
                            sender=msg.sender,
                            fenced=bool(payload.get(FENCED_KEY)),
                            e2e_ms=round(e2e * 1e3, 3),
                        )
            if payload.get(BUSY_KEY):
                # device-plane soft backpressure (ISSUE 12): the server's
                # ApplyLedger backlog exceeded its bound when this ack was
                # stamped.  Count + timestamp; :meth:`server_busy` reads it.
                with self._staleness_lock:
                    self.busy_hints += 1
                    self._busy_last[msg.sender] = time.monotonic()
            sver = payload.get(VERSION_KEY)
            table = payload.get("table")
            if sver is not None and table is not None:
                if self.cache is not None:
                    # serving plane: EVERY stamped reply — push ack, pull
                    # reply, and (ISSUE 13) fence reject — raises the
                    # cache-invalidation watermark for (table, server)
                    self.cache.observe(table, msg.sender, int(sver))
                key = (table, msg.sender)
                if payload.get(FENCED_KEY):
                    # fence: the request was REJECTED, so the stamp must not
                    # advance last-push bookkeeping (the push never applied)
                    # nor count as a served-pull staleness sample — it only
                    # feeds the watermark above
                    pass
                else:
                    with self._staleness_lock:
                        if msg.task.kind == TaskKind.PUSH:
                            prev = self._last_push_version.get(key, 0)
                            if sver > prev:
                                self._last_push_version[key] = int(sver)
                        elif msg.task.kind == TaskKind.PULL:
                            last = self._last_push_version.get(key)
                            if last is not None:
                                hist = self._staleness.get(key)
                                if hist is None:
                                    hist = self._staleness[key] = (
                                        LatencyHistogram()
                                    )
                                hist.record(float(max(int(sver) - last, 0)))
                                self.staleness_samples += 1
        except Exception:  # noqa: BLE001 — observability must never lose
            pass  # the reply itself
        super()._on_response(msg)

    def staleness_digests(self) -> Dict[str, dict]:
        """Cumulative update-lag digests, named for the telemetry plane.

        ``staleness.<table>`` merges every server's distribution (the
        SLO-able fleet series, e.g. ``SloSpec("staleness.w", 8,
        source="p99", p99_scale=1)``); ``staleness.<table>@<server>`` keeps
        the per-key-range split for diagnosis.  Digests are cumulative and
        monotone — ``TelemetryPublisher`` delta-encodes them.
        """
        with self._staleness_lock:
            per_range = {
                f"staleness.{t}@{s}": h.to_dict()
                for (t, s), h in self._staleness.items()
            }
            merged: Dict[str, LatencyHistogram] = {}
            for (t, _s), h in self._staleness.items():
                agg = merged.get(t)
                if agg is None:
                    agg = merged[t] = LatencyHistogram()
                agg.merge(h)
        out = {f"staleness.{t}": h.to_dict() for t, h in merged.items()}
        out.update(per_range)
        return out

    def latency_digests(self) -> Dict[str, dict]:
        """Tracing-plane digests for the telemetry publisher (ISSUE 18).

        ``trace.e2e`` is submit → last-ack latency across sampled requests
        — the denominator ``tools/critpath.py`` attributes into plane
        segments.  Cumulative and monotone, same contract as the server's
        :meth:`~parameter_server_tpu.kv.server.KVServer.latency_digests`.
        """
        out = {}
        with self._trace_lock:
            if self._trace_e2e.count:
                out["trace.e2e"] = self._trace_e2e.to_dict()
        with self._consist_lock:
            if self._gate_hist.count:
                # consistency plane (ISSUE 20): seconds parked on gates
                out["consist.gate_wait"] = self._gate_hist.to_dict()
        return out

    # -- consistency plane (ISSUE 20) -----------------------------------------
    def consist_step(self, table: str) -> int:
        """This worker's committed step for ``table`` (completed pushes)."""
        with self._consist_lock:
            return self._consist_steps.get(table, 0)

    def _consist_commit(self, table: str) -> int:
        with self._consist_lock:
            s = self._consist_steps.get(table, 0) + 1
            self._consist_steps[table] = s
            return s

    def _gated(self, table: str) -> bool:
        return self.table_cfgs[table].consistency is not None

    @staticmethod
    def _scan_waits(responses, order) -> Tuple[list, list, list, float]:
        """Split out typed ``__wait__`` consistency defers (ISSUE 20).

        Wait replies are fence-SHAPED (they carry ``__fenced__`` too, for
        old workers) but are not fences: routing is fine, the sender just
        ran too far ahead of the fleet minimum.  Returns ``(rest, waits,
        waited position arrays, max retry_after hint)`` so the retry loops
        can park on the gate budget instead of burning fence retries.
        """
        rest, waits, pos, retry = [], [], [], 0.0
        for resp in responses:
            p = resp.task.payload
            if p.get(WAIT_KEY):
                waits.append(resp)
                pos.append(order[resp.sender])
                retry = max(retry, float(p.get("retry_after") or 0.0))
            else:
                rest.append(resp)
        return rest, waits, pos, retry

    @staticmethod
    def _scan_fences(responses, order) -> Tuple[list, set, List[np.ndarray]]:
        """Split a completed task's responses into (data, fenced senders,
        fenced position arrays)."""
        data, senders, fenced = [], set(), []
        for resp in responses:
            if resp.task.payload.get(FENCED_KEY):
                senders.add(resp.sender)
                fenced.append(order[resp.sender])
            else:
                data.append(resp)
        return data, senders, fenced

    @staticmethod
    def _real_errors(errs, fenced_senders) -> list:
        """Errors minus the typed fence rejects (recorded as 'S0: <err>')."""
        return [
            e
            for e in errs
            if not any(e.startswith(f"{s}: ") for s in fenced_senders)
        ]

    def _adopt_from(self, responses) -> None:
        for resp in responses:
            if resp.task.payload.get(FENCED_KEY):
                self.adopt_routing(resp.task.payload.get(ROUTING_KEY))

    def _trace_ctx(self) -> Optional[dict]:
        """Fresh trace context for one logical request — or ``None``.

        ``None`` means the request missed the deterministic hash sample
        (``core/tracectx.py``): no context is stamped, no ``__trace__``
        payload key exists, zero trace bytes ride the wire, and the int-only
        fast meta codec stays eligible.  A sampled request gets a dict
        stamped into ``Task.payload["__trace__"]`` of every wire leg and
        recorded as a ``trace`` attr on this worker's span; the receiving
        van stamps ``rx``, the server adds dispatch/reply stamps and echoes
        the context back on acks, so ``tools/merge_traces.py`` +
        ``tools/critpath.py`` can stitch one cross-node timeline.  The id is
        unique per (node, customer, request) — no coordination needed
        across nodes, and the sampling decision is a pure function of
        ``(tid, seed)`` so replays sample the same requests.
        """
        tid = f"{self.post.node_id}/{self.name}/{next(self._trace_seq)}"
        if not self.trace.enabled or not sampled(
            tid, self.trace.seed, self.trace.sample_every
        ):
            return None
        return {
            "tid": tid,
            "origin": self.post.node_id,
            "customer": self.name,
            "t": time.monotonic(),
        }

    def _trace_submitted(self, tctx: dict, op: str, legs: int) -> None:
        """Bookkeep one sampled submit: ``legs`` acks close the span tree.

        A ``None`` tctx (unsampled request) is a no-op — the whole body
        sits behind the sampling gate, a contract ``tools/check_wrappers.py``
        enforces statically (``TRACE_GATED_FUNCS``).
        The pending map is bounded — the oldest entry is evicted when full,
        so a reply that never returns (dead server past the resend budget)
        degrades to a missing e2e sample, never to leaked memory.  The
        orphan still shows in flightrec: ``trace.submit`` with no matching
        ``trace.ack`` is exactly what ``tools/postmortem.py`` anchors on.
        """
        if tctx is not None:
            with self._trace_lock:
                self.trace_samples += 1
                while len(self._trace_pending) >= 4096:
                    self._trace_pending.pop(next(iter(self._trace_pending)))
                self._trace_pending[tctx["tid"]] = [tctx["t"], int(legs)]
            flightrec.record(
                "trace.submit",
                tid=tctx["tid"],
                op=op,
                node=self.post.node_id,
                legs=int(legs),
                t0_s=tctx["t"],
            )

    # -- hierarchical push (ISSUE 15) ----------------------------------------
    def _group_push(
        self,
        table: str,
        slots: np.ndarray,
        combined: np.ndarray,
        *,
        sync: bool,
        timeout: Optional[float],
    ) -> int:
        """Route one prepared push through the group: elect, then either
        lead the rendezvous or contribute to the elected leader.

        Returns the submit timestamp of whatever leg THIS member sent this
        step (the reduced wire push when leading and the set completed
        locally, the contribution otherwise); ``-1`` when the leader is
        still waiting on members (the completing deposit issues the wire
        push from its own thread).
        """
        step = self._group_step_next(table)
        leader = self._group.leader(table, step)
        flightrec.record(
            "group.elect",
            node=self.post.node_id,
            table=table,
            step=step,
            leader=leader,
            size=self._group.size,
        )
        # flush rendezvous sets a dead/skewed member stranded (partial
        # reduction — the contributions that DID arrive are never lost)
        self._group_gc_stale()
        if leader == self.post.node_id:
            return self._group_lead(
                table, step, slots, combined, sync=sync, timeout=timeout
            )
        return self._group_contribute(
            table, step, leader, slots, combined, sync=sync, timeout=timeout
        )

    def _group_step_next(self, table: str) -> int:
        with self._group_lock:
            step = self._group_steps.get(table, 0)
            self._group_steps[table] = step + 1
        return step

    def _group_event(self, table: str, step: int) -> threading.Event:
        with self._group_lock:
            ev = self._group_events.get((table, step))
            if ev is None:
                ev = self._group_events[(table, step)] = threading.Event()
        return ev

    def _group_pop_event(self, table: str, step: int) -> None:
        with self._group_lock:
            self._group_events.pop((table, step), None)

    def _group_lead(
        self, table, step, slots, combined, *, sync, timeout
    ) -> int:
        """Leader leg: deposit own contribution; push when the set
        completes; on member timeout flush a PARTIAL reduction (no loss)."""
        cfg = self._group_cfg
        ev = self._group_event(table, step) if sync else None
        done = self._group_reducer.deposit(
            table, step, self.post.node_id, slots, combined
        )
        ts = -1
        if done is not None:
            ts = self._group_wire_push(table, step, *done)
        if not sync:
            return ts
        try:
            # the degradation decision runs on the group's own clock
            # (fallback_timeout), not the caller's push deadline — chaos
            # runs stay deterministic whatever timeout the test passes
            if not ev.wait(cfg.fallback_timeout):
                part = self._group_reducer.take(table, step)
                if part is not None:
                    if cfg.fallback == "none":
                        raise TimeoutError(
                            f"group push of {table!r} step {step}: members "
                            f"missing and fallback='none'"
                        )
                    with self._group_lock:
                        self.group_fallbacks += 1
                    flightrec.record(
                        "group.fallback",
                        node=self.post.node_id,
                        table=table,
                        step=step,
                        reason="member_timeout",
                        fanin=part[2],
                    )
                    ts = self._group_wire_push(table, step, *part)
                # either way the wire push is now in flight (here or from
                # the completing deposit's thread); wait for its acks
                if not ev.wait(timeout if timeout is not None else cfg.fallback_timeout):
                    raise TimeoutError(
                        f"group push of {table!r} step {step} timed out"
                    )
            return ts
        finally:
            self._group_pop_event(table, step)

    def _group_contribute(
        self, table, step, leader, slots, combined, *, sync, timeout
    ) -> int:
        """Member leg: ship the combined plane to the leader as a CONTROL
        contribution (CoalescingVan passthrough — never bundled), degrade
        to a direct push if the leader is dead or partitioned."""
        cfg = self._group_cfg
        ev = self._group_event(table, step) if sync else None
        msg = Message(
            task=Task(
                TaskKind.CONTROL,
                self.name,
                payload={
                    GROUP_KEY: {
                        "op": "contrib",
                        "table": table,
                        "step": int(step),
                        "member": self.post.node_id,
                        "fanin": 1,
                    }
                },
            ),
            recver=leader,
            keys=np.asarray(slots).astype(np.int64, copy=False),
            values=[combined],
        )
        with self._group_lock:
            self.group_contribs += 1
        if not sync:
            cb = functools.partial(
                self._group_contrib_done, table, step, slots, combined
            )
            return self.submit([msg], callback=cb)
        ts = self.submit([msg], keep_responses=True)
        try:
            if not self.wait(ts, cfg.fallback_timeout):
                # partitioned leader (blackhole): fence the contribution so
                # a late delivery cannot double-apply, then push direct
                self.cancel(ts, "group leader deadline", remote=True)
                self.take_responses(ts)
                return self._group_fallback(
                    table, step, slots, combined,
                    reason="leader_timeout", sync=True, timeout=timeout,
                )
            errs = self.errors(ts)
            self.take_responses(ts)
            if errs:
                # dead leader: the send failed outright (undeliverable) or
                # its handler errored — the contribution was NOT absorbed
                return self._group_fallback(
                    table, step, slots, combined,
                    reason="dead_leader", sync=True, timeout=timeout,
                )
            # acked: the leader owns this gradient now.  Wait for the done
            # notify (which advances _last_push_version so staleness
            # accounting sees the group push as our own).  No fallback
            # after this point — re-pushing an absorbed gradient would
            # double-apply; a lost done notify only costs bookkeeping.
            ev.wait(timeout if timeout is not None else cfg.fallback_timeout)
            return ts
        finally:
            self._group_pop_event(table, step)

    def _group_contrib_done(self, table, step, slots, combined, responses):
        """Async-contribution callback: degrade on a dead leader."""
        ok = any(
            r.task.payload.get("__error__") is None for r in responses
        )
        if not ok:
            self._group_fallback(
                table, step, slots, combined,
                reason="dead_leader", sync=False, timeout=None,
            )

    def _group_fallback(
        self, table, step, slots, combined, *, reason, sync, timeout
    ) -> int:
        """Direct per-worker push of this member's own gradient — the
        same-step, no-loss degradation the group contract promises."""
        if self._group_cfg.fallback == "none":
            raise VanError(
                f"group push of {table!r} step {step}: leader unreachable "
                f"({reason}) and fallback='none'"
            )
        with self._group_lock:
            self.group_fallbacks += 1
        flightrec.record(
            "group.fallback",
            node=self.post.node_id,
            table=table,
            step=step,
            reason=reason,
        )
        if sync:
            return self._push_sync_prepared(table, slots, combined, timeout)
        ts, _ = self._submit_push(table, slots, combined)
        return ts

    def _group_gc_stale(self) -> None:
        """Flush rendezvous sets whose stragglers exceeded the timeout."""
        red = self._group_reducer
        if red is None or not red.pending():
            return
        for table, step, (keys, vals, fanin) in red.take_stale(
            self._group_cfg.fallback_timeout
        ):
            with self._group_lock:
                self.group_fallbacks += 1
            flightrec.record(
                "group.fallback",
                node=self.post.node_id,
                table=table,
                step=step,
                reason="stale_set",
                fanin=fanin,
            )
            self._group_wire_push(table, step, keys, vals, fanin)

    def _group_wire_push(
        self, table, step, keys, vals, fanin, attempt: int = 0,
        positions: Optional[np.ndarray] = None,
    ) -> int:
        """Push the reduced tensor, stamped as ONE logical group apply.

        Non-blocking by contract: this runs on driver threads, the
        endpoint recv thread (a completing deposit), and the callback pool
        (fence retries) — blocking here on a same-endpoint reply would
        deadlock the LoopbackVan's single recv thread, so acks are handled
        by :meth:`_group_wire_done` via the submit callback.
        """
        stamp = {
            "id": self._group.gid,
            "n": int(fanin),
            "step": int(step),
            "ef": self._group_ef,
        }
        # hierarchical hop: the LEADER stamps a fresh context for the
        # reduced wire push — member contributions that fed it were local
        # to the group, so the cross-node chain starts here
        tctx = self._trace_ctx()
        routing = self.routing
        keys = np.asarray(keys)
        if positions is None:
            positions = np.arange(keys.shape[0], dtype=np.int64)
        sub = keys[positions]
        with self.tracer.span("ps.worker.submit") as sp:
            msgs, order = [], {}
            for s, rel, ids in routing.slice_ids(table, sub):
                abs_pos = positions[rel]
                order[server_id(s)] = abs_pos
                payload = {
                    "table": table,
                    ROUTING_EPOCH_KEY: routing.epoch,
                    GROUP_KEY: dict(stamp),
                }
                if tctx is not None:
                    payload[TRACE_KEY] = tctx
                msgs.append(
                    Message(
                        task=Task(TaskKind.PUSH, self.name, payload=payload),
                        recver=server_id(s),
                        keys=ids.astype(np.int32),
                        values=[vals[abs_pos]],
                    )
                )
            cb = functools.partial(
                self._group_wire_done, table, step, keys, vals, fanin,
                attempt, order,
            )
            # registered before the submit: the acks race the submit call
            self._trace_submitted(tctx, "group_push", len(msgs))
            with self.coalesce_window():
                ts = self.submit(msgs, callback=cb)
            sp.set(req=self._req(ts), legs=len(msgs))
        with self._group_lock:
            self.group_pushes += 1
            self.group_reduced_fanin += int(fanin)
        return ts

    def _group_wire_done(
        self, table, step, keys, vals, fanin, attempt, order, responses
    ) -> None:
        """Ack callback of a group wire push: adopt/re-elect on fences,
        then broadcast the done notify carrying the acked versions.

        Fence re-election (the ``push_many``/``push_sync`` contract): a
        fenced reduced push re-elects with ``salt=attempt+1`` — if the new
        leader is another member, the reduced subset is HANDED OFF so the
        retry load rotates; the handoff degrades to a local retry if that
        member is unreachable.
        """
        try:
            self._adopt_from(responses)
            data, _senders, fenced = self._scan_fences(responses, order)
            vers = {}
            for r in data:
                p = r.task.payload
                if p.get("__error__") is None:
                    sver = p.get(VERSION_KEY)
                    if sver is not None:
                        vers[r.sender] = int(sver)
            if fenced and attempt < self.max_fence_retries:
                pos = np.sort(np.concatenate(fenced))
                with self._group_lock:
                    self.refresh_retries += 1
                new_leader = self._group.leader(
                    table, step, salt=attempt + 1
                )
                flightrec.record(
                    "group.elect",
                    node=self.post.node_id,
                    table=table,
                    step=step,
                    leader=new_leader,
                    size=self._group.size,
                    salt=attempt + 1,
                    cause="fence",
                )
                if new_leader != self.post.node_id:
                    self._group_handoff(
                        new_leader, table, step, keys[pos], vals[pos],
                        fanin, attempt + 1,
                    )
                else:
                    self._group_wire_push(
                        table, step, keys, vals, fanin, attempt + 1,
                        positions=pos,
                    )
            if fenced:
                if vers:  # acked legs advance versions; retry notifies later
                    self._group_notify_done(table, step, vers, final=False)
            else:
                self._group_notify_done(table, step, vers, final=True)
        except Exception:  # noqa: BLE001 — a callback-thread error must not
            # strand the group's sync waiters silently un-notified forever
            flightrec.record(
                "group.fallback",
                node=self.post.node_id,
                table=table,
                step=step,
                reason="wire_done_error",
            )

    def _group_handoff(
        self, new_leader, table, step, keys, vals, fanin, attempt
    ) -> None:
        with self._group_lock:
            self.group_handoffs += 1
        msg = Message(
            task=Task(
                TaskKind.CONTROL,
                self.name,
                payload={
                    GROUP_KEY: {
                        "op": "handoff",
                        "table": table,
                        "step": int(step),
                        "fanin": int(fanin),
                        "attempt": int(attempt),
                    }
                },
            ),
            recver=new_leader,
            keys=np.asarray(keys).astype(np.int64, copy=False),
            values=[vals],
        )
        cb = functools.partial(
            self._group_handoff_done, table, step, keys, vals, fanin, attempt
        )
        self.submit([msg], callback=cb)

    def _group_handoff_done(
        self, table, step, keys, vals, fanin, attempt, responses
    ) -> None:
        ok = any(
            r.task.payload.get("__error__") is None for r in responses
        )
        if not ok:  # new leader unreachable too: retry the push locally
            self._group_wire_push(table, step, keys, vals, fanin, attempt)

    def _group_notify_done(self, table, step, vers, *, final) -> None:
        """Tell every member the group push landed (fire-and-forget).

        Carries the per-server acked versions so each member advances its
        OWN ``_last_push_version`` — the group push is one logical apply
        owned by the whole group, and the staleness plane (ISSUE 10) must
        measure every member's update lag from it, not just the leader's.
        """
        self._group_apply_done(table, step, vers, final)
        for m in self._group.members:
            if m == self.post.node_id:
                continue
            self.post.send(
                Message(
                    task=Task(
                        TaskKind.CONTROL,
                        self.name,
                        # fresh payload per leg (Loopback may alias them)
                        payload={
                            GROUP_KEY: {
                                "op": "done",
                                "table": table,
                                "step": int(step),
                                "vers": dict(vers),
                                "final": bool(final),
                            }
                        },
                    ),
                    recver=m,
                )
            )

    def _group_apply_done(self, table, step, vers, final) -> None:
        with self._staleness_lock:
            for server, sver in vers.items():
                key = (table, server)
                if int(sver) > self._last_push_version.get(key, 0):
                    self._last_push_version[key] = int(sver)
        with self._group_lock:
            self.group_done_recv += 1
            ev = self._group_events.get((table, int(step))) if final else None
        if ev is not None:
            ev.set()

    def handle_request(self, msg: Message) -> Optional[Message]:
        """Worker-to-worker group ops (ISSUE 15): contribution deposit,
        fence-retry handoff, done notify.  Anything else keeps the base
        behaviour (NotImplementedError -> typed ``__error__`` reply)."""
        payload = msg.task.payload
        grp = payload.get(GROUP_KEY) if isinstance(payload, dict) else None
        if grp is None or self._group is None:
            return super().handle_request(msg)
        op = grp.get("op")
        if op == "contrib":
            table, step = grp["table"], int(grp["step"])
            done = self._group_reducer.deposit(
                table,
                step,
                grp.get("member", msg.sender),
                msg.keys,
                msg.values[0],
                fanin=int(grp.get("fanin", 1)),
            )
            if done is not None:
                self._group_wire_push(table, step, *done)
            self._group_gc_stale()
            return msg.reply()
        if op == "handoff":
            self._group_wire_push(
                grp["table"],
                int(grp["step"]),
                msg.keys,
                msg.values[0],
                int(grp.get("fanin", 1)),
                attempt=int(grp.get("attempt", 0)),
            )
            return msg.reply()
        if op == "done":
            self._group_apply_done(
                grp["table"],
                int(grp["step"]),
                {k: int(v) for k, v in (grp.get("vers") or {}).items()},
                bool(grp.get("final", True)),
            )
            return None  # fire-and-forget: the sender tracks no task
        return super().handle_request(msg)

    # -- push ---------------------------------------------------------------
    def _submit_push(
        self,
        table: str,
        slots: np.ndarray,
        combined,
        positions: Optional[np.ndarray] = None,
        *,
        keep: bool = False,
        tctx: Optional[dict] = None,
        ungated: bool = False,
    ) -> Tuple[int, Dict[str, np.ndarray]]:
        """Wire one push of ``combined[positions]`` rows at global ids
        ``slots[positions]``; returns ``(ts, {server: positions})``.

        ``positions`` (absolute indices into ``slots``, ascending) defaults
        to all of them; fence retries pass only the rejected subset.
        ``ungated=True`` skips the consistency stamp (ISSUE 20) — the
        gate-deadline force-through path: the push bypasses the fleet gate
        rather than being dropped.
        """
        tctx = tctx if tctx is not None else self._trace_ctx()
        routing = self.routing  # one consistent table per submit
        with self.tracer.span("ps.worker.submit") as sp:
            if positions is None:
                positions = np.arange(slots.shape[0], dtype=np.int64)
            sub = slots[positions]
            # consistency plane (ISSUE 20): gated tables stamp the sender's
            # committed step (a plain int — the fast meta codec stays
            # eligible)
            cstep = (
                self.consist_step(table)
                if not ungated and self._gated(table)
                else None
            )
            msgs, order = [], {}
            for s, rel, ids in routing.slice_ids(table, sub):
                abs_pos = positions[rel]
                order[server_id(s)] = abs_pos
                payload = {
                    "table": table,
                    ROUTING_EPOCH_KEY: routing.epoch,
                }
                if cstep is not None:
                    payload[CONSIST_STEP_KEY] = cstep
                if tctx is not None:
                    payload[TRACE_KEY] = tctx
                msgs.append(
                    Message(
                        task=Task(TaskKind.PUSH, self.name, payload=payload),
                        recver=server_id(s),
                        keys=ids.astype(np.int32),
                        values=[self._leg_rows(combined, abs_pos)],
                    )
                )
            # register the span tree BEFORE the wire submit: replies race
            # the submit call (a fast peer can ack before submit() returns),
            # and a decrement that finds no pending entry would leak an open
            # tree
            self._trace_submitted(tctx, "push", len(msgs))
            # window: under a CoalescingVan the burst flushes at submit
            # exit (no flush-timer latency); nested inside push_many's
            # window it coalesces across tables instead
            with self.coalesce_window():
                ts = self.submit(msgs, keep_responses=keep)
            sp.set(req=self._req(ts), legs=len(msgs))
        return ts, order

    @staticmethod
    def _leg_rows(combined, abs_pos: np.ndarray):
        """One server's rows of a combined plane.  A device plane
        (``push_device``) is handed over padded with zero rows to the leg's
        bucket, the size the server pads to anyway: ``combined[abs_pos]`` on
        the device is half a dozen compiled programs a leg size."""
        if not isinstance(combined, jax.Array):
            return combined[abs_pos]
        return _take_rows(combined, _pad_index(
            abs_pos, leg_bucket(abs_pos.shape[0]), combined.shape[0]
        ))

    def _prepare_push(self, table: str, keys, values, root=None):
        """Worker half of a push: localize (:meth:`_localize`: reused where
        the keys are the last pull's), then combine duplicates on
        ``self.device``; the combined ``[slots, dim]`` plane comes back to
        the host for the wire.

        Where the gradient is combined from is read off ``values``: a
        ``jax.Array`` (a step's output) is placed on ``self.device`` (a
        no-op when it was computed there), cast and reshaped there, and
        never crosses to the host whole; anything else is made a NumPy
        array and uploaded.  The combine program and its operand shapes are
        the same either way, so the plane is bit-identical."""
        cfg = self.table_cfgs[table]
        on_device = isinstance(values, jax.Array)
        if on_device:
            vals = jax.device_put(values, self.device)
            vals = vals.astype(cfg.dtype).reshape(keys.size, cfg.dim)
        else:
            vals = np.asarray(values, dtype=cfg.dtype).reshape(
                keys.size, cfg.dim
            )
        slots, inverse = self._localize(table, keys, root)
        with self.tracer.span(
            "ps.worker.combine", unique=slots.shape[0],
            where="device" if on_device else "host",
        ) as sp:
            combined = np.asarray(
                _segment_combine(
                    jax.device_put(inverse, self.device),
                    vals if on_device else jax.device_put(vals, self.device),
                    slots.shape[0],
                )
            )
            sp.set(
                h2d_bytes=inverse.nbytes + (0 if on_device else vals.nbytes),
                d2h_bytes=combined.nbytes,
            )
        if on_device:
            self.push_combined_from_device += 1
        return slots, combined

    def _localize(
        self, table: str, keys, root=None
    ) -> Tuple[np.ndarray, np.ndarray]:
        """``(slots, inverse)`` of ``keys``: computed by
        ``localize_to_slots`` under ``ps.worker.localize``, or the table's
        newest computed pair handed back.

        A localization is a pure function of the keys (flattened, as
        ``uint64``: ``utils/keys.py::flat_keys``), the table's
        localizer and ``min_bucket``, and a localizer maps a key it has
        seen to the same row for ever.  So the call reuses the kept pair
        when all three are what it was computed from (the localizer by
        identity, the keys by content against a private copy: an equal
        array hits, a buffer refilled in place misses) and computes
        otherwise; what it computes replaces the table's entry.  One entry
        a table: a step's push reuses its pull's, and no batch is
        remembered past the next.  The pair is shared between a pull's
        plan and the push, so it is not writable.  ``root``, the request's
        root span, is told which it was (``localize="computed"`` /
        ``"reused"``); the ``ps.worker.localize`` span says which engine
        computed (``engine``: ``utils/keys.py::localize_engine``)."""
        loc = self.localizers[table]
        flat = flat_keys(keys)
        kept = self._localized.get(table)
        if (
            kept is not None
            and kept.localizer is loc
            and kept.min_bucket == self.min_bucket
            and np.array_equal(kept.keys, flat)
        ):
            self.localize_reused += 1
            how = "reused"
        else:
            with self.tracer.span(
                "ps.worker.localize", keys=int(flat.size)
            ) as sp:
                slots, inverse, n = localize_to_slots(
                    flat, loc, min_bucket=self.min_bucket
                )
                engine = localize_engine(loc)
                sp.set(unique=n, engine=engine)
            if engine == "native":
                self.localize_native += 1
            slots.flags.writeable = False
            inverse.flags.writeable = False
            kept = self._localized[table] = _Localized(
                flat.copy(), loc, self.min_bucket, slots, inverse
            )
            self.localize_computed += 1
            how = "computed"
        if root is not None:
            root.set(localize=how)
        return kept.slots, kept.inverse

    def _req(self, ts: int) -> str:
        """``req`` of this customer's task ``ts`` (``utils/trace.py``)."""
        return req_id(self.post.node_id, self.name, ts)

    def _wait_traced(
        self, ts: int, legs: int, timeout: Optional[float], retry: int = 0
    ) -> bool:
        """``self.wait(ts, timeout)`` under ``ps.worker.wait``."""
        with self.tracer.span(
            "ps.worker.wait", req=self._req(ts), legs=legs, retry=retry
        ):
            return self.wait(ts, timeout)

    def push(self, table: str, keys: np.ndarray, values: np.ndarray) -> int:
        """Push per-position gradient rows for ``keys``.  Returns timestamp.

        ``values`` has shape ``[len(keys), dim]`` (or ``[len(keys)]`` for
        dim=1 tables), as a NumPy array or as a ``jax.Array``: a device
        array is combined where it is and only the combined plane crosses to
        the host (:meth:`_prepare_push`).  Fire-and-forget: cannot observe
        routing fences — under live migration use :meth:`push_sync`.

        With a :class:`~parameter_server_tpu.kv.routing.WorkerGroup` the
        push routes through the group pre-reduction instead (ISSUE 15):
        non-leaders ship their combined plane to the elected leader, whose
        reduced tensor is the only PUSH on the wire; a dead leader
        degrades to a direct push via the submit callback (no loss).
        """
        tctx = self._trace_ctx()
        with self.tracer.span(
            "ps.worker.push", table=table, keys=int(keys.size),
            **({"trace": tctx["tid"]} if tctx is not None else {}),
        ) as root:
            slots, combined = self._prepare_push(table, keys, values, root)
            if self._group is not None:
                return self._group_push(
                    table, slots, combined, sync=False, timeout=None
                )
            ts, _ = self._submit_push(table, slots, combined, tctx=tctx)
            return ts

    def push_device(self, table: str, keys: np.ndarray, values) -> int:
        """Device-resident push: gradient rows never leave the device.

        Only the (small, int) keys are handled on the host; the value rows
        are a ``jax.Array`` that is duplicate-combined on device and sliced
        per server as device views.  Over the LoopbackVan those views flow
        to the server tables with no host round-trip — the SArray-zero-copy
        role of SURVEY §2 #19 in its TPU form.  (A cross-host Van serializes
        at its own boundary, which is where the reference copies too.)
        """
        tctx = self._trace_ctx()
        with self.tracer.span(
            "ps.worker.push", table=table, keys=int(keys.size),
            **({"trace": tctx["tid"]} if tctx is not None else {}),
        ) as root:
            cfg = self.table_cfgs[table]
            vals = values.reshape(keys.size, cfg.dim)
            slots, inverse = self._localize(table, keys, root)
            with self.tracer.span(
                "ps.worker.combine", unique=slots.shape[0], where="device",
                h2d_bytes=inverse.nbytes, d2h_bytes=0,
            ):
                combined = _segment_combine(
                    jnp.asarray(inverse), vals, slots.shape[0]
                )
            self.push_combined_from_device += 1
            ts, _ = self._submit_push(table, slots, combined, tctx=tctx)
            return ts

    def coalesce_window(self):
        """Context manager batching this worker's sends per destination.

        When the Postoffice's Van stack includes a
        :class:`~parameter_server_tpu.core.coalesce.CoalescingVan`, every
        message sent inside the window is bundled per server — a multi-table
        push pays the per-server frame overhead (pickle header, seq/ACK,
        filter pass) once.  A no-op (null context) on plain stacks, so
        callers never need to know what the Van is.
        """
        win = getattr(self.post.van, "window", None)
        return win() if callable(win) else contextlib.nullcontext()

    def push_many(
        self, updates: Dict[str, Tuple[np.ndarray, np.ndarray]]
    ) -> Dict[str, int]:
        """Push several tables' gradients in one coalescing window.

        ``updates``: ``{table: (keys, values)}``.  Returns ``{table: ts}``
        — one timestamp per table (responses from the same server must not
        share a ts), all of whose wire messages coalesce into one frame per
        server.  ``wait()`` each ts as usual.

        Group mode (ISSUE 15): each table elects its own leader (the crc32
        table offset in :meth:`~parameter_server_tpu.kv.routing.
        WorkerGroup.leader` de-phases them), and fenced rejects of any
        reduced push re-elect per table inside the ack callback.
        """
        with self.coalesce_window():
            return {
                t: self.push(t, keys, values)
                for t, (keys, values) in updates.items()
            }

    # -- pull ---------------------------------------------------------------
    def pull(self, table: str, keys: np.ndarray, *, read_only: bool = False) -> int:
        """Request weights for ``keys``; fetch with :meth:`pull_result`.

        ``read_only=True`` stamps the serving plane's ``__ro__`` flag: the
        server answers on the read-only fast path (ISSUE 13) — relaxed
        reads that may NOT observe writes coalesced into the same wire
        bundle.  Training pulls must keep the default.
        """
        return self._pull(table, keys, read_only=read_only)

    def _pull(self, table, keys, *, read_only: bool = False, root=None) -> int:
        slots, inverse = self._localize(table, keys, root)
        return self._submit_pull(
            table, slots, inverse, keys.shape, read_only=read_only
        )

    def _submit_pull(
        self,
        table,
        slots,
        inverse,
        shape,
        positions: Optional[np.ndarray] = None,
        *,
        read_only: bool = False,
        ungated: bool = False,
    ) -> int:
        tctx = self._trace_ctx()
        routing = self.routing
        with self.tracer.span("ps.worker.submit") as sp:
            if positions is None:
                positions = np.arange(slots.shape[0], dtype=np.int64)
            sub = slots[positions]
            msgs = []
            order = {}
            payload = {
                "table": table,
                ROUTING_EPOCH_KEY: routing.epoch,
            }
            # consistency plane (ISSUE 20): training pulls on gated tables
            # stamp the committed step so a lagging/ahead worker is gated at
            # the server.  Read-only serving pulls are NEVER gated — they are
            # the shed target — and ``ungated=True`` is the deadline
            # force-through (fresh data can never violate a staleness bound).
            if not read_only and not ungated and self._gated(table):
                payload[CONSIST_STEP_KEY] = self.consist_step(table)
            if tctx is not None:
                payload[TRACE_KEY] = tctx
            if read_only:
                payload[READ_ONLY_KEY] = True
            for s, rel, ids in routing.slice_ids(table, sub):
                abs_pos = positions[rel]
                order[server_id(s)] = abs_pos
                msgs.append(
                    Message(
                        # fresh dict per leg: payloads must never be shared
                        # across messages (a Loopback reply path may alias
                        # them)
                        task=Task(
                            TaskKind.PULL, self.name, payload=dict(payload)
                        ),
                        recver=server_id(s),
                        keys=ids.astype(np.int32),
                    )
                )
            # registered before the submit: the replies race the submit call
            self._trace_submitted(tctx, "pull", len(msgs))
            with self.coalesce_window():
                ts = self.submit(msgs, keep_responses=True)
            sp.set(req=self._req(ts), legs=len(msgs))
        self._pull_plans[ts] = {
            "order": order,
            "inverse": inverse,
            "n_slots": slots.shape[0],
            "shape": shape,
            "table": table,
            # retained so deadline/fence retries can re-issue subsets
            "slots": slots,
            "ro": read_only,
            "ungated": ungated,
        }
        return ts

    def _await_pull(self, ts: int, timeout: Optional[float]) -> tuple:
        """Wait for pull ``ts``; on deadline, cancel the stuck task and
        retry ONCE against the (possibly promoted) server identity.

        Returns ``(plan, responses, errs)`` with all kept state drained.
        """
        completed = self._wait_traced(
            ts, len(self._pull_plans[ts]["order"]), timeout
        )
        if not completed and self.retry_on_timeout:
            plan = self._pull_plans.pop(ts)
            # remote=True fences the dead pull at servers whose request leg
            # is still in flight — they drop it instead of computing a reply
            # nobody will read
            self.cancel(ts, "pull deadline", remote=True)
            self.take_responses(ts)  # responses of the dead task: drained
            self.pull_retries += 1
            pos = np.sort(np.concatenate(list(plan["order"].values())))
            ts = self._submit_pull(
                plan["table"],
                plan["slots"],
                plan["inverse"],
                plan["shape"],
                positions=pos,
                read_only=plan.get("ro", False),
                ungated=plan.get("ungated", False),
            )
            completed = self._wait_traced(
                ts, len(self._pull_plans[ts]["order"]), timeout, retry=1
            )
        plan = self._pull_plans.pop(ts)  # always reclaim, even on error paths
        errs = self.errors(ts)
        responses = self.take_responses(ts)  # always drain kept state
        if not completed:
            raise TimeoutError(f"pull ts={ts} timed out")
        return plan, responses, errs

    def _shed_pull_stale(self, plan: dict, pos: np.ndarray):
        """Answer the WAITED positions from the stale cache (ISSUE 20).

        The gate-deadline shed target: the PR 13 stale serving path,
        bounded by whatever ``__sver__`` each cached row's reply carried.
        Returns a synthetic ``(positions, rows, sver, "cache")`` pair, or
        None when any waited slot is uncached (the caller then forces an
        ungated pull — fresh data, never a dropped read).
        """
        cache = self.cache
        if cache is None:
            return None
        table = plan["table"]
        cfg = self.table_cfgs[table]
        grows = self.routing.tables[table].rows
        rows = np.zeros((int(pos.shape[0]), cfg.dim), dtype=cfg.dtype)
        sver = None
        for j, sl in enumerate(plan["slots"][pos].tolist()):
            if int(sl) >= grows:
                continue  # bucket pad: stays zero, matching the wire reply
            hit = cache.lookup_stale(table, int(sl))
            if hit is None:
                return None
            rows[j] = hit[0]
            sver = hit[1] if sver is None else min(sver, hit[1])
        return pos, rows, sver, "cache"

    def _gate_deadline_s(self, table: str) -> float:
        cfg = self.table_cfgs[table].consistency
        return cfg.gate_deadline_s if cfg is not None else 0.0

    def _gate_pause(self, table: str, retry_after: float) -> None:
        cfg = self.table_cfgs[table].consistency
        base = cfg.gate_retry_s if cfg is not None else 0.005
        with self.tracer.span(
            "ps.worker.gate_pause", retry_after_ms=1e3 * retry_after
        ):
            time.sleep(max(retry_after, base))

    def _pull_pairs(self, ts: int, timeout: Optional[float]) -> tuple:
        """Resolve pull ``ts`` into ``(plan, [(positions, rows, sver,
        sender)])``, looping over routing fences: fenced legs adopt the
        attached table and only their positions are re-pulled (under the
        NEW epoch).  ``sver``/``sender`` let :meth:`pull_serve` stamp cache
        inserts with the version EACH REPLY actually carried — never the
        watermark at insert time, which may have advanced concurrently.

        Consistency gates (ISSUE 20): ``__wait__`` defers are NOT fences —
        waited positions retry on the gate budget (``gate_deadline_s``,
        honoring the server's ``retry_after`` hint) without consuming
        fence retries.  Past the deadline the read degrades gracefully:
        shed to the stale cache when it covers the waited rows
        (``consist.shed``), else forced through ungated — counted, never
        dropped."""
        pairs: list = []
        first_plan = None
        attempt = 0  # fence budget only; gate waits ride their own clock
        gate_t0 = None
        forced = False
        ungated = False
        while attempt <= self.max_fence_retries:
            plan, responses, errs = self._await_pull(ts, timeout)
            if first_plan is None:
                first_plan = plan
                ungated = plan.get("ungated", False)
            self._adopt_from(responses)
            responses, waits, wait_pos, retry_after = self._scan_waits(
                responses, plan["order"]
            )
            data, fenced_senders, fenced = self._scan_fences(
                responses, plan["order"]
            )
            skip = fenced_senders | {r.sender for r in waits}
            real = self._real_errors(errs, skip)
            if real:  # a dropped leg must not read as zero weights
                raise VanError(f"pull ts={ts} failed on: " + "; ".join(real))
            if len(responses) + len(waits) < len(plan["order"]):
                raise VanError(
                    f"pull ts={ts} incomplete: {len(responses)}/"
                    f"{len(plan['order'])} servers answered (dead server?)"
                )
            pairs.extend(
                (
                    plan["order"][r.sender],
                    r.values[0],
                    r.task.payload.get(VERSION_KEY),
                    r.sender,
                )
                for r in data
            )
            if not fenced and not waits:
                if gate_t0 is not None:
                    with self._consist_lock:
                        self._gate_hist.record(
                            max(time.monotonic() - gate_t0, 0.0)
                        )
                return first_plan, pairs
            pending = list(fenced)
            if waits:
                with self._consist_lock:
                    self.consist_waits += len(waits)
                if gate_t0 is None:
                    gate_t0 = time.monotonic()
                table = first_plan["table"]
                deadline = self._gate_deadline_s(table)
                waited = np.sort(np.concatenate(wait_pos))
                if (
                    deadline > 0
                    and time.monotonic() - gate_t0 > deadline
                    and not forced
                ):
                    # graceful degradation: past the deadline the read
                    # sheds to the stale cache, else forces through
                    shed = self._shed_pull_stale(first_plan, waited)
                    with self._consist_lock:
                        self._gate_hist.record(
                            max(time.monotonic() - gate_t0, 0.0)
                        )
                    if shed is not None:
                        pairs.append(shed)
                        with self._consist_lock:
                            self.consist_sheds += 1
                        flightrec.record(
                            "consist.shed", node=self.post.node_id,
                            table=table, op="pull", how="stale-cache",
                            n=int(waited.shape[0]),
                        )
                        if not fenced:
                            return first_plan, pairs
                    else:
                        forced = ungated = True
                        pending.append(waited)
                        with self._consist_lock:
                            self.consist_forced += 1
                        flightrec.record(
                            "consist.shed", node=self.post.node_id,
                            table=table, op="pull", how="forced",
                            n=int(waited.shape[0]),
                        )
                else:
                    pending.append(waited)
                    self._gate_pause(table, retry_after)
            if fenced:
                self.refresh_retries += 1
                attempt += 1
                if attempt > 1:  # mid-broadcast epoch bounce: outlast it
                    time.sleep(self.fence_backoff * (attempt - 1))
            pos = np.sort(np.concatenate(pending))
            ts = self._submit_pull(
                first_plan["table"],
                first_plan["slots"],
                first_plan["inverse"],
                first_plan["shape"],
                positions=pos,
                read_only=first_plan.get("ro", False),
                ungated=ungated,
            )
        raise VanError(
            f"pull of {first_plan['table']!r}: routing fence retries "
            f"exhausted after {self.max_fence_retries} refreshes"
        )

    @staticmethod
    def _sole_full_pair(pairs: list, n_slots: int):
        """The single reply covering every slot in identity order, or None.

        The common single-server (or single-owner-after-localize) pull has
        exactly one ``(positions, rows)`` pair whose positions are
        ``0..n_slots-1``; its rows array — a zero-copy view of the received
        wire frame — can feed the inverse gather directly, skipping the
        zeros allocation + scatter pass entirely.
        """
        if len(pairs) != 1:
            return None
        pos, rows = pairs[0][0], pairs[0][1]
        pos = np.asarray(pos)
        if pos.size == n_slots and np.array_equal(pos, np.arange(n_slots)):
            return rows
        return None

    def pull_result(self, ts: int, timeout: Optional[float] = None):
        """Block for pull ``ts`` and reassemble per-position weight rows.

        Output shape: ``keys.shape + (dim,)`` for dim>1 tables, ``keys.shape``
        for dim=1.

        What comes back is decided by the table's row width.  Rows of a
        dim>1 table are a ``jax.Array`` on ``self.device``, the chip the
        step that consumes them runs on: the replies are uploaded once as
        the bucketed plane of unique rows and gathered to positions there
        (:meth:`_assemble`), so ``jax.device_put(rows, kv.device)`` is a
        no-op and ``np.asarray(rows)`` reads them on the host.  Scalar rows
        (dim 1) are assembled on the host and come back as a NumPy array:
        the chip gathers single floats more slowly than the host does
        (``PERF.md`` section 6, PR 29).  The values are the same bit for
        bit either way.
        """
        plan, pairs = self._pull_pairs(ts, timeout)
        on_host = self.table_cfgs[plan["table"]].dim == 1
        return self._assemble(plan, pairs, on_host=on_host)

    def pull_result_device(self, ts: int, timeout: Optional[float] = None):
        """Like :meth:`pull_result` but assembles rows ON DEVICE whatever
        the row width.

        Servers replying with device arrays (``KVServer(device_replies=
        True)``) never touch host memory; numpy replies are uploaded once.
        Returns a ``jax.Array`` of shape ``keys.shape + (dim,)`` (or
        ``keys.shape`` for dim=1).
        """
        plan, pairs = self._pull_pairs(ts, timeout)
        return self._assemble(plan, pairs, on_host=False)

    def _assemble(self, plan: dict, pairs: list, *, on_host: bool):
        """A pull's replies -> one row a requested position.

        ``on_host``: today's NumPy assembly (scalar rows).  Otherwise the
        result is a ``jax.Array`` on ``self.device``: device replies are
        scattered and gathered there (:meth:`_assemble_device_replies`),
        host replies are uploaded as one bucketed plane and gathered by
        ``inverse`` (:meth:`_assemble_host_replies`).  The span's
        ``h2d_bytes`` / ``d2h_bytes`` count the rows and the inverse that
        cross, not the legs' few position indices."""
        cfg = self.table_cfgs[plan["table"]]
        shape = plan["shape"] if cfg.dim == 1 else plan["shape"] + (cfg.dim,)
        inverse = np.asarray(plan["inverse"], np.int32)
        on_chip = sum(
            rows.nbytes for _p, rows, *_m in pairs if isinstance(rows, jax.Array)
        )
        with self.tracer.span(
            "ps.worker.assemble", legs=len(pairs), rows=plan["n_slots"],
            where="host" if on_host else "device",
        ) as sp:
            if on_host:
                self.pull_assembled_host += 1
                out = self._assemble_host(plan, pairs, cfg)
                sp.set(h2d_bytes=0, d2h_bytes=on_chip)
            elif on_chip:
                self.pull_assembled_device += 1
                out = self._assemble_device_replies(plan, pairs, cfg, inverse)
                from_host = sum(
                    rows.nbytes for _p, rows, *_m in pairs
                    if not isinstance(rows, jax.Array)
                )
                sp.set(h2d_bytes=from_host + inverse.nbytes, d2h_bytes=0)
            else:
                self.pull_assembled_device += 1
                out = self._assemble_host_replies(plan, pairs, cfg, inverse)
                plane = plan["n_slots"] * cfg.dim * out.dtype.itemsize
                sp.set(h2d_bytes=plane + inverse.nbytes, d2h_bytes=0)
        return out.reshape(shape)  # a no-op where the gather wrote ``shape``

    def _assemble_host(self, plan: dict, pairs: list, cfg) -> np.ndarray:
        """``uniq[inverse]`` in NumPy."""
        sole = self._sole_full_pair(pairs, plan["n_slots"])
        if sole is not None:
            # dtype= is a no-op passthrough when the reply already matches
            # (the normal case); only an off-dtype reply pays a cast copy
            uniq_rows = np.asarray(sole, dtype=cfg.dtype).reshape(
                -1, cfg.dim
            )[: plan["n_slots"]]
        else:
            uniq_rows = np.zeros((plan["n_slots"], cfg.dim), dtype=cfg.dtype)
            for pos, rows, *_meta in pairs:
                # a device reply is padded to its leg's bucket
                uniq_rows[pos] = np.asarray(rows).reshape(
                    -1, cfg.dim
                )[: len(pos)]
        return uniq_rows[plan["inverse"]]

    def _assemble_device_replies(
        self, plan: dict, pairs: list, cfg, inverse: np.ndarray
    ):
        """Device replies -> ``[keys, dim]`` rows on ``self.device``."""
        sole = self._sole_full_pair(pairs, plan["n_slots"])
        # replies from servers on other chips cross to this worker's here
        dtype = jnp.dtype(cfg.dtype)
        if sole is not None:
            uniq = jax.device_put(sole, self.device)
            uniq = uniq.astype(dtype).reshape(-1, cfg.dim)
            return jnp.take(uniq, jnp.asarray(inverse), axis=0)
        # one compiled program a (leg buckets, slots) shape, the legs in the
        # order of their positions whichever reply came first.  A device
        # reply's rows are padded to the leg's bucket; its positions are
        # padded to match with an index the scatter drops.
        legs = sorted(
            ((np.asarray(pos, np.int32), rows) for pos, rows, *_m in pairs),
            key=lambda leg: int(leg[0][0]) if leg[0].size else -1,
        )
        return _assemble_device(
            tuple(
                _pad_index(pos, max(rows.shape[0], pos.shape[0]),
                           plan["n_slots"])
                for pos, rows in legs
            ),
            tuple(jax.device_put(rows, self.device) for _pos, rows in legs),
            inverse,
            n_slots=plan["n_slots"], dim=cfg.dim, dtype=dtype,
        )

    def _assemble_host_replies(
        self, plan: dict, pairs: list, cfg, inverse: np.ndarray
    ):
        """Host replies -> per-position rows on ``self.device``, in the
        caller's shape.

        The replies are uploaded once, as the bucketed plane of unique rows
        (``[n_slots, dim]``, ``[n_slots]`` for scalar rows) whatever the
        legs' true row counts, and gathered by ``inverse`` there.  Slots are
        sorted and range-partitioned and the pads sit at the end, so a
        leg's positions are one ascending run and its rows a slice copy
        into the staging plane; a leg that is not one run (a server owning
        several ranges, a fence retry's subset, a cache shed) is assigned by
        index.  Positions no reply covers read zero, as on the host.  The
        staging plane is reused across pulls of a bucket and rewritten only
        once the transfer that read it, and the gather that read the
        uploaded plane (a backend may alias host memory), have completed."""
        n, dim = plan["n_slots"], cfg.dim
        flat = (n,) if dim == 1 else (n, dim)
        # the gather writes the caller's shape: no reshape program after it
        inverse = jax.device_put(inverse.reshape(plan["shape"]), self.device)
        sole = self._sole_full_pair(pairs, n)
        if sole is not None:
            # the reply's rows are the plane: no staging copy
            host = np.asarray(sole, dtype=cfg.dtype).reshape(-1, dim)[:n]
            plane = jax.device_put(host.reshape(flat), self.device)
            return _gather_rows(plane, inverse)
        with self._stage_lock:
            stage = self._stage.setdefault(
                (n, dim, np.dtype(cfg.dtype).str),
                [np.empty((n, dim), dtype=cfg.dtype), ()],
            )
            host, readers = stage
            for arr in readers:
                if not arr.is_deleted():
                    arr.block_until_ready()
            if sum(len(pos) for pos, *_r in pairs) < n:
                host[:] = 0
            for pos, rows, *_meta in pairs:
                k = len(pos)
                if not k:
                    continue
                rows = np.asarray(rows, dtype=cfg.dtype).reshape(-1, dim)[:k]
                lo = int(pos[0])
                if np.array_equal(pos, np.arange(lo, lo + k)):
                    host[lo:lo + k] = rows
                else:
                    host[pos] = rows
            plane = jax.device_put(host.reshape(flat), self.device)
            out = _gather_rows(plane, inverse)
            stage[1] = (plane, out)
        return out

    def pull_sync(
        self, table: str, keys: np.ndarray, timeout: Optional[float] = None
    ):
        """:meth:`pull` + :meth:`pull_result`: rows of a dim>1 table come
        back as a ``jax.Array`` on ``self.device``, scalar rows (dim 1) as a
        NumPy array; ``np.asarray`` reads either."""
        with self.tracer.span(
            "ps.worker.pull", table=table, keys=int(keys.size)
        ) as root:
            return self.pull_result(self._pull(table, keys, root=root), timeout)

    # -- read-heavy serving plane (ISSUE 13) ---------------------------------
    def pull_serve(
        self, table: str, keys: np.ndarray, timeout: Optional[float] = None
    ) -> np.ndarray:
        """Serve a read: hot-row cache first, read-only RPC for the misses.

        Same output contract as :meth:`pull_sync`, but every key the cache
        holds at a fresh version (entry ``__sver__`` >= the owner's observed
        watermark) is answered locally; only the misses go on the wire —
        stamped ``__ro__``, so the server answers them on the fast path.
        Fetched rows are inserted at the version THEIR reply carried, which
        is what keeps the bounded-staleness contract exact under races.
        Without a cache this degrades to a plain read-only pull.
        """
        keys = np.asarray(keys)
        cache = self.cache
        if cache is None:
            return self.pull_result(
                self.pull(table, keys, read_only=True), timeout
            )
        cfg = self.table_cfgs[table]
        with self.tracer.span(
            "ps.worker.pull_serve", table=table, keys=int(keys.size)
        ):
            # No dedup/sort on the hit path: ``Localizer.assign`` is
            # elementwise, so probe one slot PER POSITION (duplicates probe
            # twice — vectorized, cheaper than a ``np.unique``) and the
            # inverse is the identity.  Only the miss subset pays the sort
            # that ``Routing.slice_ids`` requires.
            loc = self.localizers[table]
            slots = loc.assign(
                np.ascontiguousarray(keys, dtype=np.uint64).ravel()
            )
            inverse = np.arange(slots.shape[0], dtype=np.int32)
            tr = self.routing.tables[table]
            grows = tr.rows
            n_slots = int(slots.shape[0])
            rows_out = np.zeros((n_slots, cfg.dim), dtype=cfg.dtype)
            real = np.flatnonzero(slots < grows)
            rslots = slots[real].astype(np.int64, copy=False)
            seg = np.searchsorted(
                np.asarray(tr.offsets, dtype=np.int64), rslots, side="right"
            ) - 1
            seg = np.clip(seg, 0, len(tr.owners) - 1)
            # per-segment owner codes interned once per adopted routing
            # table (identity-keyed: adoption replaces the object), so the
            # batch compare inside the cache is pure vector ops
            owner_codes = self._serve_owner_codes(table, tr, cache)[seg]
            hit, hit_rows = cache.lookup_many(table, rslots, owner_codes)
            n_hit = int(hit.sum())
            if n_hit:
                rows_out[real[hit]] = hit_rows
                flightrec.record(
                    "cache.hit", node=self.post.node_id, table=table,
                    n=n_hit,
                )
            if n_hit < int(real.shape[0]):
                miss = ~hit
                # slice_ids routes by searchsorted: subset must be sorted
                pos = real[miss][np.argsort(rslots[miss], kind="stable")]
                flightrec.record(
                    "cache.miss", node=self.post.node_id, table=table,
                    n=int(pos.shape[0]),
                )
                ts = self._submit_pull(
                    table, slots, inverse, keys.shape,
                    positions=pos, read_only=True,
                )
                _plan, pairs = self._pull_pairs(ts, timeout)
                for p, rows, sver, sender in pairs:
                    rows = np.asarray(rows, dtype=cfg.dtype).reshape(
                        -1, cfg.dim
                    )[: len(p)]
                    rows_out[p] = rows
                    ids = slots[p]
                    realm = ids < grows
                    if sver is not None and realm.any():
                        cache.insert(
                            table, ids[realm], rows[realm], int(sver), sender
                        )
            out = rows_out[inverse]
        if cfg.dim == 1:
            return out.reshape(keys.shape)
        return out.reshape(keys.shape + (cfg.dim,))

    def pull_stale(
        self, table: str, keys: np.ndarray
    ) -> Optional[np.ndarray]:
        """Serve entirely from cache IGNORING freshness — the "stale" shed
        policy's degraded answer during overload.  Returns None unless
        every real key is cached (a partially-stale answer would mix
        freshness classes invisibly); never touches the wire."""
        cache = self.cache
        if cache is None:
            return None
        keys = np.asarray(keys)
        cfg = self.table_cfgs[table]
        slots, inverse = self._localize(table, keys)
        grows = self.routing.tables[table].rows
        rows_out = np.zeros((int(slots.shape[0]), cfg.dim), dtype=cfg.dtype)
        for j, sl in enumerate(np.asarray(slots).tolist()):
            if int(sl) >= grows:
                continue
            hit = cache.lookup_stale(table, int(sl))
            if hit is None:
                return None
            rows_out[j] = hit[0]
        out = rows_out[inverse]
        if cfg.dim == 1:
            return out.reshape(keys.shape)
        return out.reshape(keys.shape + (cfg.dim,))

    def push_sync(
        self,
        table: str,
        keys: np.ndarray,
        values: np.ndarray,
        timeout: Optional[float] = None,
    ) -> int:
        """Push and block for all server acks, retrying once on deadline and
        looping on routing fences.

        ``values`` may be a NumPy array or the step's ``jax.Array``: a
        device array is combined on ``self.device`` as it stands and only
        the combined plane is brought to the host for the wire
        (:meth:`_prepare_push`); the wire bytes are the same either way.

        The deadline path mirrors :meth:`pull_result`: the stuck task is
        cancelled (no leaked ``_pending`` state) and the push re-issued
        against the same ``S{i}`` identities — live again after a
        :class:`~parameter_server_tpu.kv.replica.ReplicaSet` promotion.
        Retried pushes are deduplicated by the transport only when the SAME
        message is retransmitted (``ReliableVan``); an app-layer retry is a
        new message, so — like the reference's retry — it can double-apply
        iff the original was applied but its ack was lost AND the transport
        below is unreliable.  Run over ``ReliableVan`` (acks retransmitted)
        that window closes: a surviving server acks, only a dead one
        triggers the retry.

        Fence loop (PR 6): legs rejected for a stale routing epoch or moved
        range adopt the server's table and re-push ONLY the fenced positions
        — the fence fired BEFORE any apply, so the retry cannot double-count
        and the accepted legs are never re-sent.  Returns the completing
        timestamp.

        Group mode (ISSUE 15): the push routes through the group
        pre-reduction and this call blocks until the group's done notify
        (all members of a step must run :meth:`push_sync` concurrently —
        the leader's rendezvous completes only when every contribution
        lands).  Fenced rejects of the reduced push RE-ELECT
        (``salt=attempt``) inside the leader's ack callback, handing the
        retry to the next member; leader death degrades to this member's
        own direct push within the same step.
        """
        with self.tracer.span(
            "ps.worker.push", table=table, keys=int(keys.size)
        ) as root:
            slots, combined = self._prepare_push(table, keys, values, root)
            if self._group is not None:
                return self._group_push(
                    table, slots, combined, sync=True, timeout=timeout
                )
            return self._push_sync_prepared(table, slots, combined, timeout)

    def _push_sync_prepared(
        self,
        table: str,
        slots: np.ndarray,
        combined: np.ndarray,
        timeout: Optional[float] = None,
    ) -> int:
        """The direct (ungrouped) sync push loop over prepared planes —
        also the group mode's no-loss degradation target.

        Consistency gates (ISSUE 20): ``__wait__`` defers park the waited
        positions on the gate budget (no fence retries consumed).  Pushes
        are NEVER dropped: past ``gate_deadline_s`` the remainder is
        forced through ungated (``consist.shed``, how="forced").  A fully
        acked push commits this worker's step for the table — the
        ``__cstep__`` every later request stamps."""
        positions: Optional[np.ndarray] = None
        ts = -1
        attempt = 0  # fence budget only; gate waits ride their own clock
        gate_t0 = None
        ungated = False
        while attempt <= self.max_fence_retries:
            ts, order = self._submit_push(
                table, slots, combined, positions, keep=True, ungated=ungated
            )
            if not self._wait_traced(ts, len(order), timeout):
                if not self.retry_on_timeout:
                    raise TimeoutError(f"push ts={ts} timed out")
                # remote=True: servers that have not applied the original yet
                # DROP it, closing the original+retry double-apply window
                # that the transport argument alone cannot (a delayed request
                # leg is not a retransmit, so ReliableVan dedup never sees it)
                self.cancel(ts, "push deadline", remote=True)
                self.take_responses(ts)
                self.push_retries += 1
                ts, order = self._submit_push(
                    table, slots, combined, positions, keep=True,
                    ungated=ungated,
                )
                if not self._wait_traced(ts, len(order), timeout, retry=1):
                    self.cancel(ts, "push deadline (retry)", remote=True)
                    self.take_responses(ts)
                    raise TimeoutError(f"push ts={ts} timed out after retry")
            errs = self.errors(ts)
            responses = self.take_responses(ts)
            self._adopt_from(responses)
            responses, waits, wait_pos, retry_after = self._scan_waits(
                responses, order
            )
            _, fenced_senders, fenced = self._scan_fences(responses, order)
            skip = fenced_senders | {r.sender for r in waits}
            real = self._real_errors(errs, skip)
            if real:
                raise VanError(
                    f"push ts={ts} failed on: " + "; ".join(real)
                )
            if not fenced and not waits:
                if self._gated(table):
                    self._consist_commit(table)
                    if gate_t0 is not None:
                        with self._consist_lock:
                            self._gate_hist.record(
                                max(time.monotonic() - gate_t0, 0.0)
                            )
                return ts
            pending = list(fenced)
            if waits:
                with self._consist_lock:
                    self.consist_waits += len(waits)
                if gate_t0 is None:
                    gate_t0 = time.monotonic()
                pending.append(np.sort(np.concatenate(wait_pos)))
                deadline = self._gate_deadline_s(table)
                if (
                    deadline > 0
                    and time.monotonic() - gate_t0 > deadline
                    and not ungated
                ):
                    # never dropped: force the remainder through ungated
                    ungated = True
                    with self._consist_lock:
                        self.consist_forced += 1
                        self._gate_hist.record(
                            max(time.monotonic() - gate_t0, 0.0)
                        )
                    flightrec.record(
                        "consist.shed", node=self.post.node_id,
                        table=table, op="push", how="forced",
                        n=int(sum(p.shape[0] for p in wait_pos)),
                    )
                else:
                    self._gate_pause(table, retry_after)
            if fenced:
                self.refresh_retries += 1
                attempt += 1
                if attempt > 1:  # mid-broadcast epoch bounce: outlast it
                    time.sleep(self.fence_backoff * (attempt - 1))
            positions = np.sort(np.concatenate(pending))
        raise VanError(
            f"push of {table!r}: routing fence retries exhausted after "
            f"{self.max_fence_retries} refreshes"
        )

    # -- checkpoint (reference SaveModel/LoadModel broadcast tasks) ----------
    def save_model(
        self,
        root: str,
        step: int,
        *,
        clocks: Optional[list] = None,
        extras: Optional[dict] = None,
        timeout: Optional[float] = 600.0,
    ) -> None:
        """Broadcast SaveModel to all servers, then commit the manifest.

        Blocking: returns once every shard is on disk and MANIFEST.json is
        written (the commit marker — see ``checkpoint.finalize``).  Raises if
        any server's save failed (disk full etc.) instead of committing a
        partial checkpoint.
        """
        from parameter_server_tpu import checkpoint
        from parameter_server_tpu.utils.keys import localizer_meta

        ts = self._broadcast_control("save_model", {"root": root, "step": step})
        if not self.wait(ts, timeout):
            raise TimeoutError("save_model timed out")
        self.check(ts)
        self.take_responses(ts)
        # Record each table's key->row mapping so offline eval reconstructs
        # the exact localizer (hash_bits/seed) instead of guessing a default.
        extras = dict(extras or {})
        extras.setdefault(
            "localizers",
            {t: localizer_meta(loc) for t, loc in self.localizers.items()},
        )
        checkpoint.finalize(
            root,
            step,
            self.num_servers,
            {t: cfg.rows for t, cfg in self.table_cfgs.items()},
            clocks=clocks,
            extras=extras,
        )

    def load_model(
        self, root: str, step: int, *, timeout: Optional[float] = 600.0
    ) -> None:
        """Broadcast LoadModel: every server restores its row-range."""
        ts = self._broadcast_control("load_model", {"root": root, "step": step})
        if not self.wait(ts, timeout):
            raise TimeoutError("load_model timed out")
        self.check(ts)
        self.take_responses(ts)

    # -- consistency plane control (ISSUE 20) --------------------------------
    def consist_hello(
        self,
        *,
        table: Optional[str] = None,
        step: Optional[int] = None,
        incarnation: Optional[int] = None,
        timeout: Optional[float] = 30.0,
    ) -> None:
        """Register this worker in every server's fleet clock up front.

        Call BEFORE training on a gated table (ElasticTrainer and the
        bench harness do): until every peer is registered, the clock
        cannot know the fleet is larger than the senders it has seen, so
        a fast worker could free-run ahead during bring-up.  After a
        same-id restart, re-hello at the restored ``step`` with the new
        incarnation — the dead incarnation's entry is replaced, not
        wedged into the fleet minimum.
        """
        if incarnation is None:
            reg = getattr(self.post.van, "incarnations", None)
            incarnation = reg.get(self.post.node_id) if reg is not None else 0
        if step is None:
            step = (
                self.consist_step(table)
                if table is not None
                else max(self._consist_steps.values(), default=0)
            )
        payload = {
            "worker": self.post.node_id,
            "incarnation": int(incarnation or 0),
            "step": int(step),
        }
        if table is not None:
            payload["table"] = table
        ts = self._broadcast_control("consist_hello", payload)
        if not self.wait(ts, timeout):
            raise TimeoutError("consist_hello timed out")
        self.check(ts)
        self.take_responses(ts)

    def set_consistency(
        self,
        *,
        table: Optional[str] = None,
        bound: Optional[int] = None,
        mode: Optional[str] = None,
        why: str = "manual",
        timeout: Optional[float] = 30.0,
    ) -> None:
        """Live-retune the fleet's gate: new ``bound`` and/or ``mode``.

        The BoundTuner's lever (bound only) and the scenario DSL's
        ``consistency_mode`` phase knob (mode flips mid-run).  Broadcast
        to every server, then flight-recorded as ``consist.retune`` so a
        postmortem can line tuning decisions up against SLO breaches.
        """
        payload: dict = {}
        if table is not None:
            payload["table"] = table
        if bound is not None:
            payload["bound"] = int(bound)
        if mode is not None:
            payload["mode"] = str(mode)
        ts = self._broadcast_control("consist_set", payload)
        if not self.wait(ts, timeout):
            raise TimeoutError("consist_set timed out")
        self.check(ts)
        self.take_responses(ts)
        flightrec.record(
            "consist.retune", node=self.post.node_id,
            table=table or "*", bound=-1 if bound is None else int(bound),
            mode=mode or "-", why=why[:120],
        )

    def _broadcast_control(self, op: str, payload: dict) -> int:
        # broadcast to the CURRENT owner set (post-migration it need not be
        # the contiguous 0..num_servers-1 of the launch split)
        msgs = [
            Message(
                task=Task(
                    TaskKind.CONTROL, self.name, payload={"op": op, **payload}
                ),
                recver=server_id(s),
            )
            for s in self.routing.servers()
        ]
        return self.submit(msgs, keep_responses=True)

    def _control_round(
        self, msgs: List[Message], what: str, timeout: Optional[float]
    ) -> List[Message]:
        """Submit control messages, wait, raise on any error, return replies."""
        ts = self.submit(msgs, keep_responses=True)
        if not self.wait(ts, timeout):
            raise TimeoutError(f"{what} timed out")
        self.check(ts)
        return self.take_responses(ts)

    # -- durability plane (ISSUE 16): partitioned incremental snapshots ------
    def save_snapshot(
        self,
        root: str,
        step: int,
        *,
        base_step: Optional[int] = None,
        clocks: Optional[list] = None,
        extras: Optional[dict] = None,
        timeout: Optional[float] = 600.0,
    ) -> dict:
        """Partitioned, incremental, non-blocking snapshot of every table.

        Unlike :meth:`save_model` this works for ANY routing layout: each
        owning server writes one file per owned segment, and the driver
        (here) assembles + CRC-verifies the manifest.  With ``base_step``
        set, segments whose version clock has not advanced are NOT
        rewritten — the base snapshot's file is carried forward by
        reference and only the dirty-row delta logs ship (the PR-10
        ``__sver__`` clock as LSN).  Pushes keep applying throughout; the
        only freeze is each server's delta export at ``snap_commit``.

        Returns a summary: carried/written segment counts, total delta
        rows, and per-server commit-freeze seconds.
        """
        from parameter_server_tpu import checkpoint
        from parameter_server_tpu.utils.keys import localizer_meta

        base = None
        if base_step is not None:
            base = checkpoint.read_snapshot(root, base_step)
        base_entries = {
            (e["table"], int(e["lo"]), int(e["hi"])): e
            for e in (base["segments"] if base else [])
        }
        sid = f"ckpt-{int(step)}-e{self.routing.epoch}"
        begun = False
        try:
            self._control_round(
                [
                    Message(
                        task=Task(TaskKind.CONTROL, self.name,
                                  payload={"op": "snap_begin", "sid": sid}),
                        recver=server_id(s),
                    )
                    for s in self.routing.servers()
                ],
                "snap_begin", timeout,
            )
            begun = True
            # one snap_write per segment, addressed to its owner; servers
            # process them serially on the recv thread, so pushes
            # interleave between segments — no bulk-copy freeze
            writes = []
            for t in sorted(self.routing.tables):
                for lo, hi, owner in self.routing.tables[t].segments():
                    payload = {
                        "op": "snap_write", "sid": sid, "root": root,
                        "step": int(step), "table": t, "lo": lo, "hi": hi,
                    }
                    be = base_entries.get((t, lo, hi))
                    if be is not None:
                        payload["base_sver"] = int(be.get("sver", 0))
                    writes.append(
                        Message(
                            task=Task(TaskKind.CONTROL, self.name,
                                      payload=payload),
                            recver=server_id(owner),
                        )
                    )
            # a migrated owner holds several segments; the Customer dedups
            # responses per (ts, sender), so each round may address any
            # server at most once — round-robin the writes into such rounds
            rounds: List[List[Message]] = []
            for m in writes:
                for batch in rounds:
                    if all(b.recver != m.recver for b in batch):
                        batch.append(m)
                        break
                else:
                    rounds.append([m])
            entries: List[dict] = []
            carried_tables: set = set()
            n_carried = 0
            for batch in rounds:
                for r in self._control_round(batch, "snap_write", timeout):
                    pl = r.task.payload
                    key = (str(pl["table"]), int(pl["lo"]), int(pl["hi"]))
                    if pl.get("carried"):
                        entries.append(dict(base_entries[key]))
                        carried_tables.add(key[0])
                        n_carried += 1
                    else:
                        entries.append(dict(pl["entry"]))
            # commit: the measured, delta-bounded freeze on every server
            deltas: List[dict] = []
            svers: Dict[tuple, int] = {}
            freezes: List[float] = []
            delta_rows = 0
            for r in self._control_round(
                [
                    Message(
                        task=Task(
                            TaskKind.CONTROL, self.name,
                            payload={"op": "snap_commit", "sid": sid,
                                     "root": root, "step": int(step)},
                        ),
                        recver=server_id(s),
                    )
                    for s in self.routing.servers()
                ],
                "snap_commit", timeout,
            ):
                pl = r.task.payload
                for d in pl["deltas"]:
                    deltas.append(dict(d))
                    delta_rows += int(d["rows"])
                for t, lo, hi, v in pl["svers"]:
                    svers[(str(t), int(lo), int(hi))] = int(v)
                freezes.append(float(pl["freeze_s"]))
        except Exception:
            if begun:
                # best-effort: release server-side dirty tracking; orphan
                # segment files are swept by retention, and with no
                # manifest the step simply never exists
                try:
                    msgs = [
                        Message(
                            task=Task(
                                TaskKind.CONTROL, self.name,
                                payload={"op": "snap_abort", "sid": sid,
                                         "why": "driver error"},
                            ),
                            recver=server_id(s),
                        )
                        for s in self.routing.servers()
                    ]
                    self._control_round(msgs, "snap_abort", timeout)
                except Exception:
                    pass
            raise
        # stamp commit-time segment versions: a row pushed between a
        # segment's write and the commit is in this snapshot's delta log,
        # so the NEXT snapshot may carry the file at the commit-time clock
        for e in entries:
            key = (e["table"], int(e["lo"]), int(e["hi"]))
            if key in svers:
                e["sver"] = svers[key]
        # incremental chains stay flat: carry the base's deltas only for
        # tables that carried at least one base file (fresh files are
        # stamped with THIS step, so older deltas can never apply to them)
        if base is not None:
            for d in base["deltas"]:
                if d["table"] in carried_tables:
                    deltas.append(dict(d))
        extras = dict(extras or {})
        extras.setdefault(
            "localizers",
            {t: localizer_meta(loc) for t, loc in self.localizers.items()},
        )
        checkpoint.finalize_snapshot(
            root, step, self.routing.to_payload(), entries, deltas,
            base_step=base_step, clocks=clocks, extras=extras,
        )
        return {
            "step": int(step),
            "segments": len(entries),
            "carried": n_carried,
            "delta_rows": delta_rows,
            "freeze_s": freezes,
        }

    def load_snapshot(
        self, root: str, step: int, *, timeout: Optional[float] = 600.0
    ) -> None:
        """Broadcast restore-from-partitioned-snapshot to the current fleet.

        The fleet shape may differ from the writing fleet's: each server
        reads only the manifest file ranges covering its CURRENT segments.
        """
        self._control_round(
            [
                Message(
                    task=Task(
                        TaskKind.CONTROL, self.name,
                        payload={"op": "restore_snap", "root": root,
                                 "step": int(step)},
                    ),
                    recver=server_id(s),
                )
                for s in self.routing.servers()
            ],
            "restore_snap", timeout,
        )
