"""KVServer: the server-role Customer owning table shards.

Reference analogue: the server process's ``Parameter`` subclass answering
Push with ``SetValue`` (merge + update) and Pull with ``GetValue`` (gather)
(``src/parameter/parameter.h`` [U]).  Each KVServer instance owns the local
row-range shard of every registered table; requests arrive through the Van
recv thread (one per node — the reference's single-Executor-thread model, so
table mutation is single-threaded by construction) and the actual math runs
as the KVTable's jit-compiled device steps.

PR-6 ownership model: the shard is no longer the fixed uniform
``RangePartition`` split — an epoch-versioned
:class:`~parameter_server_tpu.kv.routing.RoutingTable` says which server
owns which global row ranges, and **live migration** rewrites it at runtime:

- Workers ship GLOBAL row ids stamped with their routing epoch
  (``__repoch__``); a request whose epoch disagrees, or whose rows this
  server does not own, is answered with a typed ``__error__`` reply carrying
  ``__fenced__`` + this server's routing table — rejected, NOT lost (the
  worker refreshes and retries; ``fenced_rejects`` counts these).
- Migration control ops (``migrate_*``) stream a sub-range to a recipient
  over the replica-chain transport path while the donor keeps serving;
  the only freeze is the atomic commit handler (this recv thread), whose
  duration is bounded by the final dirty-row delta.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.config import ApplyEngineConfig, LedgerConfig, TableConfig
from parameter_server_tpu.core import flightrec
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Customer, Postoffice
from parameter_server_tpu.core.tracectx import TRACE_KEY
from parameter_server_tpu.kv.consistency import MODE_CODES, FleetClock
from parameter_server_tpu.kv.ledger import ApplyLedger
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
)
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.utils import keys as keys_lib
from parameter_server_tpu.utils.keys import leg_bucket as _bucket
from parameter_server_tpu.utils.platform import role_device
from parameter_server_tpu.utils.trace import (
    NULL_TRACER,
    LatencyHistogram,
    Tracer,
    req_id,
)


class KVServer(Customer):
    """Server-side customer: routes Push/Pull to local table shards."""

    def __init__(
        self,
        post: Postoffice,
        table_cfgs: Dict[str, TableConfig],
        server_index: int,
        num_servers: int,
        *,
        name: str = "kv",
        tracer: Tracer = NULL_TRACER,
        device_replies: bool = False,
        replica: Optional[str] = None,
        replica_sync: bool = False,
        max_replica_lag: int = 8,
        replica_ack_timeout: float = 60.0,
        routing: Optional[RoutingTable] = None,
        migrate_timeout: float = 30.0,
        apply: Optional[ApplyEngineConfig] = None,
        devobs: Optional[LedgerConfig] = None,
        pallas_interpret: bool = False,
    ) -> None:
        """``replica``: node id of a hot-standby KVServer holding the same
        shard (chain replication of key ranges, the reference paper's §4.3
        recovery [U]; VERDICT r3 #6).  Every applied push is forwarded to
        it in apply order, so the standby's table+optimizer state tracks the
        primary's exactly.  ``replica_sync=True`` = chain semantics: the
        worker's ack only fires after the replica applied (ZERO update loss
        on primary death); ``False`` = async forwarding with at most
        ``max_replica_lag`` pushes in flight (bounded loss, no added push
        latency).  On death, :func:`parameter_server_tpu.kv.replica.promote`
        rebinds the standby under the primary's node id.

        ``routing``: explicit ownership map; defaults to the uniform
        epoch-0 split (identical to the legacy ``RangePartition``).  Pass a
        post-migration table to spawn a server into an already-rebalanced
        cluster (``scale_up`` spawns with ZERO owned rows and migrates onto
        it).

        ``pallas_interpret``: a CPU test's explicit request to run
        ``scatter_impl="pallas"`` tables in the Pallas interpreter (see
        :class:`~parameter_server_tpu.kv.table.KVTable`)."""
        super().__init__(name, post)
        #: the chip this server's shards, optimizer state and staged request
        #: arrays live on: servers of an in-process cluster spread over the
        #: host's chips, and each owns ONE device queue (the ApplyLedger's
        #: oldest-completes-first assumption holds per server).
        self.device = role_device(server_index)
        #: bundle-batched apply engine knobs (ISSUE 11): how many same-table
        #: PUSHes of one coalesced bundle collapse into a single device
        #: apply, and the cross-member duplicate-row policy.
        self.apply_cfg = apply or ApplyEngineConfig()
        if self.apply_cfg.dup_policy not in ("rounds", "combine"):
            raise ValueError(
                f"dup_policy must be rounds|combine, "
                f"got {self.apply_cfg.dup_policy!r}"
            )
        #: device-plane observability (ISSUE 12): the ApplyLedger registers
        #: every dispatched device apply and retires it from its own reaper
        #: thread — the ack path only READS the level-triggered
        #: ``overloaded()`` flag (the ``__busy__`` hint), never device state.
        devobs = devobs or LedgerConfig()
        self.ledger: Optional[ApplyLedger] = (
            ApplyLedger(post.node_id, devobs) if devobs.enabled else None
        )
        #: reply to pulls with device arrays instead of host numpy — the
        #: zero-copy mode for in-process (Loopback) planes where worker and
        #: server share the device; cross-host Vans keep numpy replies.
        self.device_replies = device_replies
        self.server_index = server_index
        #: legacy uniform split — still the CHECKPOINT layout contract (shard
        #: files are uniform-contiguous; see save_checkpoint's guard).
        self.partitions = {
            t: RangePartition(cfg.rows, num_servers) for t, cfg in table_cfgs.items()
        }
        self.table_cfgs = table_cfgs
        self.routing = routing or RoutingTable.uniform(table_cfgs, num_servers)
        self._shard_maps: Dict[str, tuple] = {
            t: self._make_map(self.routing, t) for t in table_cfgs
        }
        #: ISSUE-10 staleness plane: per-table, per-owned-segment version
        #: clock (parallel to ``_shard_maps[t][0]``), bumped on every
        #: push-apply touching the segment; the max over the segments a
        #: request touches is stamped into its reply (``__sver__``) so
        #: workers can measure update lag at use time.  Mutated only on the
        #: recv thread (the single-writer table discipline).
        self._seg_versions: Dict[str, np.ndarray] = {
            t: np.zeros(self._shard_maps[t][0].shape[0], dtype=np.int64)
            for t in table_cfgs
        }
        self.tables: Dict[str, KVTable] = {
            t: KVTable(
                cfg,
                rows=self.routing.tables[t].server_rows(server_index),
                # stable across OS processes (builtin str hash is salted per
                # interpreter — servers spawned as separate processes would
                # init different rows than an in-process cluster, breaking
                # cross-deployment loss parity and restart determinism)
                seed=zlib.crc32(f"{t}:{server_index}".encode()) & 0x7FFFFFFF,
                device=self.device,
                interpret=pallas_interpret,
            )
            for t, cfg in table_cfgs.items()
        }
        #: dashboard counters
        self.pushes = 0
        self.pulls = 0
        #: hierarchical push (ISSUE 15): group-stamped pushes applied, and
        #: the member contributions they carried (``__grp__``'s ``n``) —
        #: the fan-in ratio pstop's GRP column derives.  A group push is
        #: ONE apply here (one ledger entry, one dup-policy unit); these
        #: counters are what make the pre-reduction visible.
        self.group_pushes = 0
        self.group_members = 0
        #: serving plane (ISSUE 13): read-only fast-path pulls answered,
        #: and their per-table server-side latency (dispatch -> reply built,
        #: including the D2H readback — the histogram the ``ro-p99`` SLO
        #: watches).  Recv-thread-only, like every other counter here.
        self.ro_pulls = 0
        #: ids the applies had to visit and the bucket slots they were
        #: padded to (``_counted``)
        self.apply_ids_real = 0
        self.apply_ids_bucket = 0
        #: requests localized (``_localize_request``), and of those the ones
        #: the native pass ran: the two are equal wherever the keymap
        #: library loaded
        self.localize_requests = 0
        self.localize_native = 0
        self._keymap = keys_lib._keymap_lib()
        self.ro_hist: Dict[str, LatencyHistogram] = {
            t: LatencyHistogram() for t in table_cfgs
        }
        self.fenced_rejects = 0
        # -- consistency plane (ISSUE 20) ------------------------------------
        #: per-gated-table live state: mode/bound start from the table's
        #: ConsistencyConfig but are retunable at runtime (``consist_set``
        #: — the BoundTuner's lever and the scenario DSL's mode-flip knob);
        #: the FleetClock is the vector clock of per-worker committed steps
        #: fed by ``__cstep__`` stamps.  Mutated on the recv thread (plus
        #: the van's incarnation callback — FleetClock locks internally).
        self._consist: Dict[str, dict] = {}
        for t, cfg in table_cfgs.items():
            if cfg.consistency is not None:
                self._consist[t] = {
                    "cfg": cfg.consistency,
                    "mode": cfg.consistency.mode,
                    "bound": cfg.consistency.bound,
                    "clock": FleetClock(),
                }
        self.consist_defers = 0
        self.consist_releases = 0
        #: senders currently parked on a ``__wait__`` defer, per table —
        #: the gate/release event pairing the postmortem anchor keys on
        #: (``consist.gate`` fires on FIRST defer, ``consist.release`` when
        #: that sender is next admitted; retries in between stay silent).
        self._consist_waiting: Dict[str, set] = {t: set() for t in self._consist}
        if self._consist and hasattr(post.van, "on_incarnation_advance"):
            # same-id restart fencing (ISSUE 20 satellite): the dead
            # incarnation's clock entry must not wedge the fleet minimum
            post.van.on_incarnation_advance.append(self._consist_incarnation)
        # -- sampled request tracing (ISSUE 18) ------------------------------
        #: server-side plane attribution across sampled requests, exported
        #: via :meth:`latency_digests`: ``trace.wire`` = worker submit ->
        #: handler dispatch (same-host monotonic clocks; cross-host fleets
        #: read the clock-rebased ``tools/critpath.py`` view instead),
        #: ``trace.sq`` = van receive -> handler dispatch (server queue),
        #: ``trace.apply`` = dispatch -> reply built.  Recv-thread-only,
        #: same discipline as ``ro_hist``.
        self._trace_hists: Dict[str, LatencyHistogram] = {}
        #: tid -> dispatch monotonic time, bridging :meth:`_trace_dispatch`
        #: to the reply site; bounded (error paths may never reply)
        self._trace_disp: Dict[str, float] = {}
        self.rows_migrated_in = 0
        self.rows_migrated_out = 0
        self.migration_freeze_s = 0.0
        self.migration_freeze_last_s = 0.0
        self.tracer = tracer
        self.migrate_timeout = migrate_timeout
        #: in-flight donor migrations: mid -> {table, lo, hi, to, dirty}
        self._migrations: Dict[str, dict] = {}
        #: in-flight recipient staging: mid -> {table, lo, hi, chunks}
        self._staging: Dict[str, dict] = {}
        #: durability plane (ISSUE 16): open snapshot windows, sid ->
        #: {dirty: {table: set(global rows)}} — armed by ``snap_begin``,
        #: drained by ``snap_commit``'s bounded freeze.  Same recv-thread
        #: single-writer discipline as ``_migrations``.
        self._snapshots: Dict[str, dict] = {}
        self.ckpt_commits = 0
        self.ckpt_freeze_s = 0.0
        self.ckpt_freeze_last_s = 0.0
        self.ckpt_delta_rows = 0
        self.ckpt_delta_overflow = 0
        #: soft bound on the commit-freeze delta (CheckpointConfig
        #: ``max_delta_rows``; settable per snap_begin payload).
        self.ckpt_max_delta_rows = 65536
        #: basis of the ``ckpt_age_s`` gauge: stamped at construction so a
        #: fleet that NEVER snapshots ages (and breaches the ckpt-age SLO)
        #: from boot, then re-stamped on every snapshot commit / restore.
        self._ckpt_commit_t = time.monotonic()
        #: lazy side customer for donor->recipient streaming (own endpoint:
        #: waiting for stage/install acks on this recv thread would deadlock)
        self._mig: Optional[Customer] = None
        # -- hot-replica forwarding channel ---------------------------------
        self.replica = replica
        self.replica_sync = replica_sync
        self.max_replica_lag = max_replica_lag
        self.replica_ack_timeout = replica_ack_timeout
        self._fwd_inflight: collections.deque[int] = collections.deque()
        if replica is not None:
            # A DEDICATED endpoint for the primary's client role: waiting
            # for replica acks on the server's own recv thread would
            # deadlock (that thread must process the ack).  The forwarding
            # Customer shares this server's customer name so the replica
            # routes the forwarded pushes into its normal kv handler.
            self._fwd_post = Postoffice(f"{post.node_id}.fw", post.van)
            self._fwd = Customer(name, self._fwd_post)

    # -- routing / shard maps -------------------------------------------------
    def _make_map(self, routing: RoutingTable, table: str) -> tuple:
        """``(starts, ends, locals)`` of this server's owned segments.

        Global row ``g`` in segment ``i`` lives at local row
        ``g - starts[i] + locals[i]`` — segments pack contiguously into the
        KVTable in global order.
        """
        segs = routing.tables[table].owned_segments(self.server_index)
        starts = np.asarray([lo for lo, _ in segs], dtype=np.int64)
        ends = np.asarray([hi for _, hi in segs], dtype=np.int64)
        sizes = ends - starts
        locs = np.concatenate([[0], np.cumsum(sizes)])[:-1].astype(np.int64)
        # each a fresh contiguous int64 array, made once a routing: the
        # native localization reads them through their addresses
        return starts, ends, locs

    def _try_localize(
        self, table: str, gids: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Map global rows to local rows against the CURRENT shard map.

        Returns ``(local, owned)``: ``local[i]`` is valid iff ``owned[i]``.
        """
        starts, ends, locs = self._shard_maps[table]
        gids = np.asarray(gids, dtype=np.int64)
        if starts.size == 0:
            return np.zeros(gids.shape, np.int64), np.zeros(gids.shape, bool)
        idx = np.searchsorted(starts, gids, side="right") - 1
        idx_c = np.clip(idx, 0, None)
        owned = (idx >= 0) & (gids >= 0) & (gids < ends[idx_c])
        local = np.where(owned, gids - starts[idx_c] + locs[idx_c], 0)
        return local, owned

    def _localize_request(
        self, table: str, keys
    ) -> Optional[Tuple[np.ndarray, np.ndarray, int, int]]:
        """A request's keys (GLOBAL ids in any order, a pad >= the table's
        global rows) against this server's shard map: everything the request
        reads of them, ``(local_ids int32, touched_segments, real, upto)``.

        Pads map to this shard's trash row.  ``touched_segments`` are the
        sorted distinct indices of the owned segments the real keys fall in
        (what the staleness clock bumps and reports).  ``real`` counts the
        real keys; ``upto`` is one past the last of them, how many of the
        leg's ids an apply has to visit: what follows points at the trash
        row, which every apply resets (the worker pads its sorted slots with
        keys past the table, so they are the tail of the last shard's leg:
        25 k of a 45 k leg in ``criteo_lr.skew``).  None when a real key is
        not owned here, a negative one included (the fence trigger).

        Where the keymap library loaded this is ONE native call over the
        keys as they arrived (``utils/keys.py::localize_shard_native``) that
        keeps the interpreter's lock for its tens of microseconds, where the
        NumPy body hands it back in every pass and queues for it behind the
        process's other threads.  :meth:`_localize_numpy` is the definition,
        and what runs without the library (``counters``: ``localize_native``
        of ``localize_requests``)."""
        self.localize_requests += 1
        if self._keymap is None:
            return self._localize_numpy(table, keys)
        self.localize_native += 1
        return keys_lib.localize_shard_native(
            self._keymap, keys, self.routing.tables[table].rows,
            self._shard_maps[table], self.tables[table].rows,
        )

    def _localize_numpy(
        self, table: str, keys
    ) -> Optional[Tuple[np.ndarray, np.ndarray, int, int]]:
        """:meth:`_localize_request` in NumPy, some fifteen passes over the
        keys: the definition the native pass is held to, and the fallback."""
        grows = self.routing.tables[table].rows
        kn = np.asarray(keys, dtype=np.int64)
        out = np.full(kn.shape, self.tables[table].rows, dtype=np.int32)
        real = kn < grows
        segs = np.empty(0, dtype=np.int64)
        if real.any():
            starts, ends, locs = self._shard_maps[table]
            if starts.size == 0:
                return None
            rk = kn[real]
            idx = np.searchsorted(starts, rk, side="right") - 1
            idx_c = np.clip(idx, 0, None)
            owned = (idx >= 0) & (rk >= 0) & (rk < ends[idx_c])
            if not owned.all():
                return None
            out[real] = (rk - starts[idx_c] + locs[idx_c]).astype(np.int32)
            segs = np.unique(idx_c)
        rows = np.flatnonzero(real)
        upto = int(rows[-1]) + 1 if rows.size else 0
        return out, segs, int(rows.size), upto

    def _fence_reply(self, msg: Message, why: str) -> Message:
        """Typed reject: ``__error__`` + ``__fenced__`` + the CURRENT table.

        The worker's retry loop keys on ``__fenced__`` (a real handler error
        must still raise) and adopts the attached routing iff it is newer
        than what it holds — rejected, not lost.

        ISSUE 13: fences also carry the shard's ``__sver__`` (and the table
        name the fence payload would otherwise drop), so a reject still
        refreshes the worker's cache-invalidation watermark — a fenced
        worker learns about writes it raced with from the reject itself.
        """
        self.fenced_rejects += 1
        flightrec.record(
            "fence.routing", node=self.post.node_id, sender=msg.sender,
            epoch=self.routing.epoch, why=why[:120],
        )
        reply = msg.reply()
        payload = {
            "__error__": why,
            FENCED_KEY: True,
            ROUTING_KEY: self.routing.to_payload(),
        }
        tctx = msg.task.payload.get(TRACE_KEY)
        if isinstance(tctx, dict) and tctx.get("tid") is not None:
            # ISSUE 18: a fence is still a reply leg of the sampled span
            # tree — echo the context (the fresh fence payload would drop
            # it) so the worker closes the tree, and record the verdict
            payload[TRACE_KEY] = tctx
            self._trace_disp.pop(tctx["tid"], None)
            flightrec.record(
                "trace.reply",
                tid=tctx["tid"],
                node=self.post.node_id,
                verdict="fenced",
            )
        tname = msg.task.payload.get("table")
        if tname in self._seg_versions:
            payload["table"] = tname
            payload[VERSION_KEY] = self.version_max(tname)
        reply.task = dataclasses.replace(msg.task, payload=payload)
        return reply

    def _wait_reply(self, msg: Message, tname: str, step: int, fm: int) -> Message:
        """Typed consistency defer (ISSUE 20): the sender ran too far ahead.

        Deliberately FENCE-SHAPED (``__error__`` + ``__fenced__`` + the
        current routing table) so pre-ISSUE-20 workers treat it as a fence
        and retry blindly — deferred, never dropped (MIGRATION.md).  New
        workers key on ``__wait__`` first: routing is fine, so the retry
        rides the gate budget (``gate_deadline_s``), not the fence budget,
        honoring the ``retry_after`` backoff hint.  The fleet clock
        snapshot rides along so the worker can see WHO it is waiting for.
        """
        st = self._consist[tname]
        self.consist_defers += 1
        waiting = self._consist_waiting[tname]
        if msg.sender not in waiting:
            waiting.add(msg.sender)
            flightrec.record(
                "consist.gate", node=self.post.node_id, sender=msg.sender,
                table=tname, step=step, fleet_min=fm,
                bound=int(st["bound"]),
            )
        reply = msg.reply()
        gap = step - fm - int(st["bound"])
        payload = {
            "__error__": (
                f"consistency gate ({st['mode'].value}): step {step} > "
                f"fleet_min {fm} + bound {st['bound']} on {tname!r}"
            ),
            FENCED_KEY: True,
            ROUTING_KEY: self.routing.to_payload(),
            WAIT_KEY: True,
            "clock": st["clock"].snapshot(),
            "fleet_min": fm,
            "bound": int(st["bound"]),
            "retry_after": min(0.25, 0.002 * max(1, gap)),
        }
        tctx = msg.task.payload.get(TRACE_KEY)
        if isinstance(tctx, dict) and tctx.get("tid") is not None:
            # a defer is still a reply leg of the sampled span tree
            payload[TRACE_KEY] = tctx
            self._trace_disp.pop(tctx["tid"], None)
            flightrec.record(
                "trace.reply", tid=tctx["tid"], node=self.post.node_id,
                verdict="wait",
            )
        payload["table"] = tname
        payload[VERSION_KEY] = self.version_max(tname)
        reply.task = dataclasses.replace(msg.task, payload=payload)
        return reply

    def _consist_incarnation(self, node_id: str, incarnation: int) -> None:
        """Van callback: a peer restarted under the same id — prune the
        dead incarnation's clock entry so it cannot wedge the fleet
        minimum (the new incarnation re-registers via ``consist_hello``
        or its first stamped request)."""
        for st in self._consist.values():
            st["clock"].on_incarnation_advance(node_id, incarnation)

    # -- staleness version clock (ISSUE 10) -----------------------------------
    def version_max(self, table: str) -> int:
        """Highest segment version of this shard (0 when it owns nothing)."""
        ver = self._seg_versions[table]
        return int(ver.max()) if ver.size else 0

    def _stamp_version(self, msg: Message, reply: Message, sver: int) -> Message:
        """Stamp ``__sver__`` onto a data reply, copy-on-write.

        ``Message.reply`` shares the request's Task (and payload dict) — on
        a Loopback plane that dict IS the sender's object, so the stamp must
        replace the Task with a fresh payload, exactly as ``_fence_reply``
        does, never mutate in place.

        Sampled request tracing (ISSUE 18): the request's ``__trace__``
        context rides the copied payload back automatically, which is what
        lets the worker close the span tree off this ack/reply; this is
        also the one choke point every data reply passes, so the
        ``trace.reply`` event and the dispatch → reply-built attribution
        (``trace.apply``) are recorded here, gated on the sampled context.
        """
        tctx = msg.task.payload.get(TRACE_KEY)
        if isinstance(tctx, dict) and tctx.get("tid") is not None:
            t_disp = self._trace_disp.pop(tctx["tid"], None)
            if t_disp is not None:
                self._trace_hist("trace.apply").record(
                    max(time.monotonic() - t_disp, 0.0)
                )
            flightrec.record(
                "trace.reply",
                tid=tctx["tid"],
                node=self.post.node_id,
                verdict="ok",
            )
        reply.task = dataclasses.replace(
            msg.task, payload={**msg.task.payload, VERSION_KEY: sver}
        )
        return reply

    def _forward_push(self, tname: str, msg: Message) -> None:
        fwd = Message(
            task=Task(TaskKind.PUSH, self._fwd.name, payload={"table": tname}),
            recver=self.replica,
            keys=np.asarray(msg.keys),
            # a device plane arrives padded past its keys (``push_device``)
            values=[np.asarray(msg.values[0])[: len(msg.keys)]],
        )
        ts = self._fwd.submit([fwd])
        if self.replica_sync:
            if not self._fwd.wait(ts, timeout=self.replica_ack_timeout):
                # deadline: free the stuck task before failing the push —
                # the fwd customer must not leak _pending state per timeout
                self._fwd.cancel(ts, "replica ack deadline")
                raise RuntimeError(
                    f"replica {self.replica} did not ack push (sync chain)"
                )
            self._fwd.check(ts)
        else:
            self._fwd_inflight.append(ts)
            while len(self._fwd_inflight) > self.max_replica_lag:
                old = self._fwd_inflight.popleft()
                if not self._fwd.wait(old, timeout=self.replica_ack_timeout):
                    self._fwd.cancel(old, "replica ack deadline")
                    raise RuntimeError(
                        f"replica {self.replica} lag exceeded "
                        f"{self.max_replica_lag} and oldest ack timed out"
                    )

    def flush_replica(self, timeout: float = 60.0) -> None:
        """Block until every async-forwarded push is acked by the replica."""
        while self._fwd_inflight:
            old = self._fwd_inflight.popleft()
            if not self._fwd.wait(old, timeout):
                self._fwd.cancel(old, "replica flush deadline")
                raise RuntimeError(f"replica flush: ts={old} not acked")

    def _forward_control(self, payload: dict, keys=None, values=None) -> None:
        """Replica-chain a migration control op, synchronously.

        Rides the same per-link FIFO as forwarded pushes, so the standby
        applies the shard-map change AFTER every push that preceded it here.
        """
        msg = Message(
            task=Task(TaskKind.CONTROL, self._fwd.name, payload=payload),
            recver=self.replica,
            keys=keys,
            values=values if values is not None else [],
        )
        ts = self._fwd.submit([msg], keep_responses=True)
        if not self._fwd.wait(ts, timeout=self.replica_ack_timeout):
            self._fwd.cancel(ts, "replica control deadline", remote=True)
            self._fwd.take_responses(ts)
            raise RuntimeError(
                f"replica {self.replica} did not ack {payload.get('op')!r}"
            )
        errs = self._fwd.errors(ts)
        self._fwd.take_responses(ts)
        if errs:
            raise RuntimeError(
                f"replica {payload.get('op')!r} failed: " + "; ".join(errs)
            )

    def counters(self) -> dict:
        """Migration/fence counters, Dashboard-mergeable (utils.metrics)."""
        out = {
            "fenced_rejects": self.fenced_rejects,
            "ro_pulls": self.ro_pulls,
            # ids the applies had to visit, of the bucket slots they were
            # padded to: the pad share a flat plane's apply skips
            "apply_ids_real": self.apply_ids_real,
            "apply_ids_bucket": self.apply_ids_bucket,
            "localize_requests": self.localize_requests,
            "localize_native": self.localize_native,
            # hierarchical push (ISSUE 15): fan-in totals the telemetry
            # plane derives grp_pct from (group-reduced applies / raw
            # member contributions they replaced)
            "group_pushes": self.group_pushes,
            "group_members": self.group_members,
            "rows_migrated_in": self.rows_migrated_in,
            "rows_migrated_out": self.rows_migrated_out,
            "migration_freeze_s": round(self.migration_freeze_s, 6),
            # staleness plane: the shard's highest segment version, summed
            # over tables — a cheap fleet-wide write-progress gauge
            "seg_version_max": sum(
                self.version_max(t) for t in self.tables
            ),
            # durability plane (ISSUE 16): seconds since this shard last
            # committed to (or restored from) a durable snapshot — the
            # gauge pstop's CKPT column and the ckpt-age SLO watch —
            # plus commit totals and the bounded-freeze accounting
            "ckpt_age_s": round(time.monotonic() - self._ckpt_commit_t, 3),
            "ckpt_commits": self.ckpt_commits,
            "ckpt_freeze_s": round(self.ckpt_freeze_s, 6),
            "ckpt_delta_rows": self.ckpt_delta_rows,
            "ckpt_delta_overflow": self.ckpt_delta_overflow,
        }
        if self._consist:
            # consistency plane (ISSUE 20): defer/release totals plus the
            # mode/bound gauges pstop's MODE/BOUND columns decode (first
            # gated table by name — fleets gate one training table; the
            # clock size/prune gauges make membership drift visible)
            first = self._consist[sorted(self._consist)[0]]
            out["consist_defers"] = self.consist_defers
            out["consist_releases"] = self.consist_releases
            out["consist_mode"] = MODE_CODES[first["mode"]]
            out["consist_bound"] = (
                -1 if first["bound"] is None else int(first["bound"])
            )
            out["consist_clock_size"] = sum(
                st["clock"].size() for st in self._consist.values()
            )
            out["consist_pruned"] = sum(
                st["clock"].pruned for st in self._consist.values()
            )
        if self.ledger is not None:
            # device-plane gauges + totals (inflight_bundles/rows,
            # backlog_age_s, applies_*): ride the same counter channel —
            # telemetry's delta framing reconstructs gauges exactly
            out.update(self.ledger.counters())
        return out

    def latency_digests(self) -> Dict[str, dict]:
        """Device-plane apply attribution digests for the telemetry
        publisher (``apply.<t>`` total + host/h2d/dev splits, cumulative),
        plus the serving plane's read-only pull latency (``ro_pull.<t>``,
        the ``ro-p99`` SLO's metric)."""
        out = (
            self.ledger.latency_digests() if self.ledger is not None else {}
        )
        for t, hist in self.ro_hist.items():
            if hist.count:
                out[f"ro_pull.{t}"] = hist.to_dict()
        # tracing plane (ISSUE 18): trace.wire / trace.sq / trace.apply —
        # the series pstop's WIREµs/SQµs/APLY% columns and the
        # ``trace-wire-p99`` SLO (utils/slo.py tracing_plane_specs) consume
        for name, hist in self._trace_hists.items():
            if hist.count:
                out[name] = hist.to_dict()
        return out

    # -- request handling -----------------------------------------------------
    @staticmethod
    def _trace_tid_of(group: List[tuple]) -> Optional[str]:
        """First sampled member's trace id of a batched push group — the
        one the grouped apply's device attribution is charged to (pure
        dict lookups: stays sync-free on the batched-apply path)."""
        for _i, m, *_rest in group:
            tctx = m.task.payload.get(TRACE_KEY)
            if isinstance(tctx, dict) and tctx.get("tid") is not None:
                return tctx["tid"]
        return None

    def _trace_hist(self, name: str) -> LatencyHistogram:
        hist = self._trace_hists.get(name)
        if hist is None:
            hist = self._trace_hists[name] = LatencyHistogram()
        return hist

    def _trace_dispatch(self, msg: Message) -> None:
        """Handler-entry attribution for a sampled request (ISSUE 18).

        Gated on the request actually carrying a trace context — unsampled
        requests (the vast majority) cost one dict lookup here, nothing
        more (``tools/check_wrappers.py`` enforces the gate by AST).
        Records the ``trace.dispatch`` event and feeds the live
        wire/server-queue histograms from the context's origin/receive
        stamps; the dispatch time is kept so the reply site can attribute
        dispatch → reply-built into ``trace.apply``.
        """
        payload = msg.task.payload
        tctx = payload.get(TRACE_KEY) if isinstance(payload, dict) else None
        if isinstance(tctx, dict) and tctx.get("tid") is not None:
            now = time.monotonic()
            tid = tctx["tid"]
            t0 = tctx.get("t")
            rx = tctx.get("rx")
            if rx is not None:
                # wire transit proxy: origin submit -> van receive (the
                # rx stamp exists only on wire paths — loopback degrades
                # to no sample rather than a lie)
                if t0 is not None:
                    self._trace_hist("trace.wire").record(max(rx - t0, 0.0))
                self._trace_hist("trace.sq").record(max(now - rx, 0.0))
            while len(self._trace_disp) >= 1024:
                self._trace_disp.pop(next(iter(self._trace_disp)))
            self._trace_disp[tid] = now
            flightrec.record(
                "trace.dispatch",
                tid=tid,
                node=self.post.node_id,
                op=msg.task.kind.name.lower(),
                sender=msg.sender,
            )

    def _span_attrs(self, msg: Message) -> dict:
        """Attributes of a request's ``ps.server.*`` span: the ``req`` that
        joins it to the worker's ``ps.worker.submit`` and, on a sampled
        request, the worker's trace context, echoed so merge_traces can
        pair both ends."""
        t = msg.task
        span_attrs = {
            "req": req_id(msg.sender, t.customer, t.time),
            "table": t.payload.get("table"),
        }
        tctx = t.payload.get("__trace__") or {}
        if tctx.get("tid"):
            span_attrs["trace"] = tctx["tid"]
            span_attrs["origin"] = tctx.get("origin")
        return span_attrs

    def _admit(self, msg: Message, sp):
        """Trace dispatch and :meth:`_validate_data_request` of a request
        whose ``ps.server.*`` span ``sp`` is open: the span says whether the
        consistency gate deferred it."""
        self._trace_dispatch(msg)
        v = self._validate_data_request(msg)
        if isinstance(v, Message):
            sp.set(deferred=int(bool(v.task.payload.get(WAIT_KEY))))
        return v

    def _validate_data_request(self, msg: Message):
        """Routing fence + localization for a PUSH/PULL.

        Returns a fence-reject ``Message``, or the localized
        ``(tname, ids_np, upto, segs)`` tuple when the request may proceed
        (:meth:`_localize_request`: ``upto`` is how many of ``ids_np`` an
        apply has to visit).

        Routing fence (PR-6): a stamped epoch that disagrees means the
        sender routed with a different table generation — reject with the
        current table rather than guessing (an id could alias a row this
        server owns under EITHER generation; applying would double-count
        when the worker retries the reject).  Unstamped requests (replica
        forwards, which follow the primary's apply order by construction)
        skip the epoch check but still ownership-check.
        """
        tname = msg.task.payload["table"]
        repoch = msg.task.payload.get(ROUTING_EPOCH_KEY)
        if repoch is not None and repoch != self.routing.epoch:
            return self._fence_reply(
                msg,
                f"routing epoch mismatch: request {repoch} != "
                f"server {self.routing.epoch}",
            )
        with self.tracer.span("ps.server.localize") as lsp:
            loc = self._localize_request(tname, msg.keys)
            if lsp.recording:
                lsp.set(engine="numpy" if self._keymap is None else "native")
                if loc is not None:
                    lsp.set(
                        keys=int(loc[0].size), real=loc[2],
                        segs=int(loc[1].size),
                    )
        if loc is None:
            return self._fence_reply(
                msg,
                f"not owner: {self.post.node_id} does not own all of "
                f"{len(np.asarray(msg.keys))} requested rows of {tname!r} "
                f"at epoch {self.routing.epoch}",
            )
        # consistency gate (ISSUE 20): a stamped request on a gated table
        # must sit within ``bound`` of the fleet minimum or it is deferred
        # with a typed ``__wait__`` reply.  AFTER the routing checks (a
        # mis-routed request must fence, not wait) and only for stamped
        # traffic — old workers and read-only serving pulls bypass.
        cstep = msg.task.payload.get(CONSIST_STEP_KEY)
        if cstep is not None and tname in self._consist:
            st = self._consist[tname]
            allowed, fm = st["clock"].gate(
                msg.sender, int(cstep), st["bound"]
            )
            if not allowed:
                return self._wait_reply(msg, tname, int(cstep), fm)
            waiting = self._consist_waiting[tname]
            if msg.sender in waiting:
                waiting.discard(msg.sender)
                self.consist_releases += 1
                flightrec.record(
                    "consist.release", node=self.post.node_id,
                    sender=msg.sender, table=tname, step=int(cstep),
                    fleet_min=fm,
                )
        ids_np, segs, _real, upto = loc
        return tname, ids_np, upto, segs

    def _pad_ids(self, table: KVTable, ids_np: np.ndarray, b: int) -> np.ndarray:
        # Bucket-pad the slice to a power of two: the worker bucket-pads its
        # unique slots, but the per-server split (Parameter::Slice) produces
        # arbitrary lengths again — without this every distinct length
        # compiles a fresh device step, and the pallas kernels (block DMA)
        # reject unaligned id vectors outright.  Pads route to the trash row
        # with zero gradients (the established PAD contract).
        n = int(ids_np.shape[0])
        if b == n:
            return ids_np
        padded_ids = np.full(b, table.rows, dtype=np.int32)
        padded_ids[:n] = ids_np
        return padded_ids

    def _counted(self, real: int, b: int) -> np.int32:
        """``real``, the ids an apply visits of the ``b`` its bucket holds,
        as ``KVTable.push*`` takes it; both counted (``counters``)."""
        self.apply_ids_real += real
        self.apply_ids_bucket += b
        return np.int32(real)

    def _put(self, x) -> jax.Array:
        """Stage a request array on THIS server's chip, committed — staged
        on the default device it would be copied across on every push."""
        return jax.device_put(x, self.device)

    def _upload_values(self, vals, b: int, n: int) -> jax.Array:
        # direct device handoff: the wire value plane (a zero-copy
        # frombuffer view of the received frame) feeds the device transfer
        # as-is — no intermediate padded host copy.  A device plane pushed
        # by a worker on another chip crosses here, once.
        # (``push_device`` hands its planes over already padded to ``b``
        # with zeros: nothing to do, and no program a leg size.)
        vals = self._put(vals if isinstance(vals, jax.Array) else np.asarray(vals))
        have = int(vals.shape[0])
        if b != have:  # pad on device (exact zeros: bitwise-neutral)
            vals = jnp.pad(vals, ((0, b - have),) + ((0, 0),) * (vals.ndim - 1))
        return vals

    def _stack_planes(
        self, table: KVTable, group: List[tuple], k: int, bm: int, tok=None
    ) -> jax.Array:
        """Assemble the bundle's ``(k, bm, dim)`` value stack.

        Wire planes (host numpy views of the received frame) pack into ONE
        pinned host buffer and ride a single H2D transfer — measurably
        cheaper than k separate uploads plus a device-side ``stack`` (which
        re-copies the whole bundle through the CPU client).  Device-resident
        planes (Loopback ``push_device`` traffic) skip the host and stack on
        device; zero-pads are exact zeros either way, so both routes are
        bitwise-identical.
        """
        if all(not isinstance(m.values[0], jax.Array) for _, m, *_ in group):
            dim = table.dim
            buf = np.empty((k, bm, dim), dtype=np.dtype(table.cfg.dtype))
            for i, (_, m, _, ids_np, _, _) in enumerate(group):
                n = int(ids_np.shape[0])
                buf[i, :n] = np.asarray(m.values[0]).reshape(n, dim)
                if n < bm:  # pads must stay exact zeros (bitwise-neutral)
                    buf[i, n:] = 0.0
            if tok is not None:
                tok.mark_host()  # pinned-buffer pack done; H2D is next
            stack = self._put(buf)
            if tok is not None:
                tok.mark_h2d()
            return stack
        planes = []
        for _, m, _, ids_np, _, _ in group:
            n = int(ids_np.shape[0])
            planes.append(self._upload_values(m.values[0], bm, n))
        if tok is not None:
            tok.mark_host()  # device-resident planes: no host pack phase
        stack = jnp.stack(planes)
        if tok is not None:
            tok.mark_h2d()
        return stack

    def _handle_push_single(
        self,
        msg: Message,
        tname: str,
        ids_np: np.ndarray,
        real: int,
        segs: np.ndarray,
        sp,
    ) -> Message:
        """Apply one push under its open ``ps.server.push`` span ``sp``;
        ``real`` of its ``ids_np`` are to be visited (the localization's
        ``upto``)."""
        table = self.tables[tname]
        n = int(ids_np.shape[0])
        b = _bucket(n)
        sp.set(rows=n, bucket=b, real=real, members=1)
        tctx = msg.task.payload.get(TRACE_KEY)
        tok = (
            self.ledger.begin(
                tname,
                1,
                n,
                tid=tctx.get("tid") if isinstance(tctx, dict) else None,
            )
            if self.ledger is not None
            else None
        )
        with self.tracer.span("ps.server.h2d") as h2d:
            ids_host = self._pad_ids(table, ids_np, b)
            if tok is not None:
                tok.mark_host()
            ids = self._put(ids_host)
            vals = self._upload_values(msg.values[0], b, n)
            if tok is not None:
                tok.mark_h2d()
            h2d.set(bytes=ids.nbytes + vals.nbytes)
        with self.tracer.span("ps.server.dispatch", op="push"):
            ref = table.push(ids, vals, self._counted(real, b))
        with self.tracer.span("ps.server.ack", kind="push"):
            if tok is not None:
                self.ledger.submit(tok, ref, lambda t=table: t.value)
            return self._ack_push(msg, tname, segs)

    @staticmethod
    def _written_keys(msg: Message) -> np.ndarray:
        """A push's keys as the ``int64`` the dirty tracking compares in.
        Built only while a migration or a snapshot is open (``_ack_push``):
        a leg outside those windows pays no conversion pass.  The keys are
        the HOST wire plane, so this observes no device result."""
        return np.asarray(msg.keys, dtype=np.int64)

    def _ack_push(self, msg: Message, tname: str, segs: np.ndarray) -> Message:
        """Post-dispatch bookkeeping + ack: the SYNC-FREE tail of every push.

        Runs after the device apply is dispatched but makes no attempt to
        observe its result — no ``np.asarray``/``device_get``/
        ``block_until_ready`` may appear here (``tools/check_wrappers.py``
        enforces this by AST), so the worker's ack latency is host-side
        bookkeeping only, never device-apply latency.  (``_forward_push``
        is host-side wire I/O on pre-upload planes; in ``replica_sync``
        mode it deliberately blocks on the CHAIN ack, not on device work.)
        """
        self.pushes += 1
        cstep = msg.task.payload.get(CONSIST_STEP_KEY)
        if cstep is not None and tname in self._consist:
            # consistency plane (ISSUE 20): the stamped push is APPLIED —
            # the sender committed its step, so its vector-clock entry
            # advances past it (pure dict/int ops: stays sync-free)
            self._consist[tname]["clock"].commit(msg.sender, int(cstep))
        grp = msg.task.payload.get(GROUP_KEY)
        if grp is not None:
            # hierarchical push (ISSUE 15): this ONE apply stands for the
            # whole group's step — count the fan-in so the wire reduction
            # is reportable (pure dict/int ops: stays sync-free)
            self.group_pushes += 1
            self.group_members += int(grp.get("n") or 1)
        # staleness clock: every apply bumps the touched segments; the
        # ack carries the post-bump max so the pusher's next pulls can
        # be measured against a version it knows it contributed to
        ver = self._seg_versions[tname]
        if segs.size:
            ver[segs] += 1
            sver = int(ver[segs].max())
        else:
            sver = self.version_max(tname)
        kn = (
            self._written_keys(msg)
            if self._migrations or self._snapshots
            else None
        )
        if self._migrations:
            # dirty tracking: rows in a migrating range changed after
            # their chunk may have shipped — the commit delta re-sends
            # them, bounding the freeze to exactly this set
            for m in self._migrations.values():
                if m["table"] == tname:
                    hit = kn[(kn >= m["lo"]) & (kn < m["hi"])]
                    m["dirty"].update(int(x) for x in hit)
        if self._snapshots:
            # durability plane: rows written during an open snapshot
            # window go stale against the already-written segment files —
            # snap_commit re-exports exactly this set as the delta log,
            # which is what bounds the commit freeze (pure host set ops:
            # stays sync-free, same as the migration tracking above)
            hit = kn[kn < self.routing.tables[tname].rows]
            for sn in self._snapshots.values():
                sn["dirty"].setdefault(tname, set()).update(
                    int(x) for x in hit
                )
        if self.replica is not None:
            # forward AFTER the local apply, in apply order (this recv
            # thread is the only writer), so the standby replays the
            # identical update sequence
            self._forward_push(tname, msg)
        reply = self._stamp_version(msg, msg.reply(), sver)
        if self.ledger is not None and self.ledger.overloaded():
            # soft backpressure: the update WAS applied; the hint tells the
            # worker's admission control to slow down.  overloaded() is a
            # host-side flag maintained by the reaper — reading it here
            # keeps the ack sync-free.  _stamp_version already replaced the
            # Task payload with a fresh dict, so this cannot leak into the
            # sender's payload object on a Loopback plane.
            reply.task.payload[BUSY_KEY] = True
        return reply

    def _dispatch_pull(
        self, tname: str, ids_np: np.ndarray, op: str, sp
    ) -> Tuple[jax.Array, int]:
        """Upload the bucket-padded ids and enqueue the gather, under the
        request's open ``ps.server.pull`` span ``sp``."""
        table = self.tables[tname]
        n = int(ids_np.shape[0])
        b = _bucket(n)
        sp.set(rows=n, bucket=b)
        with self.tracer.span("ps.server.h2d", bytes=4 * b):
            ids = self._put(self._pad_ids(table, ids_np, b))
        with self.tracer.span("ps.server.dispatch", op=op):
            rows = table.pull(ids)
        return rows, n

    def _to_host(self, rows):
        """The D2H of a pull's reply (one array, or a bundle's list)."""
        bundle = isinstance(rows, list)
        nbytes = sum(r.nbytes for r in rows) if bundle else rows.nbytes
        with self.tracer.span("ps.server.d2h", bytes=nbytes):
            return jax.device_get(rows) if bundle else np.asarray(rows)

    def _device_reply(self, rows):
        """What a pull's reply carries under ``device_replies``: the
        bucket-padded gather whole (rows past the leg's count are the trash
        row's; the worker drops them: ``rows[:n]`` on the device is one
        compiled program a leg size).  Device arrays are handed over by
        reference, inside one process (``core/resender.py``: they never ride
        a wire buffer); a plane that is serialised all the same goes out at
        its bucket's size.  The reply's D2H stage keeps its span, ``bytes``
        0, so that a reader of the stage finds it and reads that it costs
        nothing here (a few microseconds: the span's own cost)."""
        with self.tracer.span("ps.server.d2h", bytes=0):
            return rows

    def _ack_pull(self, msg: Message, vals, sver: int) -> Message:
        """A pull's reply, built once its rows are there (``ps.server.d2h``
        has closed): the acknowledgement stage of a pull."""
        with self.tracer.span("ps.server.ack", kind="pull"):
            return self._stamp_version(msg, msg.reply(values=[vals]), sver)

    def _pull_device(
        self, tname: str, ids_np: np.ndarray, segs: np.ndarray, sp
    ) -> Tuple[jax.Array, int, int]:
        """Dispatch the device gather; D2H is the CALLER's choice (the
        bundle path defers it to one transfer per bundle)."""
        rows, n = self._dispatch_pull(tname, ids_np, "pull", sp)
        self.pulls += 1
        # staleness clock: the reply carries the current version of the
        # touched segments (read, not bumped) — what the worker computes
        # on is exactly this version of those ranges
        ver = self._seg_versions[tname]
        sver = int(ver[segs].max()) if segs.size else self.version_max(tname)
        return rows, n, sver

    def _pull_ro_device(
        self, tname: str, ids_np: np.ndarray, segs: np.ndarray, sp
    ) -> Tuple[jax.Array, int, int]:
        """Read-only fast-path gather (ISSUE 13): same device dispatch as
        ``_pull_device`` but on the serving books — its own counter and
        per-table latency histogram, and (in the bundle path) NO flush of
        the open push group.  Skips everything a write needs: optimizer,
        dup policy, ApplyLedger, replica forwarding."""
        rows, n = self._dispatch_pull(tname, ids_np, "pull_ro", sp)
        self.ro_pulls += 1
        ver = self._seg_versions[tname]
        sver = int(ver[segs].max()) if segs.size else self.version_max(tname)
        return rows, n, sver

    def handle_request(self, msg: Message) -> Message:
        if msg.task.kind == TaskKind.CONTROL:
            return self._handle_control(msg)
        if msg.task.kind == TaskKind.PUSH:
            with self.tracer.span(
                "ps.server.push", **self._span_attrs(msg)
            ) as sp:
                v = self._admit(msg, sp)
                if isinstance(v, Message):
                    return v
                return self._handle_push_single(msg, *v, sp)
        if msg.task.kind != TaskKind.PULL:
            raise ValueError(f"unsupported task kind {msg.task.kind}")
        # validation to reply built: the D2H is inside
        with self.tracer.span("ps.server.pull", **self._span_attrs(msg)) as sp:
            v = self._admit(msg, sp)
            if isinstance(v, Message):
                return v
            tname, ids_np, _upto, segs = v
            if msg.task.payload.get(READ_ONLY_KEY):
                t0 = time.perf_counter()
                rows, n, sver = self._pull_ro_device(tname, ids_np, segs, sp)
                if self.device_replies:
                    vals = self._device_reply(rows)
                else:
                    vals = self._to_host(rows)[:n]
                self.ro_hist[tname].record(time.perf_counter() - t0)
                return self._ack_pull(msg, vals, sver)
            rows, n, sver = self._pull_device(tname, ids_np, segs, sp)
            if self.device_replies:
                return self._ack_pull(msg, self._device_reply(rows), sver)
            return self._ack_pull(msg, self._to_host(rows)[:n], sver)

    # -- bundle-batched apply engine (ISSUE 11) -------------------------------
    def _error_reply(self, msg: Message, exc: Exception) -> Message:
        """Per-member failure reply, same shape the Postoffice emits for a
        raising single-request handler."""
        reply = msg.reply()
        payload = {"__error__": f"{type(exc).__name__}: {exc}"}
        tctx = msg.task.payload.get(TRACE_KEY)
        if isinstance(tctx, dict):
            # keep the sampled span tree closable even on a failed member
            payload[TRACE_KEY] = tctx
        reply.task = dataclasses.replace(msg.task, payload=payload)
        return reply

    def handle_request_batch(self, msgs: List[Message]) -> List[Message]:
        """Bundle-batched request handling (the fused apply engine).

        A coalesced frame's members arrive together; this path preserves
        their sequential semantics while collapsing the device traffic:

        - consecutive same-table PUSHes (up to ``apply.apply_batch``) become
          ONE donated-buffer device apply (``_apply_push_group``) instead of
          one jit call per member;
        - every PULL's D2H readback is deferred so the whole bundle costs a
          single ``jax.device_get`` (none at all under ``device_replies``).

        A PULL, CONTROL, fence, or table switch flushes the open PUSH run
        first, so each member still observes exactly the writes that
        preceded it in bundle order.  Failures are isolated per member (the
        failing member answers ``__error__``; the rest of the bundle
        proceeds), except that a grouped device apply fails its whole group
        — the group is one device call by design.

        Read-only pulls (``__ro__``, ISSUE 13) are the exception to the
        flush rule: they deliberately do NOT flush the open push group —
        the serving plane's relaxed-read contract is "the table as of
        dispatch", so a read-only member may observe the shard WITHOUT the
        writes riding the same bundle.  They defer to their own single
        ``jax.device_get`` and record into the ``ro_pull.<t>`` histogram.
        """
        replies: List[Optional[Message]] = [None] * len(msgs)
        pulls: List[tuple] = []  # (i, msg, rows, n, sver)
        ro: List[tuple] = []  # (i, msg, tname, rows, n, sver, t0)
        group: List[tuple] = []  # (i, msg, tname, ids_np, upto, segs)

        def flush_group() -> None:
            if not group:
                return
            try:
                if len(group) == 1:
                    i, m, tname, ids_np, upto, segs = group[0]
                    with self.tracer.span(
                        "ps.server.push", **self._span_attrs(m)
                    ) as sp:
                        replies[i] = self._handle_push_single(
                            m, tname, ids_np, upto, segs, sp
                        )
                else:
                    self._apply_push_group(group, replies)
            except Exception as e:  # noqa: BLE001
                logging.getLogger(__name__).exception(
                    "%s: batched push apply failed (%d members)",
                    self.post.node_id,
                    len(group),
                )
                for i, m, *_ in group:
                    replies[i] = self._error_reply(m, e)
            group.clear()

        batch_cap = max(1, self.apply_cfg.apply_batch)
        for i, msg in enumerate(msgs):
            try:
                if msg.task.kind == TaskKind.CONTROL:
                    flush_group()
                    replies[i] = self._handle_control(msg)
                    continue
                self._trace_dispatch(msg)
                v = self._validate_data_request(msg)
                if isinstance(v, Message):
                    flush_group()  # the fence observes prior writes too
                    replies[i] = v
                    continue
                tname, ids_np, upto, segs = v
                if msg.task.kind == TaskKind.PUSH:
                    if group and (
                        group[0][2] != tname or len(group) >= batch_cap
                    ):
                        flush_group()
                    group.append((i, msg, tname, ids_np, upto, segs))
                elif msg.task.kind == TaskKind.PULL:
                    if msg.task.payload.get(READ_ONLY_KEY):
                        # NO flush_group(): relaxed read, see docstring
                        t0 = time.perf_counter()
                        with self.tracer.span(
                            "ps.server.pull", **self._span_attrs(msg)
                        ) as sp:
                            rows, n, sver = self._pull_ro_device(
                                tname, ids_np, segs, sp
                            )
                        ro.append((i, msg, tname, rows, n, sver, t0))
                        continue
                    flush_group()  # the pull must see prior member pushes
                    with self.tracer.span(
                        "ps.server.pull", **self._span_attrs(msg)
                    ) as sp:
                        rows, n, sver = self._pull_device(
                            tname, ids_np, segs, sp
                        )
                    pulls.append((i, msg, rows, n, sver))
                else:
                    raise ValueError(
                        f"unsupported task kind {msg.task.kind}"
                    )
            except Exception as e:  # noqa: BLE001
                logging.getLogger(__name__).exception(
                    "%s: handler error for %s from %s",
                    self.post.node_id,
                    msg.task.kind,
                    msg.sender,
                )
                replies[i] = self._error_reply(msg, e)
        flush_group()
        self._finish_pulls(pulls, replies)
        self._finish_ro_pulls(ro, replies)
        return replies

    def _finish_pulls(self, pulls: List[tuple], replies: List) -> None:
        """Materialize deferred pull replies: ONE host readback per bundle
        (zero under ``device_replies`` — the rows stay on device)."""
        if not pulls:
            return
        if self.device_replies:
            for i, m, rows, n, sver in pulls:
                replies[i] = self._ack_pull(m, self._device_reply(rows), sver)
            return
        host = self._to_host([rows for _, _, rows, _, _ in pulls])
        for (i, m, _, n, sver), h in zip(pulls, host):
            replies[i] = self._ack_pull(m, h[:n], sver)

    def _finish_ro_pulls(self, ro: List[tuple], replies: List) -> None:
        """Materialize deferred READ-ONLY pull replies: the bundle's other
        single ``jax.device_get``, with per-member serving latency recorded
        from each member's dispatch time."""
        if not ro:
            return
        if self.device_replies:
            for i, m, tname, rows, n, sver, t0 in ro:
                replies[i] = self._ack_pull(m, self._device_reply(rows), sver)
                self.ro_hist[tname].record(time.perf_counter() - t0)
            return
        host = self._to_host([rows for _, _, _, rows, _, _, _ in ro])
        done = time.perf_counter()
        for (i, m, tname, _, n, sver, t0), h in zip(ro, host):
            replies[i] = self._ack_pull(m, h[:n], sver)
            self.ro_hist[tname].record(done - t0)

    def _apply_push_group(self, group: List[tuple], replies: List) -> None:
        """One device apply for a run of same-table PUSHes.

        Member value planes upload as-is and zero-pad ON DEVICE to the
        common bucket ``bm`` (stack shape ``(k, bm, dim)``), so the jitted
        apply's compile-cache keys stay bucketed: ``(k, bm)`` pairs, never
        raw wire lengths.  Duplicate rows ACROSS members follow
        ``apply.dup_policy`` — occurrence ``"rounds"`` (bitwise-sequential)
        or device ``segment_combine`` (``"combine"``, classic PS sum).
        Bookkeeping (staleness bumps, dirty tracking, replica forwarding,
        acks) then runs per member in member order, exactly as sequential
        handling would have.
        """
        tname = group[0][2]
        table = self.tables[tname]
        k = len(group)
        bm = _bucket(max(int(g[3].shape[0]) for g in group))
        rows = sum(int(g[3].shape[0]) for g in group)
        tok = (
            self.ledger.begin(
                tname, k, rows, tid=self._trace_tid_of(group)
            )
            if self.ledger is not None
            else None
        )
        # one span for the group, under its first member's ``req``
        with self.tracer.span(
            "ps.server.push", **self._span_attrs(group[0][1]),
            rows=rows, bucket=bm, members=k,
        ) as sp:
            with self.tracer.span("ps.server.h2d") as h2d:
                stack = self._stack_planes(table, group, k, bm, tok)
                h2d.set(bytes=stack.nbytes)
            # flat positions of every REAL id occurrence, in member order
            ids_list = [g[3] for g in group]
            all_ids = np.concatenate(ids_list).astype(np.int64)
            flat_pos = np.concatenate(
                [
                    i * bm + np.arange(a.shape[0], dtype=np.int32)
                    for i, a in enumerate(ids_list)
                ]
            ).astype(np.int32)
            real = all_ids != table.rows
            rid = all_ids[real]
            rpos = flat_pos[real]
            sp.set(real=int(rid.size))
            if self.apply_cfg.dup_policy == "combine":
                ref = self._push_group_combined(table, k, bm, rid, rpos, stack)
            else:
                ref = self._push_group_rounds(table, k, bm, rid, rpos, stack)
            # one acknowledgement stage for the group, as one span holds it
            with self.tracer.span("ps.server.ack", kind="push"):
                if tok is not None:
                    self.ledger.submit(tok, ref, lambda t=table: t.value)
                for i, m, tname_, _, _, segs in group:
                    replies[i] = self._ack_push(m, tname_, segs)

    def _push_group_rounds(
        self,
        table: KVTable,
        k: int,
        bm: int,
        rid: np.ndarray,
        rpos: np.ndarray,
        stack: jax.Array,
    ) -> jax.Array:
        """Occurrence-round partitioning: round ``t`` applies each row's
        ``t``-th contribution in member order.  Row updates are independent
        and the optimizer is row-wise, so the per-row grad sequence — and
        therefore the result — is bitwise-identical to sequential
        per-member applies, for EVERY optimizer.  With no cross-member
        duplicates (the common case) this is exactly one device call."""
        pad_pos = k * bm  # the appended zero row
        if rid.size == 0:
            rounds = [(rid, rpos)]
        else:
            order = np.argsort(rid, kind="stable")
            sid = rid[order]
            spos = rpos[order]
            newgrp = np.empty(sid.shape, dtype=bool)
            newgrp[0] = True
            newgrp[1:] = sid[1:] != sid[:-1]
            ar = np.arange(sid.size, dtype=np.int64)
            grp_start = np.maximum.accumulate(np.where(newgrp, ar, 0))
            occ = ar - grp_start
            rounds = [
                (sid[occ == t], spos[occ == t])
                for t in range(int(occ.max()) + 1)
            ]
        ref = None
        for uids_t, pos_t in rounds:
            nt = int(uids_t.size)
            bu = _bucket(nt)
            ids_np = np.full(bu, table.rows, dtype=np.int32)
            ids_np[:nt] = uids_t.astype(np.int32)
            pos_np = np.full(bu, pad_pos, dtype=np.int32)
            pos_np[:nt] = pos_t
            with self.tracer.span("ps.server.dispatch", op="push_batch"):
                ref = table.push_batch(
                    self._put(ids_np), self._put(pos_np), stack,
                    self._counted(nt, bu),
                )
        return ref  # last round's value: its readiness bounds every round

    def _push_group_combined(
        self,
        table: KVTable,
        k: int,
        bm: int,
        rid: np.ndarray,
        rpos: np.ndarray,
        stack: jax.Array,
    ) -> jax.Array:
        """Device pre-merge: duplicate rows across members segment-sum into
        one gradient row (the reference's ParallelOrderedMatch merge), then
        ONE apply — classic PS sum semantics (sequential-identical only for
        disjoint member rows)."""
        uids, inv_real = np.unique(rid, return_inverse=True)
        nu = int(uids.size)
        bu = _bucket(nu)
        if bu == nu and nu < k * bm:
            # every slot holds a real row but pad positions still need a
            # trash slot to sum (exact zeros) into — grow one bucket
            bu = _bucket(nu + 1)
        ids_np = np.full(bu, table.rows, dtype=np.int32)
        ids_np[:nu] = uids.astype(np.int32)
        inverse = np.full(k * bm, min(nu, bu - 1), dtype=np.int32)
        inverse[rpos] = inv_real.astype(np.int32)
        with self.tracer.span("ps.server.dispatch", op="push_combined"):
            return table.push_combined(
                self._put(ids_np), self._put(inverse), stack,
                self._counted(nu, bu),
            )

    # -- shard transfer (same-id restart: kv/replica.restart_same_id) --------
    def export_shard(self) -> Dict[str, dict]:
        """Host-side snapshot of every table shard: value + optimizer state.

        The live-donor half of same-id restart recovery: a hot standby
        exports, the restarted primary imports, and the pair is bit-identical
        — including optimizer accumulators, which the wire protocol never
        carries (only the chain forwarding replays them).
        """
        shard = {}
        for t, table in self.tables.items():
            value, state = table.host_planes()
            shard[t] = {"value": value, "state": state}
        return shard

    def import_shard(self, shard: Dict[str, dict]) -> None:
        """Adopt an :meth:`export_shard` snapshot wholesale.

        Row ranges must match (same ``server_index`` and the same routing
        generation — post-migration restarts pass ``routing=`` at
        construction); the donated push buffers are simply replaced, so the
        next push jit-step runs on the imported arrays.
        """
        for t, blob in shard.items():
            self.tables[t].resize(blob["value"], blob["state"])

    def _export_rows(
        self, table: str, gids: np.ndarray
    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """Snapshot value + optimizer-state rows at GLOBAL ids (owned)."""
        tbl = self.tables[table]
        local, owned = self._try_localize(table, gids)
        if not owned.all():
            raise ValueError(
                f"export of un-owned rows of {table!r} on {self.post.node_id}"
            )
        value, state = tbl.host_planes()
        return value[local], {k: v[local] for k, v in state.items()}

    def export_range(
        self, table: str, lo: int, hi: int
    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """:meth:`export_shard` generalized to an arbitrary global range."""
        return self._export_rows(table, np.arange(lo, hi, dtype=np.int64))

    # -- live migration (PR-6) ------------------------------------------------
    def _ensure_mig(self) -> Customer:
        """Donor-side streaming customer on its own endpoint (deadlock-free:
        stage/install acks are processed by the ``.mig`` recv thread while
        this server's recv thread blocks inside the migration handler)."""
        if self._mig is None:
            mig_post = Postoffice(f"{self.post.node_id}.mig", self.post.van)
            self._mig = Customer(self.name, mig_post)
        return self._mig

    def _mig_rpc(
        self, recver: str, payload: dict, keys=None, values=None
    ) -> Message:
        mig = self._ensure_mig()
        ts = mig.submit(
            [
                Message(
                    task=Task(TaskKind.CONTROL, mig.name, payload=payload),
                    recver=recver,
                    keys=keys,
                    values=values,
                )
            ],
            keep_responses=True,
        )
        if not mig.wait(ts, timeout=self.migrate_timeout):
            mig.cancel(ts, f"migration {payload.get('op')!r} deadline",
                       remote=True)
            mig.take_responses(ts)
            raise TimeoutError(
                f"{payload.get('op')!r} to {recver} timed out"
            )
        errs = mig.errors(ts)
        responses = mig.take_responses(ts)
        if errs:
            raise RuntimeError(
                f"{payload.get('op')!r} to {recver} failed: " + "; ".join(errs)
            )
        return responses[0]

    def _install_routing(
        self, new_routing: RoutingTable, extra: Optional[dict] = None
    ) -> None:
        """Adopt ``new_routing``, rebuilding any table whose segments change.

        ``extra``: ``{table: (gids, value, state)}`` — source rows for
        newly-adopted ranges (the migration payload).  Runs on the recv
        thread, so it is atomic wrt pushes.
        """
        # durability plane: a routing change invalidates every open
        # snapshot's segment bookkeeping (files already written describe
        # the OLD layout) — abort them; the driver's commit then fails
        # loudly and no manifest ever references the torn files
        if self._snapshots:
            for sid in list(self._snapshots):
                del self._snapshots[sid]
                flightrec.record(
                    "ckpt.abort", node=self.post.node_id, sid=sid,
                    why="routing changed mid-snapshot",
                )
        for t, tbl in self.tables.items():
            new_segs = new_routing.tables[t].owned_segments(self.server_index)
            old_segs = self.routing.tables[t].owned_segments(self.server_index)
            ex = (extra or {}).get(t)
            if new_segs == old_segs and ex is None:
                continue
            self._rebuild_table(t, new_segs, ex)
        self.routing = new_routing
        self._shard_maps = {
            t: self._make_map(new_routing, t) for t in self.tables
        }
        # staleness clock across migrations: new segment layouts restart
        # from the shard's previous MAX, so the per-table version never goes
        # backwards (a worker's recorded last-push version stays comparable)
        self._seg_versions = {
            t: np.full(
                self._shard_maps[t][0].shape[0],
                self.version_max(t) if t in self._seg_versions else 0,
                dtype=np.int64,
            )
            for t in self.tables
        }

    def _rebuild_table(
        self, t: str, new_segs: List[Tuple[int, int]], extra
    ) -> None:
        """Re-pack the shard for a new segment layout.

        Every new-layout row must come from either the OLD shard (kept or
        re-ordered rows) or ``extra`` (adopted rows) — anything uncovered is
        a protocol error, never silently zero-initialized.
        """
        tbl = self.tables[t]
        parts = [np.arange(lo, hi, dtype=np.int64) for lo, hi in new_segs]
        gids = (
            np.concatenate(parts) if parts else np.empty(0, dtype=np.int64)
        )
        n = int(gids.shape[0])
        old_v, old_s = tbl.host_planes()
        value = np.empty((n + 1, tbl.dim), dtype=old_v.dtype)
        state = {
            k: np.empty((n + 1, tbl.dim), dtype=old_v.dtype) for k in old_s
        }
        # carry the trash row (re-zeroed every push anyway, but optimizer
        # fills must survive)
        value[n] = old_v[tbl.rows]
        for k in state:
            state[k][n] = old_s[k][tbl.rows]
        local, covered = self._try_localize(t, gids)
        if covered.any():
            src = local[covered]
            value[:n][covered] = old_v[src]
            for k in state:
                state[k][:n][covered] = old_s[k][src]
        if extra is not None:
            ids_e, v_e, s_e = extra
            ids_e = np.asarray(ids_e, dtype=np.int64)
            if ids_e.size:
                pos = np.searchsorted(ids_e, gids)
                pos_c = np.minimum(pos, ids_e.size - 1)
                hit = ids_e[pos_c] == gids
                src = pos_c[hit]
                value[:n][hit] = v_e[src]
                for k in state:
                    state[k][:n][hit] = np.asarray(s_e[k])[src]
                covered = covered | hit
        if n and not covered.all():
            missing = gids[~covered]
            raise RuntimeError(
                f"shard rebuild of {t!r} on {self.post.node_id}: "
                f"{missing.size} rows uncovered (first: {missing[:4]})"
            )
        tbl.resize(value, state)

    def adopt_routing(self, routing) -> bool:
        """Adopt a broadcast routing table (non-participant servers).

        Accepts a :class:`RoutingTable` or its payload dict.  Only newer
        epochs apply, and this path must NOT change this server's owned
        segments — content moves exclusively through the migrate ops.
        """
        if isinstance(routing, dict):
            routing = RoutingTable.from_payload(routing)
        if routing.epoch <= self.routing.epoch:
            return False
        for t in self.tables:
            if (
                routing.tables[t].owned_segments(self.server_index)
                != self.routing.tables[t].owned_segments(self.server_index)
            ):
                raise ValueError(
                    f"adopt_routing would change owned segments of {t!r} on "
                    f"{self.post.node_id}; use the migration protocol"
                )
        self._install_routing(routing)
        return True

    def _handle_migrate(self, msg: Message) -> Message:
        op = msg.task.payload["op"]
        p = msg.task.payload
        if op == "migrate_begin":
            # donor: arm dirty tracking for [lo, hi).  Idempotent restart: a
            # fresh mid for the same range supersedes any stale attempt.
            mid, t, lo, hi = p["mid"], p["table"], int(p["lo"]), int(p["hi"])
            _, owned = self._try_localize(t, np.arange(lo, hi, dtype=np.int64))
            if not owned.all():
                raise ValueError(
                    f"migrate_begin: {self.post.node_id} does not own "
                    f"[{lo}, {hi}) of {t!r}"
                )
            stale = [
                k
                for k, m in self._migrations.items()
                if (m["table"], m["lo"], m["hi"]) == (t, lo, hi)
            ]
            for k in stale:
                del self._migrations[k]
            self._migrations[mid] = {
                "table": t, "lo": lo, "hi": hi, "dirty": set()
            }
            flightrec.record(
                "migrate.begin", node=self.post.node_id, mid=mid,
                table=t, lo=lo, hi=hi,
            )
            return msg.reply()
        if op == "migrate_send":
            # donor: stream one live chunk to the recipient, keep serving
            # between chunks (requests queued behind this handler bound the
            # per-chunk pause, not the whole transfer)
            m = self._migrations[p["mid"]]
            lo, hi = int(p["lo"]), int(p["hi"])
            flightrec.record(
                "migrate.send", node=self.post.node_id, mid=p["mid"],
                to=p["to"], lo=lo, hi=hi,
            )
            value, state = self.export_range(m["table"], lo, hi)
            skeys = sorted(state)
            self._mig_rpc(
                p["to"],
                {
                    "op": "migrate_stage",
                    "mid": p["mid"],
                    "table": m["table"],
                    "lo": lo,
                    "hi": hi,
                    "state_keys": skeys,
                },
                values=[value] + [state[k] for k in skeys],
            )
            return msg.reply()
        if op == "migrate_stage":
            # recipient: buffer a streamed chunk (host memory, not the table)
            st = self._staging.setdefault(
                p["mid"], {"table": p["table"], "chunks": []}
            )
            value = np.asarray(msg.values[0])
            state = {
                k: np.asarray(v)
                for k, v in zip(p["state_keys"], msg.values[1:])
            }
            st["chunks"].append((int(p["lo"]), int(p["hi"]), value, state))
            flightrec.record(
                "migrate.stage", node=self.post.node_id, mid=p["mid"],
                lo=int(p["lo"]), hi=int(p["hi"]),
            )
            return msg.reply()
        if op == "migrate_commit":
            return self._commit_migration(msg)
        if op == "migrate_install":
            return self._install_migration(msg)
        if op == "migrate_adopt":
            # recipient's standby: adopt the fully-assembled range (chain-
            # forwarded by the recipient inside its install, so it lands
            # after every forwarded push that preceded the handoff)
            routing = RoutingTable.from_payload(p["routing"])
            gids = np.asarray(msg.keys, dtype=np.int64)
            value = np.asarray(msg.values[0])
            state = {
                k: np.asarray(v)
                for k, v in zip(p["state_keys"], msg.values[1:])
            }
            self._install_routing(
                routing, extra={p["table"]: (gids, value, state)}
            )
            self.rows_migrated_in += int(gids.size)
            flightrec.record(
                "migrate.adopt", node=self.post.node_id,
                table=p["table"], rows=int(gids.size),
            )
            return msg.reply()
        if op == "migrate_release":
            # donor's standby: drop the moved range, mirroring the primary
            self._install_routing(RoutingTable.from_payload(p["routing"]))
            flightrec.record(
                "migrate.release", node=self.post.node_id, table=p["table"],
            )
            return msg.reply()
        if op == "migrate_abort":
            self._migrations.pop(p["mid"], None)
            self._staging.pop(p["mid"], None)
            flightrec.record(
                "migrate.abort", node=self.post.node_id, mid=p["mid"],
            )
            return msg.reply()
        raise ValueError(f"unsupported migration op {op!r}")

    def _commit_migration(self, msg: Message) -> Message:
        """Donor commit = the freeze-fence window, bounded to the delta.

        Runs entirely on the recv thread, so no push interleaves: export the
        dirty delta, hand it to the recipient (which installs atomically),
        then shrink the local shard and adopt the new epoch.  Requests queued
        meanwhile hit the NEW table and fence — rejected, not lost.  Donor
        crash before the install ack leaves the old routing everywhere:
        the PR-4 restart path brings the donor back and the migration simply
        re-runs (staged chunks are superseded by the new mid).
        """
        p = msg.task.payload
        m = self._migrations.pop(p["mid"])
        t0 = time.perf_counter()
        new_routing = RoutingTable.from_payload(p["routing"])
        t = m["table"]
        dirty = np.asarray(sorted(m["dirty"]), dtype=np.int64)
        d_value, d_state = self._export_rows(t, dirty)
        skeys = sorted(d_state)
        try:
            self._mig_rpc(
                p["to"],
                {
                    "op": "migrate_install",
                    "mid": p["mid"],
                    "table": t,
                    "lo": m["lo"],
                    "hi": m["hi"],
                    "state_keys": skeys,
                    "routing": new_routing.to_payload(),
                },
                keys=dirty,
                values=[d_value] + [d_state[k] for k in skeys],
            )
        except Exception:
            # install failed: the range is still owned (and served) here —
            # re-arm tracking so the driver can retry/abort cleanly
            self._migrations[p["mid"]] = m
            raise
        # recipient owns the range now: shrink + new epoch, atomically for
        # every request behind this handler
        self._install_routing(new_routing)
        self.rows_migrated_out += m["hi"] - m["lo"]
        if self.replica is not None:
            self._forward_control(
                {
                    "op": "migrate_release",
                    "table": t,
                    "routing": new_routing.to_payload(),
                }
            )
        freeze = time.perf_counter() - t0
        self.migration_freeze_last_s = freeze
        self.migration_freeze_s += freeze
        flightrec.record(
            "migrate.commit", node=self.post.node_id, mid=p["mid"],
            table=t, rows=m["hi"] - m["lo"], dirty=int(dirty.size),
            epoch=new_routing.epoch, freeze_ms=round(1e3 * freeze, 3),
        )
        return msg.reply(values=[np.asarray([freeze], np.float64)])

    def _install_migration(self, msg: Message) -> Message:
        """Recipient install: staged chunks + dirty delta -> grown shard."""
        p = msg.task.payload
        t, lo, hi = p["table"], int(p["lo"]), int(p["hi"])
        st = self._staging.pop(p["mid"], {"chunks": []})
        tbl = self.tables[t]
        n = hi - lo
        dtype = np.dtype(tbl.value.dtype)
        value = np.zeros((n, tbl.dim), dtype=dtype)
        state_names = sorted(tbl.state)
        state = {k: np.zeros((n, tbl.dim), dtype=dtype) for k in state_names}
        covered = np.zeros(n, dtype=bool)
        for c_lo, c_hi, c_val, c_state in st["chunks"]:
            a, b = c_lo - lo, c_hi - lo
            value[a:b] = c_val
            for k in state_names:
                state[k][a:b] = c_state[k]
            covered[a:b] = True
        d_ids = np.asarray(msg.keys, dtype=np.int64)
        if d_ids.size:
            d_val = np.asarray(msg.values[0])
            d_state = dict(zip(p["state_keys"], msg.values[1:]))
            idx = d_ids - lo
            value[idx] = d_val
            for k in state_names:
                state[k][idx] = np.asarray(d_state[k])
            covered[idx] = True
        if not covered.all():
            raise RuntimeError(
                f"migrate_install of {t!r}[{lo}:{hi}) on {self.post.node_id}: "
                f"{int((~covered).sum())} rows never staged"
            )
        routing = RoutingTable.from_payload(p["routing"])
        gids = np.arange(lo, hi, dtype=np.int64)
        self._install_routing(routing, extra={t: (gids, value, state)})
        self.rows_migrated_in += n
        flightrec.record(
            "migrate.install", node=self.post.node_id, mid=p["mid"],
            table=t, lo=lo, hi=hi, epoch=routing.epoch,
        )
        if self.replica is not None:
            self._forward_control(
                {
                    "op": "migrate_adopt",
                    "table": t,
                    "lo": lo,
                    "hi": hi,
                    "state_keys": state_names,
                    "routing": routing.to_payload(),
                },
                keys=gids,
                values=[value] + [state[k] for k in state_names],
            )
        return msg.reply()

    # -- checkpoint (reference SaveModel task: servers write their key-range
    # to file; src/app/linear_method/model_evaluation.h [U]) -----------------
    def _handle_control(self, msg: Message) -> Message:
        op = msg.task.payload.get("op")
        if op == "save_model":
            self.save_checkpoint(msg.task.payload["root"], msg.task.payload["step"])
            return msg.reply()
        if op == "load_model":
            self.restore_checkpoint(msg.task.payload["root"], msg.task.payload["step"])
            return msg.reply()
        if op == "adopt_routing":
            self.adopt_routing(msg.task.payload["routing"])
            return msg.reply()
        if op and op.startswith("migrate_"):
            return self._handle_migrate(msg)
        if op and op.startswith("snap_"):
            return self._handle_snapshot(msg)
        if op == "restore_snap":
            self.restore_snapshot(
                msg.task.payload["root"], msg.task.payload["step"]
            )
            return msg.reply()
        if op == "consist_hello":
            return self._handle_consist_hello(msg)
        if op == "consist_set":
            return self._handle_consist_set(msg)
        raise ValueError(f"unsupported control op {op!r}")

    # -- consistency plane control (ISSUE 20) --------------------------------
    def _handle_consist_hello(self, msg: Message) -> Message:
        """Register a worker in the fleet clock(s) BEFORE it trains.

        Up-front registration is what stops a fast worker free-running
        ahead during bring-up: until every peer's first stamped request
        arrives, the clock would not know the fleet is bigger than the
        senders it has seen.  Also the re-registration path after a
        same-id restart (a newer incarnation replaces the dead entry at
        the restored ``step``).
        """
        p = msg.task.payload
        worker = str(p.get("worker") or msg.sender)
        inc = int(p.get("incarnation", 0))
        step = int(p.get("step", 0))
        tname = p.get("table")
        tables = [tname] if tname else list(self._consist)
        for t in tables:
            if t in self._consist:
                self._consist[t]["clock"].hello(worker, inc, step)
        return msg.reply()

    def _handle_consist_set(self, msg: Message) -> Message:
        """Live retune: change a gated table's mode and/or bound.

        The BoundTuner's lever (bound only) and the scenario DSL's
        ``consistency_mode`` phase knob (mode flip mid-run).  A mode flip
        recomputes the bound from the mode semantics unless the payload
        pins one explicitly.
        """
        from parameter_server_tpu.config import ConsistencyMode

        p = msg.task.payload
        tname = p.get("table")
        tables = [tname] if tname else list(self._consist)
        for t in tables:
            st = self._consist.get(t)
            if st is None:
                continue
            if p.get("mode") is not None:
                mode = ConsistencyMode(p["mode"])
                st["mode"] = mode
                if mode == ConsistencyMode.BSP:
                    st["bound"] = 0
                elif mode == ConsistencyMode.ASP:
                    st["bound"] = None
                else:
                    st["bound"] = int(
                        p.get("bound", st["cfg"].max_delay)
                    )
            if p.get("bound") is not None:
                st["bound"] = int(p["bound"])
        return msg.reply()

    def save_checkpoint(self, root: str, step: int) -> None:
        """Write this server's row-range of every table (value + opt state).

        The shard-file format is uniform-contiguous (one ``row_offset`` per
        shard); post-migration layouts (moved/split ranges) are refused with
        a clear error — drain back to the uniform split before checkpointing,
        or rely on replica-chain recovery (the README "Elastic rebalancing"
        section documents this boundary).
        """
        from parameter_server_tpu import checkpoint

        for t, table in self.tables.items():
            part = self.partitions[t]
            uniform = [
                (int(part.offsets[s]), int(part.offsets[s + 1]))
                for s in (self.server_index,)
            ]
            segs = self.routing.tables[t].owned_segments(self.server_index)
            if segs != [seg for seg in uniform if seg[1] > seg[0]]:
                raise checkpoint.CheckpointLayoutError(
                    f"save_checkpoint: {self.post.node_id} owns migrated "
                    f"segments {segs} of {t!r} (uniform shard is {uniform}); "
                    "the legacy shard-file format is uniform-contiguous — "
                    "use the partitioned durability plane "
                    "(KVWorker.save_snapshot) or drain the migration back"
                )
            checkpoint.save_shard(
                root,
                step,
                t,
                table,
                self.server_index,
                part.num_servers,
                int(part.offsets[self.server_index]),
            )

    def restore_checkpoint(self, root: str, step: int) -> None:
        """Load this server's row-range; the saved server count may differ."""
        from parameter_server_tpu import checkpoint

        for t, table in self.tables.items():
            checkpoint.restore_shard(
                root, step, t, table, self.server_index, self.partitions[t].num_servers
            )

    # -- durability plane (ISSUE 16): partitioned incremental snapshots ------
    def _handle_snapshot(self, msg: Message) -> Message:
        """Three-phase snapshot, same shape as live migration.

        - ``snap_begin``  arms per-table dirty-row tracking (the
          ``_ack_push`` hot path adds only host set updates: sync-free);
        - ``snap_write``  bulk-exports ONE owned segment to its own file.
          Runs serially on the recv thread, so pushes interleave *between*
          segments — the table is never frozen for the bulk copy.  If the
          segment's version clock has not advanced past the driver's
          ``base_sver``, nothing is written and the driver carries the
          base manifest entry forward (the incremental path);
        - ``snap_commit`` is the only freeze: export the rows dirtied
          since ``snap_begin`` as the delta log and stamp commit-time
          segment versions.  Bounded by the dirty set exactly like
          :meth:`_commit_migration`, measured and recorded;
        - ``snap_abort``  drops the bookkeeping (files left behind are
          garbage a manifest never references — retention sweeps them).
        """
        from parameter_server_tpu import checkpoint

        p = msg.task.payload
        op = p["op"]
        if op == "snap_begin":
            sid = str(p["sid"])
            self._snapshots[sid] = {"dirty": {}}
            flightrec.record("ckpt.begin", node=self.post.node_id, sid=sid)
            return msg.reply()
        if op == "snap_abort":
            sn = self._snapshots.pop(str(p["sid"]), None)
            if sn is not None:
                flightrec.record(
                    "ckpt.abort", node=self.post.node_id, sid=str(p["sid"]),
                    why=str(p.get("why", "driver abort")),
                )
            return msg.reply()
        sid = str(p["sid"])
        if sid not in self._snapshots:
            raise RuntimeError(
                f"snapshot {sid!r} is not open on {self.post.node_id} "
                "(aborted by a routing change?)"
            )
        if op == "snap_write":
            t, lo, hi = p["table"], int(p["lo"]), int(p["hi"])
            starts, ends, _ = self._shard_maps[t]
            hit = np.nonzero((starts == lo) & (ends == hi))[0]
            if hit.size != 1:
                raise RuntimeError(
                    f"snap_write: {self.post.node_id} does not own segment "
                    f"{t}[{lo}:{hi}) as a whole"
                )
            cur = int(self._seg_versions[t][int(hit[0])])
            base = p.get("base_sver")
            reply = msg.reply()
            if base is not None and int(base) == cur:
                # version clock unchanged since the base snapshot: the
                # driver re-uses the base file + CRC (ship only deltas)
                reply.task = dataclasses.replace(
                    msg.task,
                    payload={"carried": True, "sver": cur, "table": t,
                             "lo": lo, "hi": hi},
                )
                return reply
            value, state = self.export_range(t, lo, hi)
            entry = checkpoint.write_segment_file(
                str(p["root"]), int(p["step"]), t, lo, hi, value, state
            )
            flightrec.record(
                "ckpt.segment", node=self.post.node_id, sid=sid, table=t,
                lo=lo, hi=hi, bytes=entry["bytes"],
            )
            reply.task = dataclasses.replace(
                msg.task,
                payload={"carried": False, "sver": cur, "table": t,
                         "lo": lo, "hi": hi, "entry": entry},
            )
            return reply
        if op == "snap_commit":
            sn = self._snapshots.pop(sid)
            t0 = time.perf_counter()
            root, step = str(p["root"]), int(p["step"])
            deltas: List[dict] = []
            n_dirty = 0
            for t in sorted(sn["dirty"]):
                gids = np.asarray(sorted(sn["dirty"][t]), dtype=np.int64)
                if not gids.size:
                    continue
                value, state = self._export_rows(t, gids)
                entry = checkpoint.write_delta_file(
                    root, step, t, self.server_index, gids, value, state
                )
                if entry is not None:
                    deltas.append(entry)
                    n_dirty += int(gids.size)
            svers = [
                [t, int(s), int(e), int(v)]
                for t in sorted(self.tables)
                for s, e, v in zip(
                    self._shard_maps[t][0], self._shard_maps[t][1],
                    self._seg_versions[t],
                )
            ]
            freeze = time.perf_counter() - t0
            self.ckpt_freeze_last_s = freeze
            self.ckpt_freeze_s += freeze
            self.ckpt_commits += 1
            self.ckpt_delta_rows += n_dirty
            over = n_dirty > self.ckpt_max_delta_rows
            if over:
                # soft bound: the snapshot still commits, but the breach
                # is visible (counter + event) so the interval can be
                # tightened before the freeze grows further
                self.ckpt_delta_overflow += 1
            self._ckpt_commit_t = time.monotonic()
            flightrec.record(
                "ckpt.commit", node=self.post.node_id, sid=sid, step=step,
                dirty=n_dirty, freeze_ms=round(1e3 * freeze, 3),
                over_bound=over,
            )
            reply = msg.reply()
            reply.task = dataclasses.replace(
                msg.task,
                payload={"deltas": deltas, "svers": svers,
                         "freeze_s": freeze},
            )
            return reply
        raise ValueError(f"unsupported snapshot op {op!r}")

    def restore_snapshot(
        self, root: str, step: int, *, adopt_routing: bool = False
    ) -> None:
        """Point-in-time restore from a partitioned snapshot.

        Reads only the manifest plus the file ranges covering the segments
        THIS server owns under its CURRENT routing table — the snapshot may
        have been written by a fleet of any shape (the reshard happens row-
        wise in :func:`checkpoint.snapshot_rows`).  Re-seeds the per-segment
        version clock from the manifest so the staleness plane stays
        monotonic across the restore.

        ``adopt_routing``: first adopt the manifest's routing table when it
        is NEWER than this server's — the same-id-restart path, where a
        freshly constructed server starts at the uniform epoch 0 but the
        snapshot was written by a fleet that had since migrated; without
        the adoption the restarted server would not own its migrated
        segments and every worker leg into them would fence forever.
        Fleet-shape restores (``load_snapshot``) keep it off: there the
        CURRENT fleet's routing is authoritative, not the writer's.
        """
        from parameter_server_tpu import checkpoint

        manifest = checkpoint.read_snapshot(root, step)
        if adopt_routing:
            snap_routing = RoutingTable.from_payload(manifest["routing"])
            if snap_routing.epoch > self.routing.epoch:
                # metadata-only adoption — no content hand-off like
                # ``_install_routing`` does for migrations, because every
                # owned row is about to be overwritten from the snapshot
                # (``install_rows`` below re-sizes the shard storage)
                self.routing = snap_routing
                self._shard_maps = {
                    t: self._make_map(snap_routing, t) for t in self.tables
                }
                self._seg_versions = {
                    t: np.zeros(
                        self._shard_maps[t][0].shape[0], dtype=np.int64
                    )
                    for t in self.tables
                }
        by_seg: Dict[Tuple[str, int, int], int] = {}
        for e in manifest["segments"]:
            key = (str(e["table"]), int(e["lo"]), int(e["hi"]))
            by_seg[key] = max(by_seg.get(key, 0), int(e.get("sver", 0)))
        for t, table in self.tables.items():
            segs = self.routing.tables[t].owned_segments(self.server_index)
            checkpoint.restore_segments(root, manifest, t, segs, table)
            ver = self._seg_versions[t]
            starts, ends, _ = self._shard_maps[t]
            for i in range(starts.shape[0]):
                lo, hi = int(starts[i]), int(ends[i])
                # exact match first; else the max over overlapping source
                # segments (restore onto a different fleet shape)
                v = by_seg.get((t, lo, hi))
                if v is None:
                    v = max(
                        (
                            sv for (tt, sl, sh), sv in by_seg.items()
                            if tt == t and sl < hi and sh > lo
                        ),
                        default=0,
                    )
                ver[i] = max(int(ver[i]), v)
        self._ckpt_commit_t = time.monotonic()
        flightrec.record(
            "ckpt.restore", node=self.post.node_id, step=int(step),
            tables=len(self.tables),
        )
