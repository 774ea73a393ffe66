"""Metrics and dashboard: AUC, logloss tracking, per-iteration progress rows.

Reference analogues: ``src/util/evaluation.h`` (AUC), scheduler
``dashboard.h`` per-iteration table, heartbeat-fed monitor [U].  Output is
both human-readable rows and structured JSONL (the north-star metrics
``examples/sec/chip`` and time-to-accuracy must be first-class outputs,
SURVEY.md §5).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import IO, Optional

import numpy as np


def auc(labels: np.ndarray, scores: np.ndarray) -> float:
    """Area under the ROC curve via rank statistic (ties averaged)."""
    labels = np.asarray(labels).ravel()
    scores = np.asarray(scores).ravel()
    pos = labels > 0.5
    n_pos = int(pos.sum())
    n_neg = labels.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(labels.size, dtype=np.float64)
    ranks[order] = np.arange(1, labels.size + 1)
    # average ranks over tied scores
    sorted_scores = scores[order]
    i = 0
    while i < labels.size:
        j = i
        while j + 1 < labels.size and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        if j > i:
            ranks[order[i : j + 1]] = (i + 1 + j + 1) / 2.0
        i = j + 1
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2.0) / (n_pos * n_neg))


def transport_counters(van) -> dict:
    """Merge dashboard counters from a (possibly wrapped) Van stack.

    Walks the ``.inner`` chain of Van decorators (``ReliableVan``,
    ``ChaosVan``, ``MeteredVan``) down to the base transport, merging each
    layer's ``counters()`` dict — so retransmit / dup-suppressed / gave-up
    / injected-fault / wire-byte counts ride next to sent/dropped in one
    flat dict.  Same-named keys across layers are summed.
    """
    out: dict = {}
    seen = set()
    v = van
    while v is not None and id(v) not in seen:
        seen.add(id(v))
        get = getattr(v, "counters", None)
        if callable(get):
            try:
                for k, val in get().items():
                    out[k] = out.get(k, 0) + val
            except Exception:  # pragma: no cover — metrics must never crash
                pass
        v = getattr(v, "inner", None)
    return out


class CounterGroup:
    """Merge several ``counters()`` sources into one dict (summed keys).

    The migration plane's counters live on MANY objects — each
    :class:`~parameter_server_tpu.kv.server.KVServer` (``fenced_rejects``,
    ``rows_migrated_in/out``, freeze seconds), each
    :class:`~parameter_server_tpu.kv.worker.KVWorker` (``refresh_retries``,
    deadline retries) and the
    :class:`~parameter_server_tpu.kv.migrate.ShardMigrator` (moves/aborts).
    Group them (``CounterGroup(*servers, *workers, migrator)``) and attach
    as ``Dashboard(migration=...)`` so a rebalance shows up in the SAME rows
    as retransmits and cancels.  Postoffices also expose ``counters()``
    (``cancelled_drops``) — include them in the group and the Dashboard's
    transport ``rejects`` sub-dict lights up cancellation fences too.
    """

    def __init__(self, *sources) -> None:
        self.sources = list(sources)

    def add(self, *sources) -> "CounterGroup":
        self.sources.extend(sources)
        return self

    def counters(self) -> dict:
        out: dict = {}
        for src in self.sources:
            get = getattr(src, "counters", None)
            if not callable(get):
                continue
            try:
                for k, v in get().items():
                    out[k] = out.get(k, 0) + v
            except Exception:  # pragma: no cover — metrics must never crash
                pass
        return out


def _auto_peak_flops() -> float:
    """Peak dense FLOP/s of one device for the MFU denominator, from the
    table keyed by ``device_kind`` (``utils.platform.DEVICE_PEAKS``).

    0.0 for a device the table does not know (every CPU): the MFU column
    then stays off rather than dividing by a guess.
    """
    from parameter_server_tpu.utils.platform import device_peaks

    peaks = device_peaks()
    return peaks["flops"] if peaks else 0.0


def lowered_flops(jitfn, *args) -> float:
    """XLA-reported FLOPs for ONE call of a jitted function.

    Uses the pre-compile HLO cost analysis (``Lowered.cost_analysis``): no
    compilation, no execution — cheap enough to run at trainer init.  This
    is the generic MFU numerator for models without a clean closed form
    (ResNet convs, DLRM interactions); transformers use the 6ND rule so the
    number matches the convention papers report.  Returns 0.0 when the
    backend can't produce an analysis (MFU column then stays off).
    """
    try:
        ca = jitfn.lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca.get("flops", 0.0))
    except Exception:  # pragma: no cover — metrics must never crash training
        return 0.0


def mesh_peak_flops(n_devices: int) -> float:
    """Aggregate peak FLOP/s of an ``n_devices`` mesh (MFU denominator).

    The numerator counts FLOPs executed across the WHOLE mesh, so the
    denominator must be the mesh's aggregate peak — one chip's peak would
    report an 8-chip run at up to 800% MFU.
    """
    return _auto_peak_flops() * n_devices


def lm_matmul_params(params, drop: frozenset) -> int:
    """6ND numerator: total size of matmul-participating param leaves.

    ``drop``: top-level keys that are gathers, not matmuls (the input
    embedding table when untied, positional embeddings).  Shared by every
    transformer trainer so the MFU accounting cannot drift between them.
    """
    import jax

    return sum(
        int(np.prod(leaf.shape))
        for k, sub in params.items()
        if k not in drop
        for leaf in jax.tree.leaves(sub)
    )


def trainer_dashboard(dashboard, n_devices: int) -> "Dashboard":
    """The trainer-ctor idiom in one place: default Dashboard + mesh peak.

    Every trainer calls this instead of repeating the
    default-then-set-peak_flops dance (a caller-provided non-zero
    ``peak_flops`` wins).
    """
    d = dashboard or Dashboard(print_every=0)
    if d.peak_flops <= 0.0:
        d.peak_flops = mesh_peak_flops(n_devices)
    return d


@dataclasses.dataclass
class Dashboard:
    """Per-iteration progress table + JSONL sink.

    Prints rows like the reference scheduler dashboard (iter, time, objective,
    relative delta, examples/sec) and appends machine-readable JSONL.

    MFU (VERDICT r2 weak #7): set ``flops_per_example`` (the model's FLOPs
    per trained example) and every row carries ``mfu_pct`` — per-interval
    model FLOP utilisation against ``peak_flops`` (auto-detected from the
    backend when 0).  Attach a :class:`~parameter_server_tpu.utils.trace.Tracer`
    and printed/JSONL rows also carry the host/H2D/device second-attribution
    of everything the trainer recorded spans for (:meth:`attribution`).
    """

    jsonl: Optional[IO[str]] = None
    print_every: int = 10
    #: model FLOPs per example; 0 disables the MFU column.
    flops_per_example: float = 0.0
    #: peak FLOP/s for the MFU denominator; 0 = auto by backend at first use.
    peak_flops: float = 0.0
    #: optional span recorder feeding host/H2D/device attribution.
    tracer: Optional[object] = None
    #: optional Van (stacked wrappers fine): rows gain a ``net`` dict of
    #: cumulative transport counters — retransmits, dup_suppressed, gave_up,
    #: injected chaos faults, sent/dropped (see :func:`transport_counters`)
    #: plus derived wire-efficiency fields when a ``CoalescingVan`` is in
    #: the stack: ``bundle_occupancy`` (sub-messages per bundle frame) and
    #: ``frames_per_step`` (per-interval wire frames / iterations — the
    #: number coalescing exists to shrink).  With a ``MeteredVan`` in the
    #: stack, rows also carry ``bytes_per_example`` (cumulative wire bytes
    #: / examples trained — the wire cost of progress) and
    #: ``wire_bytes_per_sec`` (per-interval link throughput).
    transport: Optional[object] = None
    #: optional ``data.prefetch.PrefetchPipeline`` (anything with
    #: ``counters()``): rows gain a ``prefetch`` dict — produced/consumed
    #: block counts and cumulative stall count/seconds (consumer time spent
    #: waiting on the producer; nonzero means ingest is the bottleneck).
    prefetch: Optional[object] = None
    #: optional migration-plane counter source (anything with ``counters()``
    #: — typically a :class:`CounterGroup` over servers/workers/migrator):
    #: rows gain a ``migration`` dict — rows migrated in/out, fenced
    #: (wrong-epoch) rejects, refresh retries, cumulative handoff freeze
    #: seconds — so a live rebalance is visible in the same place as
    #: retransmits and cancels.
    migration: Optional[object] = None
    _start: float = dataclasses.field(default_factory=time.time)
    _last_obj: Optional[float] = None
    _last_t: Optional[float] = None
    _examples: int = 0
    _header_printed: bool = False
    _attr_last: dict = dataclasses.field(default_factory=dict)
    _net_sent_last: int = 0
    _net_iter_last: int = -1
    _net_bytes_last: int = 0
    _net_t_last: Optional[float] = None

    def record(self, iteration: int, objective: float, extra: Optional[dict] = None,
               examples: int = 0, now: Optional[float] = None) -> None:
        """``now``: the tick's shared wall-clock stamp (defaults to a fresh
        ``time.time()``).  Callers that also write a fleet JSONL row this
        tick should capture one stamp and pass it to BOTH this and
        ``FleetMonitor.write_jsonl(wall=...)`` — otherwise every interval
        rate here uses a denominator skewed by however long the other sink's
        dump took."""
        self._examples += examples
        now = time.time() if now is None else now
        rel = (
            (objective - self._last_obj) / abs(self._last_obj)
            if self._last_obj not in (None, 0.0)
            else 0.0
        )
        self._last_obj = objective
        interval = now - (self._last_t if self._last_t is not None else self._start)
        self._last_t = now
        row = {
            "iter": iteration,
            "sec": round(now - self._start, 3),
            "objective": round(float(objective), 6),
            "rel_delta": round(float(rel), 6),
            "examples": self._examples,
            "examples_per_sec": round(self._examples / max(now - self._start, 1e-9), 1),
        }
        mfu = None
        if self.flops_per_example > 0.0 and examples:
            if self.peak_flops <= 0.0:
                self.peak_flops = _auto_peak_flops()
            if self.peak_flops > 0.0:  # unknown device_kind: no MFU figure
                mfu = (
                    self.flops_per_example * examples
                    / max(interval, 1e-9)
                    / self.peak_flops
                )
                row["mfu_pct"] = round(mfu * 100.0, 4)
        if extra:
            row.update(extra)
        if self.transport is not None:
            net = transport_counters(self.transport)
            if net:
                frames = net.get("coalesce_frames", 0)
                if frames:
                    net["bundle_occupancy"] = round(
                        net.get("coalesce_msgs", 0) / frames, 2
                    )
                sent = net.get("sent")
                if sent is not None:
                    d_iter = iteration - self._net_iter_last
                    if self._net_iter_last >= 0 and d_iter > 0:
                        net["frames_per_step"] = round(
                            (sent - self._net_sent_last) / d_iter, 2
                        )
                    self._net_sent_last = sent
                    self._net_iter_last = iteration
                wire_bytes = net.get("wire_bytes")
                if wire_bytes is not None:
                    # wire efficiency next to examples_per_sec: cumulative
                    # bytes per trained example + per-interval throughput
                    if self._examples:
                        net["bytes_per_example"] = round(
                            wire_bytes / self._examples, 2
                        )
                    if self._net_t_last is not None:
                        net["wire_bytes_per_sec"] = round(
                            (wire_bytes - self._net_bytes_last)
                            / max(now - self._net_t_last, 1e-9),
                            1,
                        )
                    self._net_bytes_last = wire_bytes
                    self._net_t_last = now
                row["net"] = net
        if self.prefetch is not None:
            pf_counters = getattr(self.prefetch, "counters", None)
            if callable(pf_counters):
                try:
                    row["prefetch"] = pf_counters()
                except Exception:  # pragma: no cover — metrics must never
                    pass  # crash training
        if self.migration is not None:
            mig_counters = getattr(self.migration, "counters", None)
            if callable(mig_counters):
                try:
                    row["migration"] = mig_counters()
                except Exception:  # pragma: no cover — metrics must never
                    pass  # crash training
        net_row = row.get("net")
        if net_row is not None:
            # every reject class in one 0-filled sub-dict, so a garbled-wire
            # or fencing storm is visible in the transport section without
            # grepping per-layer counters.  frame/CRC/incarnation rejects
            # come from the van walk; routing fences and cancellation drops
            # live on KVServers / Postoffices — attach them via the
            # ``migration`` CounterGroup to light those two up.
            mig_row = row.get("migration") or {}
            net_row["rejects"] = {
                "frame_rejects": int(net_row.get("frame_rejects", 0)),
                "rejected_corrupt": int(net_row.get("rejected_corrupt", 0)),
                "rejected_stale": int(net_row.get("rejected_stale", 0)),
                "fenced_rejects": int(mig_row.get("fenced_rejects", 0)),
                "cancelled_drops": int(mig_row.get("cancelled_drops", 0)),
            }
        printing = self.print_every and iteration % self.print_every == 0
        if self.tracer is not None and (printing or self.jsonl is not None):
            # interval DELTAS (this row's share), from the tracer's O(1)
            # running totals — not a scan of the span deque, and not a
            # misleading cumulative sum per row
            attr = self.attribution()
            row["spans_s"] = {
                k: round(v - self._attr_last.get(k, 0.0), 4)
                for k, v in attr.items()
                if v - self._attr_last.get(k, 0.0) > 0
            }
            self._attr_last = attr
        if self.jsonl is not None:
            self.jsonl.write(json.dumps(row) + "\n")
            self.jsonl.flush()
        if printing:
            if not self._header_printed:
                print(
                    f"{'iter':>6} {'sec':>8} {'objective':>10} {'rel':>9} "
                    f"{'ex/s':>10} {'mfu%':>8}"
                )
                self._header_printed = True
            mfu_s = f"{mfu * 100:>8.3f}" if mfu is not None else f"{'-':>8}"
            print(
                f"{iteration:>6} {row['sec']:>8.2f} {row['objective']:>10.5f} "
                f"{row['rel_delta']:>9.5f} {row['examples_per_sec']:>10.1f} "
                f"{mfu_s}"
            )

    def attribution(self) -> dict:
        """Cumulative seconds per span name from the attached tracer.

        Trainers record spans named by plane (e.g. ``host.assemble``,
        ``h2d``, ``device.step``, ``ps.worker.push``); this sums their durations
        so a step-time budget — where did the wall clock actually go — rides
        next to the throughput numbers (SURVEY §5 observability).  Uses the
        tracer's O(1) running totals when available (hot-path safe).
        """
        if self.tracer is None:
            return {}
        totals = getattr(self.tracer, "totals", None)
        if callable(totals):
            return totals()
        out: dict = {}
        for name, _start, dur, _tid, _attrs in self.tracer.spans():
            out[name] = out.get(name, 0.0) + dur
        return out

    @property
    def examples_per_sec(self) -> float:
        return self._examples / max(time.time() - self._start, 1e-9)
