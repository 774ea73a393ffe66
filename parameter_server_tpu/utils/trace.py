"""Host-side tracing: spans, latency histograms, chrome-trace export.

SURVEY.md §5 tracing plan: the reference has only ad-hoc timing macros and
``/proc`` polling (``util/resource_usage.h``, ``system/network_usage.h``
[U]); the rebuild gets a real tracer — Push/Pull latency histograms on the
host path and exportable timelines.

:meth:`Tracer.span` is the ONE way the package records a span, and it has
two sinks:

- while a ``jax.profiler`` session is capturing, every span — of an
  enabled tracer or not — opens a ``jax.profiler.TraceAnnotation``, so it
  lands in the session's ``.xplane.pb`` on the clock the device operations
  are on, with its attributes, the thread's CPU time (``cpu_us``) and, by
  nesting on its thread, its parent.  ``benchmarks/harness/
  program_spans.py`` reads them (``python3 -m
  benchmarks.harness.program_spans <file>`` prints the account);
- an enabled :class:`Tracer` also keeps the span in its own bounded deque
  and its :class:`LatencyHistogram` (the operator's use: ``Dashboard``,
  telemetry digests, the chrome-trace export).

With no session and a disabled tracer a span is one check and a shared
no-op context manager.  Span names are closed over :data:`SPANS`.

Code that holds a tracer opens its spans on it (``kv/worker.py``,
``kv/server.py``: the request's span and, under it, ``ps.server.localize``,
``.h2d``, ``.dispatch``, ``.d2h`` and ``.ack``; ``learner/hybrid.py``).  Code
with no tracer handle, which also stays free of jax, calls the module-level
:func:`span`: ``core/netmon.py`` (``ps.van.*``) and ``core/clock.py``
(``ps.worker.turn``, the bounded-delay wait that opens a worker's step).

Spans of one request share ``req="<sender>/<customer>/<task.time>"`` across
threads (:func:`req_id`): fields every message already has, so no payload
key rides the wire for it.
"""

from __future__ import annotations

import collections
import json
import math
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

#: Closed span-name registry.  ``tools/check_wrappers.py`` parses this
#: frozenset LITERAL by AST (no import) and holds every literal first
#: argument of a ``span(`` call under the package to it, so keep it a plain
#: frozenset of plain string constants.  ``PERF.md`` section 3 says which
#: metric reads which.
SPANS = frozenset({
    # consistency (core/clock.py, through the module-level ``span``): a
    # worker's turn under a bounded delay, before the step's first request
    "ps.worker.turn",
    # worker (kv/worker.py): the roots of a request, then what they nest
    "ps.worker.pull",
    "ps.worker.push",
    "ps.worker.pull_serve",
    "ps.worker.localize",
    "ps.worker.combine",
    "ps.worker.submit",
    "ps.worker.wait",
    "ps.worker.gate_pause",
    "ps.worker.assemble",
    # van (core/netmon.py)
    "ps.van.send",
    "ps.van.deliver",
    # server (kv/server.py): a request's span, then its stages in order
    "ps.server.pull",
    "ps.server.push",
    "ps.server.localize",
    "ps.server.h2d",
    "ps.server.dispatch",
    "ps.server.d2h",
    "ps.server.ack",
    # hybrid learner (learner/hybrid.py): the root of a step, then its parts
    "ps.hybrid.step",
    "ps.hybrid.pull_wait",
    "ps.hybrid.body_dispatch",
    "ps.hybrid.push_submit",
    "ps.hybrid.prefetch",
    "ps.hybrid.loss_sync",
})


def req_id(sender: str, customer: str, ts: int) -> str:
    """The id the spans of one request share: the requesting node, its
    customer and the customer timestamp that matches replies to requests."""
    return f"{sender}/{customer}/{ts}"


#: one recorded span: (name, start_s, duration_s, thread_id, attrs)
Span = Tuple[str, float, float, int, Optional[dict]]


class LatencyHistogram:
    """O(1) mergeable log-bucketed streaming duration histogram.

    Buckets are geometric: bucket ``i`` has upper edge ``BASE * GROWTH**i``
    (bucket 0 holds everything <= 1 us); 96 buckets reach ~27 minutes at
    <= 25% relative error — the right resolution for wire and handler
    latencies.  Unlike the Tracer's bounded span deque this NEVER drops
    history: count/sum/max are exact, percentiles are bucket-resolution
    upper bounds (clamped to the observed max, so ``p99 <= max`` always).
    Two histograms merge by adding bucket counts, which is what lets
    per-link digests ride heartbeats and be re-aggregated fleet-side
    (the reference monitor merged per-node ``network_usage`` the same way).

    No internal lock: recorders (Tracer, MeteredVan) already serialize
    under their own locks, and every mutation is a single GIL-atomic
    scalar op, so a concurrent read can only skew a snapshot, never
    corrupt state.
    """

    BASE = 1e-6
    GROWTH = 1.25
    NBUCKETS = 96
    _LOG_G = math.log(GROWTH)
    #: interned bucket-key strings — ``to_dict`` runs per telemetry frame
    #: on hot paths; 96 ``str(i)`` calls per digest add up.
    _BKEYS = tuple(str(i) for i in range(NBUCKETS))

    __slots__ = ("counts", "count", "sum_s", "max_s")

    def __init__(self) -> None:
        self.counts = [0] * self.NBUCKETS
        self.count = 0
        self.sum_s = 0.0
        self.max_s = 0.0

    def _bucket(self, seconds: float) -> int:
        if seconds <= self.BASE:
            return 0
        return min(
            self.NBUCKETS - 1,
            1 + int(math.log(seconds / self.BASE) / self._LOG_G),
        )

    def record(self, seconds: float) -> None:
        seconds = max(float(seconds), 0.0)
        self.counts[self._bucket(seconds)] += 1
        self.count += 1
        self.sum_s += seconds
        if seconds > self.max_s:
            self.max_s = seconds

    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Add ``other``'s mass into this histogram (returns self)."""
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.count += other.count
        self.sum_s += other.sum_s
        self.max_s = max(self.max_s, other.max_s)
        return self

    def merge_dict(self, d: dict) -> "LatencyHistogram":
        """Fold a ``to_dict`` digest in without materializing it — touches
        only the sparse occupied buckets, so merging a per-frame DELTA
        digest (usually one or two buckets) costs O(buckets present), not
        O(NBUCKETS).  The telemetry aggregator's per-frame cumulative fold
        is exactly that shape."""
        for i, c in (d.get("b") or {}).items():
            self.counts[int(i)] += int(c)
        self.count += int(d.get("count", 0))
        self.sum_s += float(d.get("sum_s", 0.0))
        self.max_s = max(self.max_s, float(d.get("max_s", 0.0)))
        return self

    def percentile(self, p: float) -> float:
        """Upper bound (seconds) of the bucket holding the p-quantile."""
        if self.count == 0:
            return 0.0
        target = max(1, math.ceil(p * self.count))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= target:
                return min(self.BASE * self.GROWTH**i, self.max_s)
        return self.max_s  # pragma: no cover — cum == count by construction

    def stats(self) -> dict:
        """The Tracer.histogram row shape (count / mean / p50 / p99 / max)."""
        if self.count == 0:
            return {"count": 0}
        return {
            "count": self.count,
            "total_s": self.sum_s,
            "mean_us": 1e6 * self.sum_s / self.count,
            "p50_us": 1e6 * self.percentile(0.50),
            "p90_us": 1e6 * self.percentile(0.90),
            "p99_us": 1e6 * self.percentile(0.99),
            "max_us": 1e6 * self.max_s,
        }

    # -- wire form (heartbeat digests are JSON) ------------------------------
    def to_dict(self) -> dict:
        """JSON-safe digest; sparse buckets keep heartbeats small."""
        return {
            "count": self.count,
            "sum_s": self.sum_s,
            "max_s": self.max_s,
            "b": {self._BKEYS[i]: c for i, c in enumerate(self.counts) if c},
        }

    @classmethod
    def from_dict(cls, d: dict) -> "LatencyHistogram":
        h = cls()
        h.count = int(d.get("count", 0))
        h.sum_s = float(d.get("sum_s", 0.0))
        h.max_s = float(d.get("max_s", 0.0))
        for i, c in (d.get("b") or {}).items():
            h.counts[int(i)] = int(c)
        return h


def _annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session captures,
    else ``None``.  Looked up in ``sys.modules``: the transport modules
    import this file and stay free of jax, and a process that never
    imported jax has no session."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    ann = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


class _NullSpan:
    """What :meth:`Tracer.span` hands out when nothing records."""

    __slots__ = ()
    #: whether anything keeps what :meth:`set` is given (an attribute that
    #: costs something to compute is computed only then)
    recording = False

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One span in flight: a ``TraceAnnotation`` while a profiler session
    captures (``ann``), a record in ``tracer`` when that is enabled."""

    __slots__ = ("_tracer", "_name", "_attrs", "_ann", "_start", "_cpu0")
    recording = True

    def __init__(self, tracer, name: str, attrs: dict, ann) -> None:
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._ann = ann(name, **attrs) if ann is not None else None

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._cpu0 = time.thread_time()
            self._ann.__enter__()
        if self._tracer is not None:
            self._start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        if self._tracer is not None:
            self._attrs.update(attrs)

    def __exit__(self, *exc) -> None:
        if self._tracer is not None:
            self._tracer._store(
                self._name, self._start,
                time.perf_counter() - self._start, self._attrs,
            )
        if self._ann is not None:
            # wall minus CPU in a span that does no I/O is time the thread
            # stood without the GIL
            self._ann.set_metadata(
                cpu_us=int(1e6 * (time.thread_time() - self._cpu0))
            )
            self._ann.__exit__(*exc)
        return None


class Tracer:
    """Thread-safe span recorder: bounded timeline + unbounded histograms.

    Two stores per span name, updated together under one lock:

    - a bounded deque of full spans (timelines / chrome-trace export) —
      oldest spans drop past ``capacity``;
    - a :class:`LatencyHistogram` that never drops, so
      :meth:`histogram` percentiles cover the whole run, not a silent
      recent window (they used to be computed over the deque: after 100k
      spans wrapped, "p99" quietly became "p99 of the last 100k").
    """

    def __init__(self, *, capacity: int = 100_000, enabled: bool = True) -> None:
        self.enabled = enabled
        self._spans: collections.deque[Span] = collections.deque(maxlen=capacity)
        #: never-dropping per-name latency histograms (histogram/summary/
        #: totals read these, so aggregates survive deque wraparound).
        self._hists: Dict[str, LatencyHistogram] = {}
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    def span(self, name: str, **attrs) -> "_Span":
        """Context manager recording one span; ``sp.set(**attrs)`` inside
        the ``with`` adds attributes learned on the way (module docstring)."""
        ann = _annotation()
        if ann is None and not self.enabled:
            return _NULL_SPAN
        return _Span(self if self.enabled else None, name, attrs, ann)

    def _store(self, name: str, start: float, dur: float, attrs) -> None:
        with self._lock:
            self._spans.append(
                (name, start - self._t0, dur, threading.get_ident(),
                 attrs or None)
            )
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = LatencyHistogram()
            h.record(dur)

    def record(self, name: str, duration_s: float,
               start_s: Optional[float] = None, **attrs) -> None:
        """Record an externally timed span (e.g. from a callback).

        ``start_s``: the span's start as a ``time.perf_counter()`` value —
        without it the span is placed ending "now", which misorders
        retrospectively recorded phases on a timeline.
        """
        if not self.enabled:
            return
        if start_s is None:
            start_s = time.perf_counter() - duration_s
        self._store(name, start_s, duration_s, attrs)

    def totals(self) -> Dict[str, float]:
        """Cumulative seconds per span name (O(names), never drops spans)."""
        with self._lock:
            return {name: h.sum_s for name, h in self._hists.items()}

    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        return out if name is None else [s for s in out if s[0] == name]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._hists.clear()

    # -- aggregation ---------------------------------------------------------
    def histogram(self, name: str) -> dict:
        """Latency stats for one span name (the Push/Pull histogram).

        Backed by the never-dropping :class:`LatencyHistogram`, so the
        percentiles cover every span ever recorded under ``name`` — not
        just the ones still in the bounded deque.
        """
        with self._lock:
            h = self._hists.get(name)
            stats = h.stats() if h is not None else {"count": 0}
        return {"name": name, **stats}

    def summary(self) -> Dict[str, dict]:
        """Histogram per distinct span name."""
        with self._lock:
            names = sorted(self._hists)
        return {name: self.histogram(name) for name in names}

    def digests(self) -> Dict[str, dict]:
        """JSON-safe per-name histogram digests (heartbeat payload form)."""
        with self._lock:
            return {name: h.to_dict() for name, h in self._hists.items()}

    # -- export --------------------------------------------------------------
    def dump_chrome_trace(self, path: str,
                          process_name: Optional[str] = None) -> None:
        """Write the spans as a chrome://tracing / Perfetto JSON timeline.

        ``process_name`` (e.g. the node id): embeds a top-level
        ``metadata`` block — the node name plus this tracer's perf_counter
        epoch — that ``tools/merge_traces.py`` uses to label the process
        and align per-node clocks on one merged timeline.
        """
        events = [
            {
                "name": name,
                "ph": "X",
                "ts": start * 1e6,
                "dur": dur * 1e6,
                "pid": os.getpid(),
                "tid": tid,
                **({"args": attrs} if attrs else {}),
            }
            for name, start, dur, tid, attrs in self.spans()
        ]
        doc: dict = {"traceEvents": events}
        if process_name is not None:
            doc["metadata"] = {"node": process_name, "clock_t0_s": self._t0}
        with open(path, "w") as f:
            json.dump(doc, f)


#: shared tracer that keeps nothing itself: its spans still reach a
#: capturing ``jax.profiler`` session
NULL_TRACER = Tracer(enabled=False)

#: for code with no tracer handle (``core/netmon.py``, ``core/clock.py``)
span = NULL_TRACER.span


def resource_usage() -> dict:
    """Process CPU/memory snapshot (reference ``util/resource_usage.h`` [U]).

    Reads ``/proc`` directly (Linux); suitable as heartbeat ``stats`` payload.
    """
    out: dict = {"time": time.time()}
    try:
        with open("/proc/self/stat") as f:
            stat = f.read()
        # field 2 is "(comm)" and may itself contain spaces/parens — split
        # only AFTER the last ')', then index relative to field 3 ("state")
        parts = stat[stat.rindex(")") + 2 :].split()
        tick = os.sysconf("SC_CLK_TCK")
        out["cpu_user_s"] = int(parts[11]) / tick  # utime (field 14)
        out["cpu_sys_s"] = int(parts[12]) / tick  # stime (field 15)
        out["threads"] = int(parts[17])  # num_threads (field 20)
        out["rss_mb"] = int(parts[21]) * os.sysconf("SC_PAGE_SIZE") / 2**20
    except (OSError, IndexError, ValueError):
        pass  # non-Linux: time-only heartbeat stats
    return out
