#!/usr/bin/env python
"""Merge per-node chrome-trace dumps into one Perfetto timeline.

Each node of a cluster run dumps its own timeline
(``Tracer.dump_chrome_trace(path, process_name=node_id)``).  Loaded alone,
those files are N disconnected views of one distributed request; merged,
each node becomes a Perfetto *process* (pid = node index, named via
``process_name`` metadata events), and the worker-side ``ps.worker.push``
span lines up with the serving nodes' ``ps.server.push`` spans — both carry the
same stitched trace id in ``args.trace`` (stamped into
``Task.payload["__trace__"]`` by ``KVWorker._trace_ctx`` and echoed by
``KVServer.handle_request``), so clicking one end finds the other.

Clock alignment: every Tracer records span starts relative to its own
construction time.  ``dump_chrome_trace(..., process_name=...)`` embeds
that epoch (``metadata.clock_t0_s``, a ``perf_counter`` value), and the
merge rebases each file's events onto the shared clock — exact for
in-process clusters (one perf_counter domain), best-effort across OS
processes (as with any unsynchronized one-way timestamps).

Flight-recorder bundles (``tools/postmortem.py`` input — a JSON document
with an ``events`` list plus ``wall/mono_anchor_s`` and ``clock_offset_s``)
are accepted alongside trace files and bridged as Perfetto *instant*
events (``ph: "i"``), so the black-box journal's ``resend.retransmit`` /
``slo.breach`` markers land on the same timeline as the spans they
explain.  Each bundle event's monotonic stamp is rebased into the shared
scheduler clock domain by subtracting the bundle's ``clock_offset_s``
(the heartbeat min-RTT estimate), then shifted onto the merge's common
epoch exactly like span ``ts`` values.

Sampled request tracing (ISSUE 18) rides on both bridges.  After the
merge, every group of "X" spans sharing an ``args.trace`` id across
DIFFERENT pids is stitched with Perfetto *flow* events (``ph: "s"`` at
the upstream span, ``ph: "f"``/``bp: "e"`` at the downstream one, flow id
derived from the trace id) — one sampled request renders as a single
cross-node arrow chain from the worker's submit span through each
server's handler span.  Transport backpressure journal events
(``net.ring_full`` / ``net.writeq_full``) are bridged with ``cat:
"backpressure"`` so a stalled arrow can be read against the pressure
instants that explain it.

Usage::

    python tools/merge_traces.py -o merged.json trace_W0.json trace_S0.json ...
    python tools/merge_traces.py -o merged.json trace_W0.json flightrec_W0.json

Node names come from each file's ``metadata.node``, else the file stem.
The output is plain chrome-trace JSON ("traceEvents" array) — open with
https://ui.perfetto.dev or chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import zlib
from typing import Dict, List, Optional, Tuple

#: ph values this tool understands (complete spans, metadata, instants,
#: flow start/finish).
_KNOWN_PHASES = {"X", "M", "i", "s", "f"}

#: journal kinds bridged with ``cat: "backpressure"`` so transport-pressure
#: instants are filterable against the request flow arrows they explain.
_BACKPRESSURE_KINDS = {"net.ring_full", "net.writeq_full"}

#: valid instant-event scopes ("g"lobal, "p"rocess, "t"hread).
_INSTANT_SCOPES = {"g", "p", "t"}


def is_bundle(doc: dict) -> bool:
    """True for a flight-recorder bundle (postmortem.py's input shape)."""
    return isinstance(doc.get("events"), list) and "traceEvents" not in doc


def bundle_to_trace(doc: dict, fallback_node: str) -> Tuple[str, dict]:
    """Bridge a flight-recorder bundle into a chrome-trace-shaped document.

    Every journal event becomes an instant (``ph: "i"``, process scope)
    named by its kind, carrying the remaining journal fields in ``args``.
    The embedded epoch is the bundle's monotonic anchor REBASED into the
    scheduler clock domain (``mono_anchor_s - clock_offset_s``), and each
    event's ``ts`` is likewise offset-corrected — so once ``merge_traces``
    shifts all files onto the earliest epoch, bundle instants from
    different nodes line up to RTT/2 accuracy, and line up with tracer
    spans exactly for in-process clusters (one clock domain).
    """
    node = str(doc.get("node") or fallback_node)
    mono = float(doc.get("mono_anchor_s") or 0.0)
    off = float(doc.get("clock_offset_s") or 0.0)
    events: List[dict] = []
    for ev in doc["events"]:
        if not isinstance(ev, dict):
            continue
        t_mono = float(ev.get("t_mono_s") or 0.0)
        args = {
            k: v for k, v in ev.items()
            if k not in ("t_mono_s", "kind")
        }
        args.setdefault("node", node)
        kind = str(ev.get("kind") or "event")
        inst = {
            "name": kind,
            "ph": "i",
            "s": "p",
            "ts": (t_mono - mono) * 1e6,
            "tid": 0,
            "args": args,
        }
        if kind in _BACKPRESSURE_KINDS:
            inst["cat"] = "backpressure"
        events.append(inst)
    return node, {
        "traceEvents": events,
        "metadata": {"node": node, "clock_t0_s": mono - off},
    }


def load_trace(path: str) -> Tuple[str, dict]:
    """Read one per-node dump; returns (node_name, document).

    Flight-recorder bundles are detected by shape and bridged via
    :func:`bundle_to_trace`; chrome-trace files pass through unchanged.
    """
    with open(path) as f:
        doc = json.load(f)
    stem = os.path.splitext(os.path.basename(path))[0]
    if is_bundle(doc):
        return bundle_to_trace(doc, stem)
    meta = doc.get("metadata") or {}
    node = meta.get("node") or stem
    return str(node), doc


def merge_traces(
    paths: List[str], nodes: Optional[List[str]] = None
) -> dict:
    """Merge per-node chrome traces into one multi-process document.

    ``nodes``: optional explicit node names (parallel to ``paths``),
    overriding embedded/filename-derived names.  Input order fixes pid
    assignment (pid = 1 + index), so merges are deterministic.
    """
    events: List[dict] = []
    # rebase every file to the EARLIEST embedded clock epoch so merged ts
    # stay positive and relative offsets between nodes are preserved
    loaded = []
    t0s = []
    for i, path in enumerate(paths):
        node, doc = load_trace(path)
        if nodes is not None:
            node = nodes[i]
        t0 = (doc.get("metadata") or {}).get("clock_t0_s")
        loaded.append((node, doc, t0))
        if t0 is not None:
            t0s.append(t0)
    base_t0 = min(t0s) if t0s else None
    for pid, (node, doc, t0) in enumerate(loaded, start=1):
        shift_us = (
            (t0 - base_t0) * 1e6 if (t0 is not None and base_t0 is not None)
            else 0.0
        )
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": node},
            }
        )
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            if ev.get("ph") == "M":
                continue  # per-file metadata is superseded by ours
            ev["pid"] = pid
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            events.append(ev)
    events.extend(_stitch_flows(events))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def _stitch_flows(events: List[dict]) -> List[dict]:
    """Build Perfetto flow arrows between same-trace spans on different pids.

    Spans sharing an ``args.trace`` id are sorted by rebased ``ts``; each
    consecutive cross-pid pair gets a flow start (``ph: "s"``) bound to
    the upstream span and a flow finish (``ph: "f"``, ``bp: "e"`` so it
    binds to the ENCLOSING downstream slice) — rendering one sampled
    request as a single arrow chain across node processes.  Flow ids are
    ``crc32("<trace>:<hop>")``: deterministic, unique per hop, shared by
    exactly its s/f pair.  Same-pid neighbours are skipped (no wire hop).
    """
    by_trace: Dict[str, List[dict]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        trace = (ev.get("args") or {}).get("trace")
        if trace:
            by_trace.setdefault(str(trace), []).append(ev)
    flows: List[dict] = []
    for trace, spans in sorted(by_trace.items()):
        spans.sort(key=lambda e: (e.get("ts", 0.0), e.get("pid", 0)))
        hop = 0
        for up, down in zip(spans, spans[1:]):
            if up.get("pid") == down.get("pid"):
                continue
            fid = zlib.crc32(f"{trace}:{hop}".encode()) & 0xFFFFFFFF
            common = {"name": "req", "cat": "traceflow", "id": fid,
                      "args": {"trace": trace}}
            flows.append(dict(common, ph="s", pid=up["pid"],
                              tid=up.get("tid", 0), ts=up.get("ts", 0.0)))
            flows.append(dict(common, ph="f", bp="e", pid=down["pid"],
                              tid=down.get("tid", 0), ts=down.get("ts", 0.0)))
            hop += 1
    return flows


def validate_chrome_trace(doc: dict) -> List[str]:
    """Schema check: the invariants Perfetto's importer relies on.

    Returns a list of problems (empty = valid): a ``traceEvents`` array
    where every event has a string ``name`` and known ``ph``; complete
    ("X") events also need numeric ``ts`` + non-negative ``dur`` and
    integer ``pid``/``tid``; instants ("i", the bridged flight-recorder
    events) need numeric ``ts``, integer ``tid``, and a valid scope when
    ``s`` is present; flow events ("s"/"f", the cross-node request
    stitches) need numeric ``ts``, integer ``tid``, an ``id``, and — for
    finishes — ``bp`` restricted to the enclosing-slice binding ("e").
    """
    problems: List[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        if not isinstance(ev.get("name"), str):
            problems.append(f"{where}: name missing or not a string")
        ph = ev.get("ph")
        if ph not in _KNOWN_PHASES:
            problems.append(f"{where}: unknown ph {ph!r}")
            continue
        if not isinstance(ev.get("pid"), int):
            problems.append(f"{where}: pid missing or not an int")
        if ph == "X":
            if not isinstance(ev.get("ts"), (int, float)):
                problems.append(f"{where}: ts missing or not numeric")
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"{where}: dur missing/negative")
            if not isinstance(ev.get("tid"), int):
                problems.append(f"{where}: tid missing or not an int")
        if ph == "i":
            if not isinstance(ev.get("ts"), (int, float)):
                problems.append(f"{where}: ts missing or not numeric")
            if not isinstance(ev.get("tid"), int):
                problems.append(f"{where}: tid missing or not an int")
            if "s" in ev and ev["s"] not in _INSTANT_SCOPES:
                problems.append(f"{where}: instant scope {ev['s']!r} invalid")
        if ph in ("s", "f"):
            if not isinstance(ev.get("ts"), (int, float)):
                problems.append(f"{where}: ts missing or not numeric")
            if not isinstance(ev.get("tid"), int):
                problems.append(f"{where}: tid missing or not an int")
            if not isinstance(ev.get("id"), (int, str)):
                problems.append(f"{where}: flow event missing id")
            if ph == "f" and "bp" in ev and ev["bp"] != "e":
                problems.append(f"{where}: flow finish bp {ev['bp']!r} invalid")
        if "args" in ev and not isinstance(ev["args"], dict):
            problems.append(f"{where}: args not an object")
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="merge per-node chrome traces into one Perfetto timeline"
    )
    ap.add_argument("traces", nargs="+", help="per-node trace JSON files")
    ap.add_argument(
        "-o", "--output", default="merged_trace.json",
        help="merged output path (default: %(default)s)",
    )
    args = ap.parse_args(argv)
    merged = merge_traces(args.traces)
    problems = validate_chrome_trace(merged)
    if problems:
        for p in problems:
            print(f"merge_traces: {p}", file=sys.stderr)
        return 1
    with open(args.output, "w") as f:
        json.dump(merged, f)
    n_spans = sum(1 for e in merged["traceEvents"] if e.get("ph") == "X")
    n_inst = sum(1 for e in merged["traceEvents"] if e.get("ph") == "i")
    n_flows = sum(1 for e in merged["traceEvents"] if e.get("ph") == "s")
    print(
        f"merged {len(args.traces)} node traces ({n_spans} spans, "
        f"{n_inst} instants, {n_flows} flow arrows) -> {args.output}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
