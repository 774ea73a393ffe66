#!/usr/bin/env python
"""Decompose sampled request traces into per-plane critical-path segments.

Input is the same per-node flight-recorder bundles ``tools/postmortem.py``
merges (``flightrec_<node>.json``: an ``events`` list plus paired
``wall_anchor_s``/``mono_anchor_s`` anchors and the heartbeat-derived
``clock_offset_s``).  The tracing plane (ISSUE 18) journals a ``trace.*``
event at every hop of a sampled request — worker submit, per-conn wire
tx/rx, bundle fan-out, server dispatch, reply build, device apply,
ack-return closure — and this tool stitches each request's events back
into ONE timeline, then attributes its end-to-end latency across planes:

    serialize     ctx stamp -> span tree registered (worker-side prep;
                  the trace.submit event fires just before the wire submit)
    send_queue    span registered -> first request-direction wire tx
                  (send call + coalescing/flush delay)
    wire          wire tx -> LAST request leg received by a server
    server_queue  wire rx -> handler dispatch (server recv-thread queue)
    apply         dispatch -> reply built (table update + version stamp)
    ack_return    reply built -> worker closes the span tree (last ack)

Segments telescope: each boundary stamp is clamped monotone (running
max), so the six segments sum EXACTLY to ``t_ack - t0`` — the same
end-to-end latency the worker's ``trace.ack`` event records as
``e2e_ms``.  A stamp a plane never produced (loopback runs have no wire
tx/rx; fenced replies skip apply) contributes a zero-width segment and
its time is absorbed by the preceding plane — attribution degrades,
never double-counts.

Direction disambiguation: both request and reply legs journal wire
events with the same trace id.  ``origin = tid.split("/")[0]`` names the
submitting node, so request-direction tx events are those with
``recver != origin`` (earliest wins: the first byte leaving the worker)
and request-direction rx events are those with ``sender == origin``
(latest wins: the span tree is open until the last leg lands).

Clock rebase is identical to postmortem.py: ``wall + (t_mono - mono) -
clock_offset`` maps every node onto the shared scheduler reference
(exact in-process, RTT/2 accuracy across hosts — ``FleetMonitor.
clock_offset``).

Usage::

    python tools/critpath.py bundles/flightrec_*.json
    python tools/critpath.py --json --requests 0 bundles/*.json

The report prints a worked per-request transcript (``--requests`` many,
default 3) and a per-plane p50/p99 attribution table; ``--json`` emits
the same data machine-readable (the e2e tests consume it).  The live complements of this offline view are the
``trace.wire`` / ``trace.sq`` / ``trace.apply`` / ``trace.e2e``
telemetry digests (pstop's WIREus/SQus/APLY%% columns and the
``tracing_plane_specs`` SLO read those).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

#: plane name -> the request-record stamp that closes the segment, in
#: causal order.  Each segment is ``stamp - previous stamp`` after the
#: running-max clamp; the tuple order IS the critical path.
PLANES = (
    ("serialize", "t_send"),
    ("send_queue", "t_tx"),
    ("wire", "t_rx"),
    ("server_queue", "t_disp"),
    ("apply", "t_reply"),
    ("ack_return", "t_ack"),
)


def load_bundle(path: str) -> dict:
    """Read one per-node bundle; same shape/stance as postmortem.py."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc.get("events"), list):
        raise ValueError(f"{path}: not a flight-recorder bundle (no events)")
    doc.setdefault("node", os.path.splitext(os.path.basename(path))[0])
    return doc


def merge_events(paths: List[str]) -> List[dict]:
    """Load bundles and rebase every trace event onto the shared clock.

    Each event gains ``t_s`` (rebased wall-clock seconds); ``trace.submit``
    events additionally gain ``_t0_s`` — the context-stamp time rebased
    with the SAME bundle anchors (``t0_s`` is a raw monotonic value from
    the submitting node's clock).
    """
    events: List[dict] = []
    for path in paths:
        b = load_bundle(path)
        wall = float(b.get("wall_anchor_s") or 0.0)
        mono = float(b.get("mono_anchor_s") or 0.0)
        off = float(b.get("clock_offset_s") or 0.0)
        node = str(b["node"])
        for ev in b["events"]:
            if not isinstance(ev, dict):
                continue
            kind = ev.get("kind") or ""
            if not kind.startswith("trace."):
                continue
            ev = dict(ev)
            t_mono = float(ev.get("t_mono_s") or 0.0)
            ev["t_s"] = wall + (t_mono - mono) - off
            if kind == "trace.submit" and ev.get("t0_s") is not None:
                ev["_t0_s"] = wall + (float(ev["t0_s"]) - mono) - off
            ev.setdefault("node", node)
            events.append(ev)
    events.sort(key=lambda e: (e["t_s"], str(e["node"]), e.get("seq", 0)))
    return events


def _blank(tid: str) -> dict:
    return {
        "tid": tid,
        "origin": tid.split("/")[0],
        "op": None,
        "legs": None,
        "t0": None,
        "t_send": None,
        "t_tx": None,
        "t_rx": None,
        "t_disp": None,
        "t_reply": None,
        "t_ack": None,
        "e2e_ms": None,
        "fenced": False,
        "retransmits": 0,
        "device_ms": None,
    }


def requests(events: List[dict]) -> Dict[str, dict]:
    """Fold rebased trace events into per-request stamp records."""
    reqs: Dict[str, dict] = {}

    def rec(tid: str) -> dict:
        return reqs.setdefault(tid, _blank(tid))

    for ev in events:
        kind = ev["kind"]
        if kind == "trace.submit":
            q = rec(ev["tid"])
            q["t0"] = ev.get("_t0_s", ev["t_s"])
            q["t_send"] = ev["t_s"]
            q["op"] = ev.get("op")
            q["legs"] = ev.get("legs")
        elif kind == "trace.wire_tx":
            for tid in ev.get("tids") or []:
                q = rec(tid)
                if ev.get("recver") != q["origin"]:
                    t = ev["t_s"]
                    q["t_tx"] = t if q["t_tx"] is None else min(q["t_tx"], t)
        elif kind == "trace.wire_rx":
            for tid in ev.get("tids") or []:
                q = rec(tid)
                if ev.get("sender") == q["origin"]:
                    t = ev["t_s"]
                    q["t_rx"] = t if q["t_rx"] is None else max(q["t_rx"], t)
        elif kind == "trace.dispatch":
            q = rec(ev["tid"])
            t = ev["t_s"]
            q["t_disp"] = t if q["t_disp"] is None else max(q["t_disp"], t)
        elif kind == "trace.reply":
            q = rec(ev["tid"])
            t = ev["t_s"]
            q["t_reply"] = t if q["t_reply"] is None else max(q["t_reply"], t)
            if ev.get("verdict") == "fenced":
                q["fenced"] = True
        elif kind == "trace.apply":
            q = rec(ev["tid"])
            if ev.get("device_ms") is not None:
                q["device_ms"] = float(ev["device_ms"])
        elif kind == "trace.ack":
            q = rec(ev["tid"])
            q["t_ack"] = ev["t_s"]
            if ev.get("e2e_ms") is not None:
                q["e2e_ms"] = float(ev["e2e_ms"])
        elif kind == "trace.retransmit":
            for tid in ev.get("tids") or []:
                rec(tid)["retransmits"] += 1
    return reqs


def segments(q: dict) -> Optional[Dict[str, float]]:
    """Telescoping per-plane segments (seconds) for one request.

    ``None`` for incomplete span trees (no submit or no ack) — those are
    postmortem.py's orphan anchors, not attribution samples.  Boundary
    stamps are clamped to a running max so every segment is >= 0 and the
    sum is exactly ``max(stamps) - t0`` (== ``t_ack - t0`` whenever the
    ack is, as it must be, the last stamp).
    """
    if q["t0"] is None or q["t_ack"] is None:
        return None
    prev = q["t0"]
    out: Dict[str, float] = {}
    for name, key in PLANES:
        t = q[key]
        t = prev if t is None else max(prev, t)
        out[name] = t - prev
        prev = t
    out["e2e"] = prev - q["t0"]
    return out


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile on a sorted copy; 0.0 for empty input."""
    if not values:
        return 0.0
    vals = sorted(values)
    idx = min(len(vals) - 1, max(0, int(round(q * (len(vals) - 1)))))
    return vals[idx]


def attribution(reqs: Dict[str, dict]) -> dict:
    """Per-plane p50/p99 (ms) + mean share of e2e across complete requests."""
    samples: Dict[str, List[float]] = {name: [] for name, _ in PLANES}
    samples["e2e"] = []
    complete = 0
    for q in reqs.values():
        segs = segments(q)
        if segs is None:
            continue
        complete += 1
        for name, v in segs.items():
            samples[name].append(v)
    out = {"requests": len(reqs), "complete": complete, "planes": {}}
    e2e_total = sum(samples["e2e"]) or 1.0
    for name in list(samples):
        vals = samples[name]
        out["planes"][name] = {
            "p50_ms": round(percentile(vals, 0.50) * 1e3, 3),
            "p99_ms": round(percentile(vals, 0.99) * 1e3, 3),
            "share_pct": round(100.0 * sum(vals) / e2e_total, 1),
        }
    return out


def transcript(q: dict) -> List[str]:
    """Worked per-request lines: each plane's width and running total."""
    segs = segments(q)
    head = (
        f"request {q['tid']} op={q['op'] or '?'} legs={q['legs'] or '?'}"
        + (" FENCED" if q["fenced"] else "")
        + (f" retransmits={q['retransmits']}" if q["retransmits"] else "")
    )
    if segs is None:
        missing = "submit" if q["t0"] is None else "ack-return"
        return [head, f"  INCOMPLETE span tree (no {missing} span) — "
                      "postmortem.py anchors on this"]
    lines = [head]
    acc = 0.0
    for name, _ in PLANES:
        acc += segs[name]
        lines.append(
            f"  {name:<12s} {segs[name] * 1e6:10.1f}us"
            f"   (cum {acc * 1e6:10.1f}us)"
        )
    lines.append(
        f"  {'e2e':<12s} {segs['e2e'] * 1e6:10.1f}us"
        + (
            f"   (worker-measured {q['e2e_ms'] * 1e3:.1f}us)"
            if q["e2e_ms"] is not None else ""
        )
    )
    return lines


def render(reqs: Dict[str, dict], *, show: int = 3) -> List[str]:
    attr = attribution(reqs)
    lines = [
        f"critpath: {attr['requests']} sampled requests "
        f"({attr['complete']} complete span trees)"
    ]
    shown = 0
    for tid in sorted(reqs):
        if shown >= show:
            break
        lines.extend(transcript(reqs[tid]))
        shown += 1
    lines.append(f"{'plane':<14s} {'p50_ms':>10s} {'p99_ms':>10s} {'share%':>8s}")
    for name in [n for n, _ in PLANES] + ["e2e"]:
        p = attr["planes"][name]
        lines.append(
            f"{name:<14s} {p['p50_ms']:>10.3f} {p['p99_ms']:>10.3f} "
            f"{p['share_pct']:>8.1f}"
        )
    return lines


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="per-plane critical-path attribution of sampled requests"
    )
    ap.add_argument("bundles", nargs="+", help="flightrec_*.json bundle files")
    ap.add_argument(
        "--requests", type=int, default=3,
        help="per-request transcripts to print (default: %(default)s)",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="emit machine-readable attribution + per-request segments",
    )
    args = ap.parse_args(argv)
    try:
        events = merge_events(args.bundles)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"critpath: {e}", file=sys.stderr)
        return 1
    reqs = requests(events)
    if args.json:
        doc = {
            "attribution": attribution(reqs),
            "requests": {
                tid: {
                    **{k: q[k] for k in ("op", "legs", "fenced",
                                         "retransmits", "e2e_ms")},
                    "segments_s": segments(q),
                }
                for tid, q in sorted(reqs.items())
            },
        }
        json.dump(doc, sys.stdout, indent=2)
        print()
    else:
        print("\n".join(render(reqs, show=args.requests)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
