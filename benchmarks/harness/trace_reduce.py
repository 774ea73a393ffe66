"""From a profiler trace (``.xplane.pb``) to device busy / idle time, the
device operations that took most time, and the idle gaps by what the host
was doing.  Reads the file with ``jax.profiler.ProfileData`` and nothing
else.  The program's own ``ps.`` spans and scopes in the same file are
``program_spans.py``'s to read.

- Device planes are those named ``/device:<PLATFORM>:<n>``.  On each, the
  operations are the events of the line named ``XLA Ops``; a plane without
  such a line contributes every line that is not a summary line (steps,
  modules, name scopes).
- Busy time of a chip is the UNION of its operations' intervals (a ``while``
  contains its body's operations; the union counts the time once).  The
  window is the one the caller gives, else the host span ``WINDOW_SPAN``
  that the tracing thread holds open while it traces (idle before the first
  operation and after the last one counts), else first start to last end.
- Host spans are ``TraceAnnotation`` events whose name starts with the
  caller's prefix; each idle gap of 100 us or more is attributed to the
  spans in flight at its midpoint, split evenly over the host threads that
  carry such spans; a thread with no span in flight counts as
  ``between_steps``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

_DEVICE = re.compile(r"^/device:([A-Za-z]+):(\d+)$")
_SUMMARY_LINES = re.compile(
    r"^(steps?|xla modules?|xla traceme|framework name scope|"
    r"framework ops|source code|launch stats|.*name scope.*)$",
    re.IGNORECASE,
)
GAP_FLOOR_S = 100e-6
SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "traced_window"


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return paths[-1] if paths else None


def union_seconds(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _op_lines(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    if named:
        return named
    return [ln for ln in lines if not _SUMMARY_LINES.match(ln.name or "")]


def clean_name(name: str) -> str:
    """An operation's name as the trace gives it, made safe for a JSON
    line: at most 64 characters of letters, digits, ``_ . : -``."""
    return re.sub(r"_+", "_", re.sub(r"[^A-Za-z0-9.:\-]", "_", name)).strip("_")[:64]


def reduce_trace(
    path: str,
    *,
    span_prefix: str = SPAN_PREFIX,
    window: Optional[Tuple[float, float]] = None,
    top: int = 10,
) -> dict:
    """Reduce one ``.xplane.pb``.  Times are seconds on the trace's clock.

    Returns ``{"chips": n, "window_s", "busy_s" (mean over chips),
    "busy_s_per_chip", "idle_pct", "device_ops": [[name, s], ...],
    "idle_gaps": [[what, s], ...], "planes": [...], "lines": {...},
    "window_from": "caller" | "span" | "operations"}``.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = list(data.planes)
    devices = [p for p in planes if _DEVICE.match(p.name or "")]
    per_chip: Dict[str, list] = {}
    op_seconds: Dict[str, float] = {}
    line_names: Dict[str, list] = {}
    for p in devices:
        line_names[p.name] = [ln.name for ln in p.lines]
        iv = []
        for ln in _op_lines(p):
            for ev in ln.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                iv.append((a, b, ev.name))
        per_chip[p.name] = iv

    # host spans, per thread line; the traced window's own span apart
    host_spans: Dict[str, list] = {}
    window_from = "caller" if window is not None else "operations"
    for p in planes:
        if _DEVICE.match(p.name or "") or not (p.name or "").startswith("/host"):
            continue
        for i, ln in enumerate(p.lines):
            evs = []
            for ev in ln.events:
                if not (ev.name or "").startswith(span_prefix):
                    continue
                a, b = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if ev.name != WINDOW_SPAN:
                    evs.append((a, b, ev.name[len(span_prefix):]))
                elif window_from == "operations":
                    window, window_from = (a, b), "span"
            if evs:
                host_spans[f"{p.name}/{i}/{ln.name}"] = sorted(evs)

    all_iv = [x for iv in per_chip.values() for x in iv]
    if window is None and all_iv:
        window = (min(a for a, _, _ in all_iv), max(b for _, b, _ in all_iv))
    out = {
        "chips": len(devices),
        "planes": [p.name for p in planes],
        "lines": line_names,
        "host_span_threads": len(host_spans),
        "window_from": window_from,
    }
    if not all_iv or window is None or window[1] <= window[0]:
        out.update(window_s=0.0, busy_s=0.0, busy_s_per_chip=[], idle_pct=None,
                   device_ops=[], idle_gaps=[])
        return out
    w0, w1 = window
    busy_per_chip, gaps = [], []
    for name, iv in sorted(per_chip.items()):
        clipped = [
            (max(a, w0), min(b, w1)) for a, b, _ in iv if b > w0 and a < w1
        ]
        busy, merged = union_seconds(clipped)
        busy_per_chip.append(busy)
        edge = w0
        for a, b in merged:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        if w1 > edge:
            gaps.append((edge, w1))
        for a, b, op in iv:
            lo, hi = max(a, w0), min(b, w1)
            if hi > lo:
                op = clean_name(op)
                op_seconds[op] = op_seconds.get(op, 0.0) + (hi - lo)
    window_s = w1 - w0
    busy_s = sum(busy_per_chip) / len(busy_per_chip)

    by_what: Dict[str, float] = {}
    for a, b in gaps:
        d = b - a
        if d < GAP_FLOOR_S:
            by_what["gaps_under_100us"] = by_what.get("gaps_under_100us", 0.0) + d
            continue
        if not host_spans:
            by_what["unattributed"] = by_what.get("unattributed", 0.0) + d
            continue
        mid = 0.5 * (a + b)
        share = d / len(host_spans)
        for spans in host_spans.values():
            what = "between_steps"
            for sa, sb, nm in spans:
                if sa <= mid < sb and nm != "step":
                    what = nm
                    break
                if sa > mid:
                    break
            by_what[what] = by_what.get(what, 0.0) + share
    # seconds are summed over chips: divide so that they add up to the
    # mean idle time of one chip
    n = len(busy_per_chip)
    out.update(
        window_s=window_s,
        busy_s=busy_s,
        busy_s_per_chip=busy_per_chip,
        idle_pct=100.0 * (1.0 - busy_s / window_s),
        device_ops=[
            [k, v / n]
            for k, v in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
        ],
        idle_gaps=[
            [k, v / n] for k, v in sorted(by_what.items(), key=lambda kv: -kv[1])
        ][:top],
    )
    return out
