"""The program's own account of a traced run: its ``ps.`` host spans
(``parameter_server_tpu/utils/trace.py``) and the device operations under its
``ps.`` scopes (``jax.named_scope`` in ``kv/table.py``, ``kv/worker.py``,
``models/``), read from the run's ``.xplane.pb``.

    python3 -m benchmarks.harness.program_spans <file.xplane.pb>

prints the whole account.  The per-layer metrics that read it call
:func:`for_run`, which finds the file the way ``run.py`` writes it.

**Host spans** are the ``TraceAnnotation`` events of the ``/host`` planes
whose name starts with ``ps.`` and that lie wholly inside the traced window
(the ``bench.traced_window`` span), with their statistics as attributes
(``req``, ``cpu_us``, ...).  Nesting on a thread gives the parent; self time
is duration less what the children cover.  A ``ps.server.*`` span joins the
``ps.worker.submit`` with the same ``req``, whose outermost ancestor is the
request's root (``ps.worker.pull`` / ``ps.worker.push``).  Submits and
roots are looked up in the whole trace, not only the window; a request
whose timestamp is lower than every submit the trace holds of its customer
was submitted before the profiler started and cannot join, nor can one
whose submit the trace holds without the root around it.

**How a scope reaches the trace** (found on a TPU v5e, jax 0.9.0; my chip
run, PR 25): a device plane has the lines ``XLA Modules``, ``XLA Ops``,
``Async XLA Ops`` and ``TC Overlay`` and no ``Framework Name Scope`` line.
An ``XLA Ops`` event's name is the instruction's HLO text, and its own
statistics are its offset and duration.  The scoped operation name (``tf_op``:
``jit(_push_impl)/ps.table.apply/ps.apply.fused/scatter:``) and the source
line (``source``) are statistics of the event's METADATA, which
``jax.profiler.ProfileData`` does not hand out.  So events, lines and times
come from ``ProfileData`` as in ``trace_reduce.py``, and the metadata of the
device planes is read from the file's bytes by the forty lines of protobuf
wire format below, keyed by (program id, event name); an operation's program
is the ``XLA Modules`` event it runs in (``jit__push_impl(<program id>)``).
An operation without a ``ps.`` scope of its own takes the scope of the
operation that holds it (a ``while``'s body), else the outermost scope that
all scoped operations of its program share: the compiler's own whole-shard
passes in ``jit__push_impl`` (relayouts of the table with no metadata at all)
are part of ``ps.table.apply``.  Eagerly dispatched primitives
(``jit_sigmoid``, ``jit__pad``) carry no scope: jax leaves the name stack
out of them.

**Device seconds under a scope** are the union of the intervals of the
operations under it on a chip, clipped to the window (a ``while`` holds its
body), summed over the chips.  The operations no other holds partition a
chip's busy time by their outermost scope, so the scopes and the unscoped
rest add up to ``trace_reduce``'s ``busy_s`` x chips.

**Idle gaps** of 100 us or more are attributed as ``trace_reduce.py`` does,
to what is in flight at the gap's midpoint on the worker threads (those that
carry ``ps.worker.*`` roots), split evenly over them: the innermost ``ps.``
span; under a ``ps.worker.wait`` the innermost span of the same ``req`` on
the servers' threads (``ps.worker.wait>ps.server.d2h``), or ``>queue`` when
no server holds the request (it waits in an inbox, or its reply is on its
way); with no ``ps.`` span in flight the benchmark's own span (``bench.grad``)
or ``between_steps``.
"""

from __future__ import annotations

import dataclasses
import os
import re
import statistics
import sys
import warnings
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import trace_reduce
from benchmarks.harness.trace_reduce import GAP_FLOOR_S, WINDOW_SPAN, union_seconds

PREFIX = "ps."
_SCOPE = re.compile(r"ps\.[A-Za-z0-9_.]+")
_PROGRAM = re.compile(r"\((\d+)\)$")
_OP = re.compile(r"^%?([^ =]+)")
#: roots of a request on a worker's thread
ROOTS = ("ps.worker.pull", "ps.worker.push", "ps.worker.pull_serve")
UNSCOPED = "unscoped"


# -- the event metadata ProfileData does not hand out -----------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return out, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: varints as ints,
    length-delimited fields as bytes, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def op_metadata(path: str) -> Dict[str, Dict[Tuple[int, str], Tuple[str, str]]]:
    """``{device plane: {(program id, event name): (tf_op, source)}}`` from
    ``XSpace.planes[].event_metadata[].stats`` (module docstring)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metas = "", {}, []
        for f2, v2 in _fields(plane):
            if f2 == 2:  # XPlane.name
                name = v2.decode()
            elif f2 == 5:  # stat_metadata: map<id, XStatMetadata{id=1, name=2}>
                entry = dict(_fields(v2))
                stat_names[entry[1]] = dict(_fields(entry[2])).get(2, b"").decode()
            elif f2 == 4:  # event_metadata: map<id, XEventMetadata>
                metas.append(dict(_fields(v2))[2])
        if not trace_reduce._DEVICE.match(name):
            continue
        ops = out[name] = {}
        for meta in metas:
            ev_name, stats = "", {}
            for f3, v3 in _fields(meta):
                if f3 == 2:  # XEventMetadata.name
                    ev_name = v3.decode()
                elif f3 == 5:  # XEventMetadata.stats
                    stat = list(_fields(v3))
                    key = stat_names.get(dict(stat).get(1))
                    if key in ("tf_op", "source", "program_id"):
                        stats[key] = next(v for k, v in stat if k != 1)
            if "tf_op" in stats or "source" in stats:
                ops[int(stats.get("program_id", 0)), ev_name] = (
                    bytes(stats.get("tf_op", b"")).decode(),
                    bytes(stats.get("source", b"")).decode(),
                )
    return out


# -- spans --------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class Span:
    name: str
    thread: str
    start: float
    end: float
    attrs: dict
    parent: Optional["Span"] = None
    covered: float = 0.0  # by its children

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.dur - self.covered

    def root(self) -> "Span":
        sp = self
        while sp.parent is not None:
            sp = sp.parent
        return sp


def _in_order(sp: Span):
    """Sort key: by start, a holder before what it holds."""
    return sp.start, -sp.end


def _nest(spans: List[Span]) -> None:
    """Parents by containment, per thread (spans of a thread never cross)."""
    by_thread: Dict[str, list] = {}
    for sp in spans:
        by_thread.setdefault(sp.thread, []).append(sp)
    for mine in by_thread.values():
        stack: List[Span] = []
        for sp in sorted(mine, key=_in_order):
            while stack and stack[-1].end < sp.end:
                stack.pop()
            if stack:
                sp.parent = stack[-1]
                stack[-1].covered += sp.dur
            stack.append(sp)


def _innermost(spans: List[Span], t: float) -> Optional[Span]:
    """The innermost of one thread's spans in flight at ``t`` (spans sorted
    by start; a later start inside an earlier span is nested in it)."""
    best = None
    for sp in spans:
        if sp.start > t:
            break
        if t < sp.end:
            best = sp
    return best


@dataclasses.dataclass
class Account:
    path: str
    window: Tuple[float, float]
    chips: int
    spans: List[Span]  # wholly inside the window
    by_name: Dict[str, List[Span]]
    submits: Dict[str, Span]  # by req, of the whole trace
    bench_ms: Dict[str, List[float]]  # the benchmark's own spans in the window
    busy_s: float  # summed over the chips
    scope_s: Dict[str, float]  # device seconds under each scope, any depth
    top_s: Dict[str, float]  # busy_s split by outermost scope, and UNSCOPED
    ops: Dict[str, Dict[Tuple[str, str], float]]  # innermost scope -> (op, source) -> s
    idle_gaps: Dict[str, float]  # of the mean chip

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def durations_ms(self, name: str) -> List[float]:
        return [1e3 * sp.dur for sp in self.by_name.get(name, [])]

    def root_of(self, sp: Span) -> Optional[Span]:
        """The worker's root a ``ps.server.*`` / ``ps.van.deliver`` span
        belongs to, through the ``ps.worker.submit`` of its ``req``."""
        sub = self.submits.get(sp.attrs.get("req"))
        if sub is None:
            return None
        root = sub.root()
        return root if root.name in ROOTS else None

    def requests(self, kind: str) -> float:
        """Worker requests whose ``ps.server.<kind>`` spans lie in the
        window, each leg counting 1 / legs of its request."""
        n = 0.0
        for sp in self.by_name.get(f"ps.server.{kind}", []):
            sub = self.submits.get(sp.attrs.get("req"))
            if sub is not None and sub.attrs.get("legs"):
                n += 1.0 / sub.attrs["legs"]
        return n

    def dispatches(self, prefix: str) -> int:
        return sum(
            1 for sp in self.by_name.get("ps.server.dispatch", [])
            if str(sp.attrs.get("op", "")).startswith(prefix)
        )

    def recv_threads(self) -> Dict[str, float]:
        """``{server recv thread: busy share of the window}``: the union of
        the ``ps.van.deliver`` spans on each thread that delivers PUSH or
        PULL requests."""
        by_thread: Dict[str, list] = {}
        servers = set()
        for sp in self.by_name.get("ps.van.deliver", []):
            by_thread.setdefault(sp.thread, []).append((sp.start, sp.end))
            if sp.attrs.get("is_request") and sp.attrs.get("verb") in ("PUSH", "PULL"):
                servers.add(sp.thread)
        return {
            t: union_seconds(by_thread[t])[0] / self.window_s
            for t in sorted(servers)
        }


_CACHE: Dict[str, Account] = {}


def load(path: str) -> Account:
    """Parse ``path`` once per process."""
    if path not in _CACHE:
        with warnings.catch_warnings():
            # reading an event's statistics warns about jaxlib's own binding
            warnings.simplefilter("ignore", DeprecationWarning)
            _CACHE[path] = _load(path)
    return _CACHE[path]


def _load(path: str) -> Account:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    window, spans, bench = None, [], {}
    for p in planes:
        if not (p.name or "").startswith("/host"):
            continue
        for i, ln in enumerate(p.lines):
            thread = f"{p.name}/{i}/{ln.name}"
            for ev in ln.events:
                name = ev.name or ""
                a, b = ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9
                if name == WINDOW_SPAN:
                    window = (a, b)
                elif name.startswith(PREFIX):
                    spans.append(Span(name, thread, a, b, dict(ev.stats)))
                elif name.startswith(trace_reduce.SPAN_PREFIX):
                    bench.setdefault(thread, []).append(Span(name, thread, a, b, {}))
    devices = [p for p in planes if trace_reduce._DEVICE.match(p.name or "")]
    if window is None:  # no traced window: nothing can be laid against it
        return Account(
            path, (0.0, 0.0), len(devices), [], {}, {}, {}, 0.0, {}, {}, {}, {}
        )
    w0, w1 = window
    _nest(spans)
    submits = {
        sp.attrs["req"]: sp for sp in spans
        if sp.name == "ps.worker.submit" and "req" in sp.attrs
    }
    spans = [sp for sp in spans if sp.start >= w0 and sp.end <= w1]
    by_name: Dict[str, List[Span]] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append(sp)
    bench_ms: Dict[str, List[float]] = {}
    for mine in bench.values():
        for sp in mine:
            if sp.start >= w0 and sp.end <= w1:
                bench_ms.setdefault(sp.name, []).append(1e3 * sp.dur)

    meta = op_metadata(path) if devices else {}
    busy_s, gaps = 0.0, []
    under: Dict[str, float] = {}
    top: Dict[str, float] = {}
    ops: Dict[str, Dict[Tuple[str, str], float]] = {}
    for p in devices:
        busy, chip_gaps, chip_under, chip_top = _chip(
            p, meta.get(p.name, {}), w0, w1, ops
        )
        busy_s += busy
        gaps += chip_gaps
        for acc, part in ((under, chip_under), (top, chip_top)):
            for k, v in part.items():
                acc[k] = acc.get(k, 0.0) + v
    idle = _idle_gaps(gaps, spans, bench)
    n = max(1, len(devices))
    return Account(
        path, window, len(devices), spans, by_name, submits, bench_ms, busy_s,
        under, top, ops, {k: v / n for k, v in idle.items()},
    )


def _chip(plane, meta, w0, w1, ops):
    """One device plane: busy seconds, idle gaps, seconds under each scope
    and the split of busy by outermost scope; adds to ``ops``."""
    modules = sorted(
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
         int(m.group(1)))
        for ln in plane.lines if ln.name == "XLA Modules"
        for ev in ln.events
        for m in [_PROGRAM.search(ev.name or "")] if m
    )
    events = sorted(
        (ev.start_ns * 1e-9, -(ev.start_ns + ev.duration_ns) * 1e-9, ev.name or "")
        for ln in trace_reduce._op_lines(plane) for ev in ln.events
    )
    shared: Dict[int, set] = {}  # program -> outermost scopes of its operations
    for (program, _name), (tf_op, _source) in meta.items():
        found = _SCOPE.findall(tf_op)
        if found:
            shared.setdefault(program, set()).add(found[0])
    mi, stack = 0, []  # stack of (end, scope path)
    under_iv: Dict[str, list] = {}
    top_iv: Dict[str, list] = {}
    all_iv = []
    for a, neg_b, name in events:
        b = -neg_b
        while mi + 1 < len(modules) and modules[mi][1] <= a:
            mi += 1
        program = (
            modules[mi][2] if modules and modules[mi][0] <= a < modules[mi][1] else 0
        )
        tf_op, source = meta.get((program, name), ("", ""))
        while stack and stack[-1][0] < b:
            stack.pop()
        path = _SCOPE.findall(tf_op)
        if not path and stack:
            path = stack[-1][1]
        elif not path and len(shared.get(program, ())) == 1:
            path = list(shared[program])
        lo, hi = max(a, w0), min(b, w1)
        if hi > lo:
            all_iv.append((lo, hi))
            for scope in set(path):
                under_iv.setdefault(scope, []).append((lo, hi))
            if not stack:  # held by no other operation: a share of busy
                top_iv.setdefault(path[0] if path else UNSCOPED, []).append((lo, hi))
            inner = ops.setdefault(path[-1] if path else UNSCOPED, {})
            # an operation the compiler made has no source line: its HLO
            key = (_OP.match(name).group(1), source or name.partition(" = ")[2][:96])
            inner[key] = inner.get(key, 0.0) + (hi - lo)
        stack.append((b, path))
    busy, merged = union_seconds(all_iv)
    gaps, edge = [], w0
    for a, b in merged:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if w1 > edge:
        gaps.append((edge, w1))
    return (
        busy, gaps,
        {k: union_seconds(v)[0] for k, v in under_iv.items()},
        {k: union_seconds(v)[0] for k, v in top_iv.items()},
    )


def _idle_gaps(gaps, spans, bench) -> Dict[str, float]:
    """Seconds of idle gaps by what was in flight (module docstring),
    summed over the chips."""
    by_thread: Dict[str, List[Span]] = {}
    for sp in sorted(spans, key=_in_order):
        by_thread.setdefault(sp.thread, []).append(sp)
    workers = sorted({sp.thread for sp in spans if sp.parent is None and sp.name in ROOTS})
    own = {  # the benchmark's spans on the worker threads, its step apart
        t: sorted((s for s in bench.get(t, []) if s.name != "bench.step"), key=_in_order)
        for t in workers
    }
    out: Dict[str, float] = {}

    def add(what, seconds):
        out[what] = out.get(what, 0.0) + seconds

    for a, b in gaps:
        d = b - a
        if d < GAP_FLOOR_S:
            add("gaps_under_100us", d)
            continue
        if not workers:
            add("unattributed", d)
            continue
        mid, share = 0.5 * (a + b), d / len(workers)
        for thread in workers:
            sp = _innermost(by_thread[thread], mid)
            if sp is None:
                theirs = _innermost(own[thread], mid)
                add(theirs.name if theirs is not None else "between_steps", share)
            elif sp.name != "ps.worker.wait":
                add(sp.name, share)
            else:
                held = [
                    inner for t, mine in by_thread.items() if t != thread
                    for inner in [_innermost(mine, mid)]
                    if inner is not None and _req_of(inner) == sp.attrs.get("req")
                ]
                for inner in held:
                    add(f"{sp.name}>{inner.name}", share / len(held))
                if not held:
                    add(f"{sp.name}>queue", share)
    return out


def _req_of(sp: Span) -> Optional[str]:
    """``req`` of the span or of the nearest ancestor that carries one."""
    while sp is not None:
        if "req" in sp.attrs:
            return sp.attrs["req"]
        sp = sp.parent
    return None


# -- what the per-layer metrics read ---------------------------------------------
def for_run(run) -> Optional[Account]:
    """The account of ``run``'s trace, or ``None``: an untraced run, no
    file, or a program without ``ps.`` spans (the parent of PR 25), where
    every metric that reads this is left out.  The first call of a run
    logs the account to standard error."""
    if not run.trace:
        return None
    path = trace_reduce.find_xplane(
        os.path.join(run.bench_dir, "out", "trace", run.name)
    )
    if path is None:
        return None
    first = path not in _CACHE
    acc = load(path)
    if first:
        print(render(acc), file=sys.stderr, flush=True)
    return acc if acc.spans else None


def span_ms_p50(run, name: str) -> Optional[float]:
    """Median duration of the window's ``name`` spans, in ms."""
    acc = for_run(run)
    durs = acc.durations_ms(name) if acc is not None else []
    return statistics.median(durs) if durs else None


def kernel_ms(run, scope: str, op_prefix: str) -> Optional[float]:
    """Device ms under ``scope`` per ``ps.server.dispatch`` whose ``op``
    starts with ``op_prefix``."""
    acc = for_run(run)
    n = acc.dispatches(op_prefix) if acc is not None else 0
    if not n or scope not in acc.scope_s:
        return None
    return 1e3 * acc.scope_s[scope] / n


def kernel_roofline_pct(run, scope: str, kind: str, bytes_per_request) -> Optional[float]:
    """Share of the HBM roofline under ``scope``: the bytes the window's
    ``kind`` requests need (``bytes_per_request`` of the byte model, from
    the unique rows a step touches) over the peak, over the device seconds
    under the scope.  Seconds are summed over the chips and each chip
    works on its own share of the bytes, so the peak is one chip's."""
    acc = for_run(run)
    if acc is None or run.peaks is None or not acc.scope_s.get(scope):
        return None
    need = acc.requests(kind) * bytes_per_request
    if not need:
        return None
    return 100.0 * (need / run.peaks["hbm_bytes_per_s"]) / acc.scope_s[scope]


def above_100(name: str, value: float) -> List[str]:
    return [f"{name} = {value:.4f} % is above 100"] if value > 100 else []


# -- the account, printed -------------------------------------------------------
def checks(acc: Account) -> Dict[str, float]:
    """The figures that say whether the account holds together."""
    servers = acc.by_name.get("ps.server.pull", []) + acc.by_name.get("ps.server.push", [])
    joined = sum(1 for sp in servers if acc.root_of(sp) is not None)
    first: Dict[str, int] = {}  # customer -> lowest timestamp a submit holds
    for req in acc.submits:
        customer, _, ts = req.rpartition("/")
        first[customer] = min(int(ts), first.get(customer, int(ts)))
    early = 0  # the submit, or the root around it, began before the profiler
    for sp in servers:
        customer, _, ts = str(sp.attrs.get("req", "")).rpartition("/")
        sub = acc.submits.get(sp.attrs.get("req"))
        early += (
            ts.isdigit() and int(ts) < first.get(customer, 0)
            if sub is None else sub.parent is None
        )
    roots = [sp for sp in acc.spans if sp.parent is None and sp.name in ROOTS]
    return {
        "server_spans": len(servers),
        "server_spans_joined": joined,
        "server_spans_before_trace": early,
        "roots": len(roots),
        "max_children_over_root": max(
            (sp.covered / sp.dur for sp in roots if sp.dur > 0), default=0.0
        ),
        "busy_s": acc.busy_s,
        "scoped_plus_unscoped_s": sum(acc.top_s.values()),
    }


def render(acc: Account) -> str:
    out = [f"[program_spans] {acc.path}"]
    if acc.window_s <= 0:
        return out[0] + ": no traced window"
    out.append(
        f"window {acc.window_s:.4f} s, {acc.chips} chip(s), device busy "
        f"{acc.busy_s:.4f} s summed over chips, {len(acc.spans)} ps. spans"
    )
    out.append(f"{'host span':28s} {'count':>6s} {'total s':>9s} {'self s':>9s} "
               f"{'p50 ms':>9s} {'cpu %':>6s}")
    for name in sorted(acc.by_name):
        sps = acc.by_name[name]
        total = sum(sp.dur for sp in sps)
        cpu = sum(sp.attrs.get("cpu_us", 0) for sp in sps) * 1e-6
        out.append(
            f"{name:28s} {len(sps):6d} {total:9.4f} "
            f"{sum(sp.self_s for sp in sps):9.4f} "
            f"{statistics.median(1e3 * sp.dur for sp in sps):9.3f} "
            f"{100 * cpu / total if total else 0:6.1f}"
        )
    waits = [
        sp.attrs["wait_us"] for sp in acc.by_name.get("ps.van.deliver", [])
        if "wait_us" in sp.attrs
    ]
    if waits:
        out.append(f"ps.van.deliver wait_us: p50 {statistics.median(waits):.0f} "
                   f"max {max(waits)} over {len(waits)}")
    for thread, share in acc.recv_threads().items():
        out.append(f"recv thread {thread}: busy {100 * share:.1f} % of the window")
    c = checks(acc)
    out.append(
        f"join: {c['server_spans_joined']} of {c['server_spans']} ps.server.pull/push "
        f"spans reach a root by req ({c['server_spans_before_trace']} of "
        f"requests that began before the profiler); {c['roots']} roots, children "
        f"cover at most {100 * c['max_children_over_root']:.2f} % of one"
    )
    for kind in ("pull", "push"):
        ours, theirs = acc.durations_ms(f"ps.worker.{kind}"), acc.bench_ms.get(f"bench.{kind}")
        if ours and theirs:
            out.append(
                f"ps.worker.{kind} p50 {statistics.median(ours):.3f} ms beside "
                f"bench.{kind} p50 {statistics.median(theirs):.3f} ms in the same window"
            )
    out.append("device seconds under a scope (summed over chips; inner scopes lie in outer ones):")
    for scope in sorted(acc.scope_s):
        out.append(f"  {scope:28s} {acc.scope_s[scope]:9.4f}")
    out.append(
        "busy by outermost scope: "
        + ", ".join(f"{k} {v:.4f}" for k, v in sorted(acc.top_s.items(), key=lambda kv: -kv[1]))
        + f" = {c['scoped_plus_unscoped_s']:.4f} s of busy {acc.busy_s:.4f} s"
    )
    out.append("operations by innermost scope (a while holds its body), seconds, source:")
    for scope in sorted(acc.ops):
        for (op, source), s in sorted(acc.ops[scope].items(), key=lambda kv: -kv[1])[:6]:
            out.append(f"  {scope:24s} {op:44s} {s:8.4f}  {source}")
    out.append("idle gaps of the mean chip by what was in flight on the worker threads:")
    for what, s in sorted(acc.idle_gaps.items(), key=lambda kv: -kv[1]):
        out.append(f"  {what:44s} {s:8.4f}")
    return "\n".join(out)


if __name__ == "__main__":
    print(render(load(sys.argv[1])))
