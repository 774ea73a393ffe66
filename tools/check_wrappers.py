#!/usr/bin/env python
"""Static contract check for VanWrapper subclasses.

The Van decorator stack (``ReliableVan(ChaosVan(LoopbackVan()))`` +
``CoalescingVan`` + ``MeteredVan``) relies on two conventions that, until
PR 6, nothing enforced:

1. **flush/close delegate down the chain.**  ``VanWrapper`` provides
   delegating defaults, but a subclass that overrides either (to drain its
   own buffers / join its own threads) MUST still call ``self.inner.flush``
   / ``self.inner.close`` (or ``super()``'s) — otherwise a buffering layer
   below it silently never drains, which reads as message loss only under
   load.  This was a real latent bug: ``ReliableVan.flush`` drained its own
   inflight table but swallowed the rest of the stack.

2. **counters() does NOT recurse.**  ``utils.metrics.transport_counters``
   walks the ``.inner`` chain itself and sums each layer's ``counters()``;
   a layer that also merged its inner's counters would double-count every
   key below it.

3. **No pickle on the frame hot path.**  The flat wire codec
   (``core/frame.py`` + its users ``core/tcp_van.py``, ``core/resender.py``,
   ``core/coalesce.py``) exists to kill the per-message pickle serialize/
   copy tax; an ``import pickle`` (or ``cPickle``/``dill``) creeping back
   into any of those modules silently re-introduces it — and puts
   arbitrary-code-execution deserialization back on a network-facing path.
   Enforced as a module-level import ban on :data:`NO_PICKLE_MODULES`
   (``check_no_pickle``).

4. **Flight-recorder kinds come from the closed registry.**  Every
   ``flightrec.record("<kind>", ...)`` call site (and the aliased/method
   forms ``rec(...)``, ``recorder.record(...)``) must pass a LITERAL kind
   string present in ``core/flightrec.py``'s ``EVENTS`` frozenset —
   otherwise the event taxonomy drifts stringly-typed and
   ``tools/postmortem.py`` / the SLO plane silently miss events
   (``check_flightrec_calls``; registry parsed by AST via
   ``load_event_registry``, which fails loudly if the literal moves).

5. **CONTROL verbs come from the closed registry.**  Every
   ``{"cmd": ...}`` payload literal must name a verb from
   ``core/manager.py``'s ``CONTROL_VERBS`` frozenset — either as one of
   the module's verb constants (``HEARTBEAT``, ``TELEMETRY``, ...) or as
   a literal string in the set.  A stringly-typed ``{"cmd": "telemtry"}``
   typo would otherwise fall through ``Manager.handle_request``'s elif
   chain and be silently acked as a no-op (``check_control_verbs``;
   registry parsed by AST via ``load_verb_registry``, same loud-failure
   stance as the event registry).

6. **The PUSH-ack path never blocks on device work.**  The server's
   bundle-batched apply engine (ISSUE 11) acks a push as soon as the
   donated-buffer device apply is DISPATCHED; a ``np.asarray`` /
   ``np.array`` / ``jax.device_get`` / ``.block_until_ready`` creeping
   into the post-dispatch bookkeeping (:data:`SYNC_FREE_FUNCS` in
   ``kv/server.py``) would silently put the whole device apply latency
   back on every worker's ack round trip.  Enforced per registered
   function (``check_push_ack_sync_free``); a registered function that
   disappears (rename) is itself a loud failure, never a vacuous pass.

7. **The ApplyLedger's submit side is sync-free too.**  The device-plane
   ledger (ISSUE 12, ``kv/ledger.py``) runs its registration methods
   (:data:`LEDGER_SYNC_FREE_FUNCS`: ``begin``/``mark_host``/``mark_h2d``/
   ``submit``/``overloaded``) ON the ack path — a device sync creeping into
   any of them would reintroduce exactly the latency the ledger exists to
   observe.  Same checker, same loud-failure stance.  The ``apply.*``
   event kinds the ledger journals must also be present in the EVENTS
   registry (:data:`REQUIRED_EVENTS`) — a registry edit that drops them
   would silence the device plane while every record call still "worked".

8. **The shm fast path is copy-free.**  Transport v2's whole win
   (ISSUE 17) is that a frame crosses a colocated link with ONE data
   movement (the slice-assign into the shared mapping) and is decoded as
   views in place on the other side.  A ``.tobytes()``, ``bytes(...)``
   staging copy, or ``ctypes.string_at`` creeping into the registered
   hot-path functions (:data:`SHM_COPY_FREE_FUNCS` in
   ``core/shm_ring.py``, :data:`VAN_COPY_FREE_FUNCS` in
   ``core/tcp_van.py`` — which also guards the borrowed-native-buffer
   recv path) silently reintroduces the per-frame copy tax the ring
   exists to kill.  Same loud-failure stance as the sync-free checks: a
   registered function that disappears is itself a violation.

9. **Trace-span recording is gated behind the sampling predicate.**  The
   request-tracing plane (ISSUE 18) promises ZERO per-message overhead
   for unsampled traffic: a ``trace.*`` flightrec record (or the aliased
   ``self._record("trace.*", ...)`` form) reached unconditionally on the
   hot path would put a span allocation on every message at 1/1024
   sampling.  Every registered hot-path function
   (:data:`TRACE_GATED_FUNCS`) must emit its ``trace.*`` records under an
   ``if`` — the sampling/context-presence gate — and a registered
   function that stops recording any ``trace.*`` kind (refactored away)
   is itself a violation (``check_trace_gated``).  The ``trace.*`` kinds
   are pinned in :data:`REQUIRED_EVENTS` so a registry edit cannot
   silence the plane.

10. **Span names come from the closed registry.**  ``utils/trace.py::span``
   is the one way the package records a span (ISSUE 25), and the names are
   what ``benchmarks/harness/program_spans.py`` and its per-layer metrics
   read from the profiler's trace.  Every literal first argument of a
   ``.span(...)`` / ``span(...)`` call under the package must start with
   ``ps.`` and be present in ``utils/trace.py``'s ``SPANS`` frozenset
   (``check_span_names``; registry parsed by AST via
   ``load_span_registry``, same loud-failure stance as the event
   registry).

Pure-AST check (no imports of the checked modules), so it runs in any
environment and is wired as a tier-1 test (``tests/test_wrapper_contract.py``).
Exit code 0 = clean; 1 = violations (one line each).
"""

from __future__ import annotations

import ast
import pathlib
import sys
from typing import Iterator, List

PKG = pathlib.Path(__file__).resolve().parent.parent / "parameter_server_tpu"

#: methods that must delegate to the inner van when overridden.
DELEGATING = ("flush", "close")

#: frame hot-path modules where any pickle-family import is banned —
#: encode/decode (tcp_van + frame), stamp/verify (resender), bundling
#: (coalesce).  Paths relative to the package root.
NO_PICKLE_MODULES = (
    "core/frame.py",
    "core/tcp_van.py",
    "core/resender.py",
    "core/coalesce.py",
)

#: module names whose import re-introduces the serialization tax (and an
#: arbitrary-code-execution decode) on the hot path.
_PICKLE_NAMES = frozenset(
    {"pickle", "cPickle", "_pickle", "dill", "cloudpickle", "marshal"}
)

#: module holding the closed span-name registry (``SPANS`` frozenset
#: literal), relative to the package root, and the prefix every name has.
TRACE_MODULE = "utils/trace.py"
SPAN_PREFIX = "ps."

#: module holding the closed event-kind registry (``EVENTS`` frozenset
#: literal), relative to the package root.
FLIGHTREC_MODULE = "core/flightrec.py"

#: module holding the closed CONTROL-verb registry (``CONTROL_VERBS``
#: frozenset literal + the verb constants), relative to the package root.
MANAGER_MODULE = "core/manager.py"

#: bare-callable names treated as flight-recorder record aliases (the
#: ``rec = recorder.record or flightrec.record`` pattern in utils/slo.py).
_RECORD_ALIASES = frozenset({"record", "rec"})

#: module holding the server's push-ack path, relative to the package root.
SERVER_MODULE = "kv/server.py"

#: ``kv/server.py`` functions on the PUSH-ack path — everything that runs
#: AFTER the device apply is dispatched and BEFORE the ack returns.  These
#: must never observe a device result: the ack's latency is host
#: bookkeeping only.  (``_upload_values`` / ``_handle_push_single`` stay
#: unregistered: their ``np.asarray`` touches the HOST wire plane before
#: dispatch; ``_forward_push`` is wire I/O that deliberately blocks on the
#: replica CHAIN ack in sync mode, not on device work.)
SYNC_FREE_FUNCS = frozenset(
    {
        "_ack_push",
        "_apply_push_group",
        "_push_group_rounds",
        "_push_group_combined",
    }
)

#: module holding the device-plane apply ledger, relative to the package
#: root (ISSUE 12).
LEDGER_MODULE = "kv/ledger.py"

#: ``kv/ledger.py`` methods that run on the server's ack path (register /
#: split-point stamping / the overload read in ``_ack_push``) — host
#: bookkeeping only, same contract as :data:`SYNC_FREE_FUNCS`.  The reaper
#: (``_reap_loop``/``_reap_once``/``_retire``) polls device readiness by
#: design and is deliberately NOT registered.
LEDGER_SYNC_FREE_FUNCS = frozenset(
    {
        "begin",
        "mark_host",
        "mark_h2d",
        "submit",
        "overloaded",
    }
)

#: event kinds that MUST exist in the EVENTS registry: the device-plane
#: taxonomy the ApplyLedger journals (ISSUE 12) plus the serving-plane
#: taxonomy the hot-row cache and admission control journal (ISSUE 13).
#: Checked in ``main`` so a registry edit dropping them fails loudly
#: instead of silencing either plane.
REQUIRED_EVENTS = frozenset({
    "apply.submit",
    "apply.done",
    "apply.backlog",
    "cache.hit",
    "cache.miss",
    "cache.invalidate",
    "serve.shed",
    # quantized wire plane (ISSUE 14): encode/decode hooks plus the
    # error-feedback residual lifecycle — dropping any of these would
    # silence the compression plane's observability
    "compress.encode",
    "compress.decode",
    "compress.residual_reset",
    # hierarchical push (ISSUE 15): pre-reduction, leader election, and
    # the degradation-to-direct-push edge — dropping any of these would
    # silence the group plane's observability
    "group.reduce",
    "group.elect",
    "group.fallback",
    # durability plane (ISSUE 16): the partitioned-snapshot lifecycle —
    # dropping any of these would silence the checkpoint plane (and lose
    # the interrupted-snapshot anomaly anchor, ckpt.abort)
    "ckpt.begin",
    "ckpt.segment",
    "ckpt.commit",
    "ckpt.restore",
    "ckpt.abort",
    # transport v2 (ISSUE 17): shm-ring and epoll write-queue backpressure
    # — dropping either would silence the fast path's only pressure signal
    "net.ring_full",
    "net.writeq_full",
    # request tracing plane (ISSUE 18): the sampled span taxonomy —
    # submit/dispatch/reply/apply/ack form the span tree critpath.py
    # decomposes; wire_tx/wire_rx/bundle/retransmit are the transport
    # hops merge_traces.py stitches into flow arrows.  Dropping any of
    # these silently unstitches the cross-node timeline.
    "trace.submit",
    "trace.wire_tx",
    "trace.wire_rx",
    "trace.bundle",
    "trace.dispatch",
    "trace.reply",
    "trace.apply",
    "trace.ack",
    "trace.retransmit",
    # war-game plane (ISSUE 19): the scenario runner's schedule must leave
    # a reconstructable trail — begin/phase/inject/heal/action/end — or
    # the scorecard's incident report loses its causal anchors.
    "scenario.begin",
    "scenario.phase",
    "scenario.inject",
    "scenario.heal",
    "scenario.action",
    "scenario.end",
    # consistency plane (ISSUE 20): gate/release pair the postmortem
    # wedged-gate anchor matches on, the graceful-degradation shed edge,
    # and the BoundTuner's retune trail — dropping any of these would
    # silence the enforcement plane's observability.
    "consist.gate",
    "consist.release",
    "consist.shed",
    "consist.retune",
})

#: ``np.<attr>`` calls that materialize a device array on the host.
_SYNC_BANNED_NP = frozenset({"asarray", "array"})

#: hot-path functions (module-relpath -> function names) whose ``trace.*``
#: record sites must sit behind an ``if`` — the sampling / trace-context
#: gate (ISSUE 18).  An unconditional record here would allocate a span
#: per MESSAGE, not per sampled request; a registered function that stops
#: recording any ``trace.*`` kind, or disappears, fails loudly
#: (``check_trace_gated``).  ``unbundle`` is CoalescingVan's nested
#: dispatch closure; the rest are methods.
TRACE_GATED_FUNCS = {
    "kv/worker.py": frozenset({"_trace_submitted", "_on_response"}),
    "kv/server.py": frozenset(
        {"_trace_dispatch", "_stamp_version", "_fence_reply", "_wait_reply"}
    ),
    "kv/ledger.py": frozenset({"_retire"}),
    "core/tcp_van.py": frozenset({"_send_on_conn", "_dispatch_frame"}),
    "core/coalesce.py": frozenset({"unbundle"}),
    "core/resender.py": frozenset({"_retransmit_loop"}),
}

#: module holding the SPSC shared-memory ring (ISSUE 17), relative to the
#: package root.
SHM_RING_MODULE = "core/shm_ring.py"

#: ``core/shm_ring.py`` functions on the per-frame fast path — writer
#: (``write``: the ONE slice-assign into the mapping), reader
#: (``poll``/``read``: zero-copy record views), and slot reclamation
#: (``release``).  Copy-free by contract (:func:`check_copy_free`).
SHM_COPY_FREE_FUNCS = frozenset({"write", "poll", "read", "release"})

#: ``core/tcp_van.py`` functions on the per-frame fast path — the per-conn
#: send choke point (ring write / vectored TCP), the ring reader, and the
#: two receive-side functions that decode borrowed buffers in place.
#: (``_wire_send_segs`` is deliberately NOT registered: its single-buffer
#: fallback legitimately joins segments for the legacy ``ps_van_send``.)
VAN_COPY_FREE_FUNCS = frozenset(
    {"_send_on_conn", "_shm_reader", "_dispatch_loop", "_dispatch_frame"}
)


def _base_names(cls: ast.ClassDef) -> List[str]:
    out = []
    for b in cls.bases:
        if isinstance(b, ast.Name):
            out.append(b.id)
        elif isinstance(b, ast.Attribute):
            out.append(b.attr)
    return out


def _calls(fn: ast.FunctionDef) -> Iterator[ast.Call]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            yield node


def _is_inner_call(call: ast.Call, method: str) -> bool:
    """Matches ``self.inner.<method>(...)`` and ``super().<method>(...)``."""
    f = call.func
    if not (isinstance(f, ast.Attribute) and f.attr == method):
        return False
    v = f.value
    if (
        isinstance(v, ast.Attribute)
        and v.attr == "inner"
        and isinstance(v.value, ast.Name)
        and v.value.id == "self"
    ):
        return True
    if (
        isinstance(v, ast.Call)
        and isinstance(v.func, ast.Name)
        and v.func.id == "super"
    ):
        return True
    return False


def _rel(path: pathlib.Path) -> str:
    try:
        return str(path.relative_to(PKG.parent))
    except ValueError:  # checked file outside the repo (e.g. test fixtures)
        return str(path)


def check_file(path: pathlib.Path) -> List[str]:
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        if "VanWrapper" not in _base_names(cls):
            continue
        methods = {
            n.name: n for n in cls.body if isinstance(n, ast.FunctionDef)
        }
        for name in DELEGATING:
            fn = methods.get(name)
            if fn is None:
                continue  # inherits VanWrapper's delegating default — fine
            if not any(_is_inner_call(c, name) for c in _calls(fn)):
                problems.append(
                    f"{_rel(path)}:{fn.lineno}: "
                    f"{cls.name}.{name} overrides VanWrapper.{name} without "
                    f"delegating to self.inner.{name} (or super().{name}) — "
                    "layers below it never drain"
                )
        fn = methods.get("counters")
        if fn is not None and any(
            _is_inner_call(c, "counters") for c in _calls(fn)
        ):
            problems.append(
                f"{_rel(path)}:{fn.lineno}: "
                f"{cls.name}.counters merges self.inner.counters — "
                "transport_counters walks the chain itself; this "
                "double-counts every layer below"
            )
    return problems


def check_no_pickle(path: pathlib.Path) -> List[str]:
    """Ban pickle-family imports anywhere in ``path`` (module or nested)."""
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    for node in ast.walk(tree):
        names: List[str] = []
        if isinstance(node, ast.Import):
            names = [a.name.split(".")[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
            names = [node.module.split(".")[0]]
        for name in names:
            if name in _PICKLE_NAMES:
                problems.append(
                    f"{_rel(path)}:{node.lineno}: imports {name!r} — the "
                    "frame hot path is pickle-free by contract (flat binary "
                    "codec in core/frame.py); route any object serialization "
                    "through the meta codec instead"
                )
    return problems


def _parse_frozenset_literal(
    path: pathlib.Path, tree: ast.Module, var: str, moved_hint: str
) -> frozenset:
    """Extract a module-level ``<var> = frozenset({...})`` string literal.

    Parsed without importing (same stance as the rest of this tool), which
    is why the registry modules keep their sets plain literals — no
    comprehension, no concatenation.  Raises ``ValueError`` when the
    assignment is missing, non-literal, or empty: a refactor that moves a
    registry must break this check loudly, never let every call site pass
    vacuously against an empty set.
    """
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == var for t in node.targets
        ):
            continue
        value = node.value
        if not (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id == "frozenset"
            and len(value.args) == 1
            and isinstance(value.args[0], (ast.Set, ast.List, ast.Tuple))
        ):
            raise ValueError(
                f"{_rel(path)}:{node.lineno}: {var} must be a plain "
                "frozenset({...}) literal of string constants (AST-parsed)"
            )
        items = []
        for elt in value.args[0].elts:
            if not (
                isinstance(elt, ast.Constant) and isinstance(elt.value, str)
            ):
                raise ValueError(
                    f"{_rel(path)}:{elt.lineno}: non-literal element in "
                    f"{var} — every entry must be a plain string constant"
                )
            items.append(elt.value)
        if not items:
            raise ValueError(f"{_rel(path)}: {var} registry is empty")
        return frozenset(items)
    raise ValueError(
        f"{_rel(path)}: no module-level {var} assignment found — "
        f"{moved_hint}"
    )


def load_event_registry(path: pathlib.Path) -> frozenset:
    """Extract the ``EVENTS`` frozenset literal from ``core/flightrec.py``."""
    tree = ast.parse(path.read_text(), filename=str(path))
    return _parse_frozenset_literal(
        path, tree, "EVENTS",
        "the flight-recorder kind registry moved; update FLIGHTREC_MODULE",
    )


def load_span_registry(path: pathlib.Path) -> frozenset:
    """Extract the ``SPANS`` frozenset literal from ``utils/trace.py``."""
    tree = ast.parse(path.read_text(), filename=str(path))
    return _parse_frozenset_literal(
        path, tree, "SPANS",
        "the span-name registry moved; update TRACE_MODULE",
    )


def check_span_names(path: pathlib.Path, spans: frozenset) -> List[str]:
    """Flag ``.span("<name>", ...)`` / ``span("<name>", ...)`` calls whose
    literal name lacks the ``ps.`` prefix or is absent from ``SPANS``.  A
    non-literal first argument is not a recorder call this check can see
    (``re.Match.span()`` takes none or an int) and is left alone."""
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        if not (
            (isinstance(f, ast.Attribute) and f.attr == "span")
            or (isinstance(f, ast.Name) and f.id == "span")
        ):
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            continue
        if not arg.value.startswith(SPAN_PREFIX) or arg.value not in spans:
            problems.append(
                f"{_rel(path)}:{node.lineno}: span name {arg.value!r} "
                f"must start with {SPAN_PREFIX!r} and be in the SPANS registry "
                "(utils/trace.py) — add it there (and to PERF.md section 3) "
                "or fix the typo; the trace's readers find spans by name"
            )
    return problems


def load_verb_registry(path: pathlib.Path):
    """Extract ``core/manager.py``'s verb registry.

    Returns ``(verbs, names)``: the ``CONTROL_VERBS`` frozenset literal
    plus a map of module-level verb constants (``NAME = "literal"``
    string assignments whose value is in the set) — ``{"HEARTBEAT":
    "heartbeat", "TELEMETRY": "telemetry", ...}``.  Same loud-failure
    stance as :func:`load_event_registry`: a moved or computed registry
    raises ``ValueError`` instead of letting every ``{"cmd": ...}`` site
    pass vacuously.
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    verbs = _parse_frozenset_literal(
        path, tree, "CONTROL_VERBS",
        "the CONTROL-verb registry moved; update MANAGER_MODULE",
    )
    names = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not (
            isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
            and node.value.value in verbs
        ):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                names[t.id] = node.value.value
    if not names:
        raise ValueError(
            f"{_rel(path)}: no verb constants found — CONTROL_VERBS exists "
            "but no NAME = \"<verb>\" module-level assignments match it"
        )
    return verbs, names


def _record_kind_arg(call: ast.Call):
    """Classify ``call`` as a flight-recorder record site.

    Returns ``(definitive, first_arg)`` for record-shaped calls, else None:

    - ``flightrec.record(...)`` — the canonical module form — is DEFINITIVE:
      a non-literal kind there is itself a violation;
    - ``<expr>.record(...)`` / bare ``record(...)`` / ``rec(...)`` are
      aliased forms, checked only when the first argument is a literal
      dotted string (so ``histogram.record(0.003)`` never false-positives).
    """
    f = call.func
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "record"
        and isinstance(f.value, ast.Name)
        and f.value.id == "flightrec"
    ):
        return True, (call.args[0] if call.args else None)
    shaped = (
        (isinstance(f, ast.Attribute) and f.attr == "record")
        or (isinstance(f, ast.Name) and f.id in _RECORD_ALIASES)
    )
    if shaped:
        return False, (call.args[0] if call.args else None)
    return None


def check_flightrec_calls(path: pathlib.Path, events: frozenset) -> List[str]:
    """Flag record calls whose kind is absent from the EVENTS registry."""
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        classified = _record_kind_arg(node)
        if classified is None:
            continue
        definitive, arg = classified
        literal = (
            arg.value
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str)
            else None
        )
        if literal is None:
            if definitive:
                problems.append(
                    f"{_rel(path)}:{node.lineno}: flightrec.record called "
                    "with a non-literal kind — kinds must be literal strings "
                    "from core/flightrec.py EVENTS so this check (and "
                    "tools/postmortem.py) can see them statically"
                )
            continue  # aliased .record with non-string arg: not a recorder
        if "." not in literal and not definitive:
            continue  # aliased form with an undotted string: unrelated API
        if literal not in events:
            problems.append(
                f"{_rel(path)}:{node.lineno}: record kind {literal!r} is not "
                "in the EVENTS registry (core/flightrec.py) — add it there "
                "or fix the typo; unknown kinds never reach postmortem / SLO "
                "tooling"
            )
    return problems


def check_push_ack_sync_free(
    path: pathlib.Path,
    funcs_registry: frozenset = SYNC_FREE_FUNCS,
    registry_name: str = "SYNC_FREE_FUNCS",
) -> List[str]:
    """Ban blocking device syncs inside the registered sync-free functions.

    Flags ``np.asarray`` / ``np.array`` / ``jax.device_get`` calls and any
    ``.block_until_ready()`` inside a ``funcs_registry`` function (the
    push-ack path by default; the ApplyLedger's submit side via
    :data:`LEDGER_SYNC_FREE_FUNCS`).  A registry entry with no matching
    function definition is ITSELF a violation — a rename must break this
    check loudly, never let the contract pass vacuously against code it no
    longer reads.
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    funcs = {}
    for node in ast.walk(tree):
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in funcs_registry
        ):
            funcs[node.name] = node
    missing = sorted(funcs_registry - set(funcs))
    if missing:
        problems.append(
            f"{_rel(path)}: sync-free functions missing: "
            f"{missing} — renamed?  Update {registry_name} in "
            "tools/check_wrappers.py so the contract keeps checking the "
            "real ack path"
        )
    for name, fn in sorted(funcs.items()):
        for call in _calls(fn):
            f = call.func
            label = None
            if isinstance(f, ast.Attribute):
                if f.attr == "block_until_ready":
                    label = ".block_until_ready()"
                elif isinstance(f.value, ast.Name):
                    if f.value.id == "np" and f.attr in _SYNC_BANNED_NP:
                        label = f"np.{f.attr}()"
                    elif f.value.id == "jax" and f.attr == "device_get":
                        label = "jax.device_get()"
            if label is not None:
                problems.append(
                    f"{_rel(path)}:{call.lineno}: {name} calls {label} — "
                    "the push-ack path is sync-free by contract (the ack "
                    "returns while the device apply is in flight); move "
                    "the readback off this path"
                )
    return problems


def check_copy_free(
    path: pathlib.Path,
    funcs_registry: frozenset,
    registry_name: str,
) -> List[str]:
    """Ban per-frame copies inside the registered fast-path functions.

    Flags ``.tobytes()`` calls, ``bytes(...)`` constructions, and
    ``ctypes.string_at`` (module-qualified or bare) inside a
    ``funcs_registry`` function.  A registry entry with no matching
    function definition is ITSELF a violation — a rename must break this
    check loudly, never let the contract pass vacuously against code it no
    longer reads.
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    funcs = {}
    for node in ast.walk(tree):
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in funcs_registry
        ):
            funcs[node.name] = node
    missing = sorted(funcs_registry - set(funcs))
    if missing:
        problems.append(
            f"{_rel(path)}: copy-free fast-path functions missing: "
            f"{missing} — renamed?  Update {registry_name} in "
            "tools/check_wrappers.py so the contract keeps checking the "
            "real hot path"
        )
    for name, fn in sorted(funcs.items()):
        for call in _calls(fn):
            f = call.func
            label = None
            if isinstance(f, ast.Attribute):
                if f.attr == "tobytes":
                    label = ".tobytes()"
                elif f.attr == "string_at":
                    label = "ctypes.string_at()"
            elif isinstance(f, ast.Name):
                if f.id == "bytes":
                    label = "bytes()"
                elif f.id == "string_at":
                    label = "string_at()"
            if label is not None:
                problems.append(
                    f"{_rel(path)}:{call.lineno}: {name} calls {label} — "
                    "the shm/recv fast path is copy-free by contract "
                    "(ISSUE 17: one slice-assign in, zero-copy views out); "
                    "decode over the borrowed buffer instead"
                )
    return problems


def _trace_record_kind(call: ast.Call):
    """Return the literal ``trace.*`` kind of a record-shaped ``call``.

    Matches every recorder spelling used in the package — module
    ``flightrec.record(...)``, method ``<expr>.record(...)`` and the
    ledger's injected ``<expr>._record(...)``, plus bare ``record`` /
    ``rec`` aliases — but only when the first argument is a literal
    string starting with ``"trace."`` (so ``histogram.record(0.003)``
    never false-positives).  Returns ``None`` otherwise.
    """
    f = call.func
    shaped = (
        (isinstance(f, ast.Attribute) and f.attr in ("record", "_record"))
        or (
            isinstance(f, ast.Name)
            and f.id in (_RECORD_ALIASES | {"_record"})
        )
    )
    if not shaped or not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        if arg.value.startswith("trace."):
            return arg.value
    return None


def check_trace_gated(
    path: pathlib.Path,
    funcs_registry: frozenset,
    registry_name: str = "TRACE_GATED_FUNCS",
) -> List[str]:
    """Require every ``trace.*`` record in a registered function to sit
    under an ``if`` — the sampling / trace-context-presence gate.

    The tracing plane's hot-path promise (ISSUE 18) is zero span
    allocation for unsampled traffic; an unconditional record here turns
    1/1024 sampling into per-message work.  Two loud-failure modes keep
    the check honest: a registry entry with no matching function
    definition (rename), and a registered function that records NO
    ``trace.*`` kind at all (the instrumentation was refactored away but
    the registry still claims it is checked).
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    funcs = {}
    for node in ast.walk(tree):
        if (
            isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node.name in funcs_registry
        ):
            funcs[node.name] = node
    missing = sorted(funcs_registry - set(funcs))
    if missing:
        problems.append(
            f"{_rel(path)}: trace-gated functions missing: {missing} — "
            f"renamed?  Update {registry_name} in tools/check_wrappers.py "
            "so the contract keeps checking the real hot path"
        )
    for name, fn in sorted(funcs.items()):
        parents = {}
        for parent in ast.walk(fn):
            for child in ast.iter_child_nodes(parent):
                parents[child] = parent
        recorded = 0
        for call in _calls(fn):
            kind = _trace_record_kind(call)
            if kind is None:
                continue
            recorded += 1
            node, gated = call, False
            while node is not fn:
                node = parents.get(node)
                if node is None:
                    break
                if isinstance(node, ast.If):
                    gated = True
                    break
            if not gated:
                problems.append(
                    f"{_rel(path)}:{call.lineno}: {name} records {kind!r} "
                    "unconditionally — hot-path trace spans must be gated "
                    "behind the sampling predicate (no per-message span "
                    "allocation when unsampled)"
                )
        if not recorded:
            problems.append(
                f"{_rel(path)}:{fn.lineno}: {name} records no trace.* "
                "events — instrumentation refactored away?  Update "
                f"{registry_name} or restore the span record"
            )
    return problems


def check_control_verbs(
    path: pathlib.Path, verbs: frozenset, names: dict
) -> List[str]:
    """Flag ``{"cmd": ...}`` dict literals naming an unregistered verb.

    A value passes when it is a literal string in ``CONTROL_VERBS``, a
    bare ``Name`` (or dotted ``Attribute`` tail) matching one of the verb
    constants, and fails otherwise — unknown literal, unknown name, or a
    computed expression the AST cannot vouch for.  Dynamic routing code
    that reads ``payload.get("cmd")`` is untouched: only dict DISPLAYS
    with a literal ``"cmd"`` key are payload-construction sites.
    """
    tree = ast.parse(path.read_text(), filename=str(path))
    problems: List[str] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        for key, value in zip(node.keys, node.values):
            if not (
                isinstance(key, ast.Constant) and key.value == "cmd"
            ):
                continue
            if isinstance(value, ast.Constant) and isinstance(value.value, str):
                if value.value in verbs:
                    continue
                problems.append(
                    f"{_rel(path)}:{value.lineno}: cmd literal "
                    f"{value.value!r} is not in CONTROL_VERBS "
                    "(core/manager.py) — Manager.handle_request would "
                    "silently ack it as a no-op; add the verb to the "
                    "registry or fix the typo"
                )
                continue
            const = None
            if isinstance(value, ast.Name):
                const = value.id
            elif isinstance(value, ast.Attribute):
                const = value.attr  # manager.TELEMETRY style
            if const is not None and const in names:
                continue
            problems.append(
                f"{_rel(path)}:{value.lineno}: cmd payload value is not a "
                "registered verb constant or CONTROL_VERBS literal — verbs "
                "must be statically checkable (core/manager.py registry)"
            )
    return problems


def main(argv: List[str]) -> int:
    roots = [pathlib.Path(a) for a in argv[1:]] or [PKG]
    problems: List[str] = []
    found_wrapper = False
    found_hot_path = 0
    found_server = False
    found_ledger = False
    found_shm_ring = False
    found_tcp_van = False
    found_trace_gated = 0
    try:
        events = load_event_registry(PKG / FLIGHTREC_MODULE)
    except (OSError, ValueError) as e:
        print(f"check_wrappers: event registry unreadable: {e}", file=sys.stderr)
        return 1  # a moved/emptied registry must fail loudly, not pass
    absent = sorted(REQUIRED_EVENTS - events)
    if absent:
        print(
            f"check_wrappers: required event kinds missing from EVENTS: "
            f"{absent} — the device-plane apply taxonomy (ISSUE 12) must "
            "stay registered",
            file=sys.stderr,
        )
        return 1
    try:
        spans = load_span_registry(PKG / TRACE_MODULE)
    except (OSError, ValueError) as e:
        print(f"check_wrappers: span registry unreadable: {e}", file=sys.stderr)
        return 1  # same loud-failure stance as the event registry
    try:
        verbs, verb_names = load_verb_registry(PKG / MANAGER_MODULE)
    except (OSError, ValueError) as e:
        print(f"check_wrappers: verb registry unreadable: {e}", file=sys.stderr)
        return 1  # same loud-failure stance as the event registry
    for root in roots:
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for f in files:
            try:
                rel = str(f.resolve().relative_to(PKG)).replace("\\", "/")
            except ValueError:
                rel = None
            if rel in NO_PICKLE_MODULES:
                found_hot_path += 1
                problems.extend(check_no_pickle(f))
            if rel == SERVER_MODULE:
                found_server = True
                problems.extend(check_push_ack_sync_free(f))
            if rel == LEDGER_MODULE:
                found_ledger = True
                problems.extend(
                    check_push_ack_sync_free(
                        f, LEDGER_SYNC_FREE_FUNCS, "LEDGER_SYNC_FREE_FUNCS"
                    )
                )
            if rel == SHM_RING_MODULE:
                found_shm_ring = True
                problems.extend(
                    check_copy_free(f, SHM_COPY_FREE_FUNCS, "SHM_COPY_FREE_FUNCS")
                )
            if rel == "core/tcp_van.py":
                found_tcp_van = True
                problems.extend(
                    check_copy_free(f, VAN_COPY_FREE_FUNCS, "VAN_COPY_FREE_FUNCS")
                )
            if rel in TRACE_GATED_FUNCS:
                found_trace_gated += 1
                problems.extend(check_trace_gated(f, TRACE_GATED_FUNCS[rel]))
            problems.extend(check_flightrec_calls(f, events))
            problems.extend(check_span_names(f, spans))
            problems.extend(check_control_verbs(f, verbs, verb_names))
            text = f.read_text()
            if "VanWrapper" not in text:
                continue
            found_wrapper = True
            problems.extend(check_file(f))
    if not found_wrapper:
        print("check_wrappers: no VanWrapper subclasses found", file=sys.stderr)
        return 1  # a rename must fail loudly, not pass vacuously
    if roots == [PKG] and not found_server:
        # the sync-free push-ack contract must not pass vacuously if the
        # server module moves
        print(
            "check_wrappers: kv/server.py not found — update SERVER_MODULE",
            file=sys.stderr,
        )
        return 1
    if roots == [PKG] and not found_ledger:
        # same vacuous-pass guard for the ledger's sync-free submit side
        print(
            "check_wrappers: kv/ledger.py not found — update LEDGER_MODULE",
            file=sys.stderr,
        )
        return 1
    if roots == [PKG] and not (found_shm_ring and found_tcp_van):
        # the copy-free fast-path contract must not pass vacuously if
        # either transport module moves
        print(
            "check_wrappers: shm/tcp transport module not found — update "
            "SHM_RING_MODULE / the core/tcp_van.py hook",
            file=sys.stderr,
        )
        return 1
    if roots == [PKG] and found_trace_gated != len(TRACE_GATED_FUNCS):
        # the sampled-tracing gate contract must not pass vacuously if a
        # traced hot-path module moves
        print(
            "check_wrappers: only "
            f"{found_trace_gated}/{len(TRACE_GATED_FUNCS)} trace-gated "
            "modules found — update TRACE_GATED_FUNCS",
            file=sys.stderr,
        )
        return 1
    if roots == [PKG] and found_hot_path != len(NO_PICKLE_MODULES):
        # same loud-failure stance: a moved/renamed hot-path module must not
        # let the pickle ban pass vacuously
        print(
            "check_wrappers: only "
            f"{found_hot_path}/{len(NO_PICKLE_MODULES)} no-pickle hot-path "
            "modules found — update NO_PICKLE_MODULES",
            file=sys.stderr,
        )
        return 1
    for p in problems:
        print(p)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
