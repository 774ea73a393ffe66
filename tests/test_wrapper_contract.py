"""tools/check_wrappers.py wired as a tier-1 test (ISSUE 6 satellite).

The Van wrapper flush/close-delegation and counters-no-recursion contracts
were convention until PR 6; this keeps them enforced on every run.
"""

from __future__ import annotations

import pathlib
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import check_wrappers  # noqa: E402


def test_repo_wrappers_clean():
    problems = []
    for f in sorted((REPO / "parameter_server_tpu").rglob("*.py")):
        if "VanWrapper" in f.read_text():
            problems.extend(check_wrappers.check_file(f))
    assert problems == [], "\n".join(problems)


def test_catches_non_delegating_flush(tmp_path):
    bad = tmp_path / "bad_van.py"
    bad.write_text(
        textwrap.dedent(
            """
            class SwallowingVan(VanWrapper):
                def flush(self, timeout=5.0):
                    return True  # drains nothing below

                def close(self):
                    self.inner.close()
            """
        )
    )
    problems = check_wrappers.check_file(bad)
    assert len(problems) == 1
    assert "SwallowingVan.flush" in problems[0]


def test_catches_counters_recursion(tmp_path):
    bad = tmp_path / "bad_counters.py"
    bad.write_text(
        textwrap.dedent(
            """
            class DoubleCountVan(VanWrapper):
                def counters(self):
                    return {**self.inner.counters(), "mine": 1}
            """
        )
    )
    problems = check_wrappers.check_file(bad)
    assert len(problems) == 1
    assert "DoubleCountVan.counters" in problems[0]


def test_frame_hot_path_is_pickle_free():
    """The flat-frame hot path (codec, transport, resender, coalescer) must
    never re-import pickle — the serialize tax ISSUE 7 removed."""
    problems = []
    for rel in check_wrappers.NO_PICKLE_MODULES:
        path = REPO / "parameter_server_tpu" / rel
        assert path.is_file(), f"hot-path module moved: {rel}"
        problems.extend(check_wrappers.check_no_pickle(path))
    assert problems == [], "\n".join(problems)


def test_catches_pickle_import_on_hot_path(tmp_path):
    bad = tmp_path / "bad_codec.py"
    bad.write_text(
        textwrap.dedent(
            """
            import pickle
            from pickle import dumps

            def encode(msg):
                return pickle.dumps(msg)
            """
        )
    )
    problems = check_wrappers.check_no_pickle(bad)
    assert len(problems) == 2
    assert "pickle" in problems[0]


def test_no_pickle_allows_clean_module(tmp_path):
    ok = tmp_path / "ok_codec.py"
    ok.write_text("import struct\nimport zlib\n")
    assert check_wrappers.check_no_pickle(ok) == []


def test_main_fails_loudly_if_hot_path_module_missing(tmp_path, monkeypatch):
    """NO_PICKLE_MODULES entries must exist when scanning the real package;
    a rename must fail the check, not silently skip the ban."""
    monkeypatch.setattr(
        check_wrappers, "NO_PICKLE_MODULES",
        check_wrappers.NO_PICKLE_MODULES + ("core/renamed_codec.py",),
    )
    assert check_wrappers.main(["check_wrappers"]) == 1


def test_event_registry_loads_and_repo_record_sites_clean():
    """Every flightrec.record / rec(...) call site in the package uses a
    literal kind from the EVENTS registry (ISSUE 8 satellite)."""
    events = check_wrappers.load_event_registry(
        REPO / "parameter_server_tpu" / check_wrappers.FLIGHTREC_MODULE
    )
    assert "frame.send" in events and "slo.breach" in events
    problems = []
    for f in sorted((REPO / "parameter_server_tpu").rglob("*.py")):
        problems.extend(check_wrappers.check_flightrec_calls(f, events))
    assert problems == [], "\n".join(problems)


def test_catches_unregistered_kind(tmp_path):
    bad = tmp_path / "bad_kind.py"
    bad.write_text(
        textwrap.dedent(
            """
            from parameter_server_tpu.core import flightrec

            def fence(node):
                flightrec.record("fence.incarnaton", node=node)  # typo
            """
        )
    )
    events = frozenset({"fence.incarnation"})
    problems = check_wrappers.check_flightrec_calls(bad, events)
    assert len(problems) == 1
    assert "fence.incarnaton" in problems[0]


def test_catches_unregistered_kind_via_alias_and_method(tmp_path):
    bad = tmp_path / "bad_alias.py"
    bad.write_text(
        textwrap.dedent(
            """
            def sweep(recorder, rec):
                rec("slo.braech", node="W0")              # aliased callable
                recorder.record("frame.rejct", node="S0")  # method form
            """
        )
    )
    events = frozenset({"slo.breach", "frame.reject"})
    problems = check_wrappers.check_flightrec_calls(bad, events)
    assert len(problems) == 2
    assert "slo.braech" in problems[0]
    assert "frame.rejct" in problems[1]


def test_catches_non_literal_kind_on_canonical_form(tmp_path):
    bad = tmp_path / "bad_dynamic.py"
    bad.write_text(
        textwrap.dedent(
            """
            from parameter_server_tpu.core import flightrec

            def log(kind):
                flightrec.record(kind, node="S0")  # dynamic — unverifiable
            """
        )
    )
    problems = check_wrappers.check_flightrec_calls(bad, frozenset({"x.y"}))
    assert len(problems) == 1
    assert "non-literal" in problems[0]


def test_record_shaped_non_recorder_calls_not_flagged(tmp_path):
    ok = tmp_path / "ok_hist.py"
    ok.write_text(
        textwrap.dedent(
            """
            def measure(hist, lat):
                hist.record(lat)           # histogram sample, not an event
                hist.record(0.003)
                db.record("row")           # undotted string: unrelated API
            """
        )
    )
    assert check_wrappers.check_flightrec_calls(ok, frozenset({"x.y"})) == []


def test_registry_load_fails_loudly(tmp_path):
    """A moved or computed EVENTS literal must raise, never yield an empty
    registry that passes every call site vacuously."""
    import pytest

    missing = tmp_path / "no_registry.py"
    missing.write_text("OTHER = frozenset({'a.b'})\n")
    with pytest.raises(ValueError, match="EVENTS"):
        check_wrappers.load_event_registry(missing)

    computed = tmp_path / "computed.py"
    computed.write_text("EVENTS = frozenset(sorted({'a.b'}))\n")
    with pytest.raises(ValueError, match="literal"):
        check_wrappers.load_event_registry(computed)

    empty = tmp_path / "empty.py"
    empty.write_text("EVENTS = frozenset(set())\n")
    with pytest.raises(ValueError):
        check_wrappers.load_event_registry(empty)


def test_verb_registry_loads_and_repo_cmd_sites_clean():
    """Every ``{"cmd": ...}`` payload literal in the package names a verb
    from the CONTROL_VERBS registry (ISSUE 10 satellite), and the new
    telemetry event kinds are registered."""
    verbs, names = check_wrappers.load_verb_registry(
        REPO / "parameter_server_tpu" / check_wrappers.MANAGER_MODULE
    )
    assert "telemetry" in verbs and "heartbeat" in verbs
    assert names.get("TELEMETRY") == "telemetry"
    events = check_wrappers.load_event_registry(
        REPO / "parameter_server_tpu" / check_wrappers.FLIGHTREC_MODULE
    )
    assert "telemetry.publish" in events and "telemetry.drop" in events
    problems = []
    for f in sorted((REPO / "parameter_server_tpu").rglob("*.py")):
        problems.extend(check_wrappers.check_control_verbs(f, verbs, names))
    assert problems == [], "\n".join(problems)


def test_catches_unknown_cmd_literal_and_computed_value(tmp_path):
    bad = tmp_path / "bad_cmd.py"
    bad.write_text(
        textwrap.dedent(
            """
            def send(mgr, verb):
                mgr.submit({"cmd": "telemtry"})       # typo literal
                mgr.submit({"cmd": verb})             # unknown name
                mgr.submit({"cmd": "heartbeat"})      # fine: registered
                mgr.submit({"cmd": HEARTBEAT})        # fine: verb constant
                mgr.submit({"cmd": manager.TELEMETRY})  # fine: dotted form
            """
        )
    )
    verbs = frozenset({"heartbeat", "telemetry"})
    names = {"HEARTBEAT": "heartbeat", "TELEMETRY": "telemetry"}
    problems = check_wrappers.check_control_verbs(bad, verbs, names)
    assert len(problems) == 2
    assert "telemtry" in problems[0]
    assert "not a" in problems[1]


def test_verb_registry_load_fails_loudly(tmp_path):
    """Same stance as the event registry: a moved/computed CONTROL_VERBS
    literal (or a registry with no matching verb constants) raises."""
    import pytest

    missing = tmp_path / "no_verbs.py"
    missing.write_text("OTHER = frozenset({'ping'})\n")
    with pytest.raises(ValueError, match="CONTROL_VERBS"):
        check_wrappers.load_verb_registry(missing)

    computed = tmp_path / "computed_verbs.py"
    computed.write_text("CONTROL_VERBS = frozenset(sorted({'ping'}))\n")
    with pytest.raises(ValueError, match="literal"):
        check_wrappers.load_verb_registry(computed)

    unnamed = tmp_path / "unnamed_verbs.py"
    unnamed.write_text("CONTROL_VERBS = frozenset({'ping'})\n")
    with pytest.raises(ValueError, match="constants"):
        check_wrappers.load_verb_registry(unnamed)


def test_push_ack_path_is_sync_free():
    """The registered push-ack functions in kv/server.py contain no
    blocking device syncs (ISSUE 11 satellite): acks return while the
    donated device apply is still in flight."""
    path = REPO / "parameter_server_tpu" / check_wrappers.SERVER_MODULE
    assert path.is_file(), "server module moved: update SERVER_MODULE"
    problems = check_wrappers.check_push_ack_sync_free(path)
    assert problems == [], "\n".join(problems)


def test_catches_sync_in_ack_path(tmp_path):
    bad = tmp_path / "bad_server.py"
    bad.write_text(
        textwrap.dedent(
            """
            class KVServer:
                def _ack_push(self, msg, tname, kn, segs):
                    rows = np.asarray(self._last)      # D2H sync
                    self._last.block_until_ready()     # explicit sync
                    return msg.reply()

                def _apply_push_group(self, group, replies):
                    snap = jax.device_get(self._v)     # D2H sync
                    return snap

                def _push_group_rounds(self, *a):
                    pass

                def _push_group_combined(self, *a):
                    pass
            """
        )
    )
    problems = check_wrappers.check_push_ack_sync_free(bad)
    assert len(problems) == 3
    assert "np.asarray" in problems[0]
    assert "block_until_ready" in problems[1]
    assert "jax.device_get" in problems[2]


def test_sync_free_registry_fails_loudly_on_rename(tmp_path):
    """A renamed registered function must FAIL the check — the contract
    never passes vacuously against code it no longer reads."""
    bad = tmp_path / "renamed_server.py"
    bad.write_text(
        textwrap.dedent(
            """
            class KVServer:
                def _ack_push_v2(self, msg):
                    return msg.reply()
            """
        )
    )
    problems = check_wrappers.check_push_ack_sync_free(bad)
    assert len(problems) == 1
    assert "missing" in problems[0]
    assert "SYNC_FREE_FUNCS" in problems[0]


def test_sync_free_allows_host_side_bookkeeping(tmp_path):
    ok = tmp_path / "ok_server.py"
    ok.write_text(
        textwrap.dedent(
            """
            class KVServer:
                def _ack_push(self, msg, tname, kn, segs):
                    ver = self._seg_versions[tname]
                    if segs.size:
                        ver[segs] += 1
                    hit = kn[(kn >= 0) & (kn < 10)]
                    return msg.reply()

                def _apply_push_group(self, group, replies):
                    ids = np.concatenate([g[3] for g in group])
                    stack = jnp.stack([g[1] for g in group])  # H2D is fine
                    return ids, stack

                def _push_group_rounds(self, *a):
                    order = np.argsort(a[0], kind="stable")
                    return order

                def _push_group_combined(self, *a):
                    u, inv = np.unique(a[0], return_inverse=True)
                    return u, inv
            """
        )
    )
    assert check_wrappers.check_push_ack_sync_free(ok) == []


def test_accepts_super_delegation(tmp_path):
    ok = tmp_path / "ok_van.py"
    ok.write_text(
        textwrap.dedent(
            """
            class PoliteVan(VanWrapper):
                def flush(self, timeout=5.0):
                    self._drain_mine(timeout)
                    return super().flush(timeout)

                def close(self):
                    self._thread.join()
                    self.inner.close()

                def counters(self):
                    return {"mine": 1}
            """
        )
    )
    assert check_wrappers.check_file(ok) == []


def test_ledger_submit_path_is_sync_free():
    """The ApplyLedger's ack-path methods (ISSUE 12) obey the same AST
    ban as the push-ack functions they run inside: registration is host
    bookkeeping only, never a device sync."""
    path = REPO / "parameter_server_tpu" / check_wrappers.LEDGER_MODULE
    assert path.is_file(), "ledger module moved: update LEDGER_MODULE"
    problems = check_wrappers.check_push_ack_sync_free(
        path,
        check_wrappers.LEDGER_SYNC_FREE_FUNCS,
        "LEDGER_SYNC_FREE_FUNCS",
    )
    assert problems == [], "\n".join(problems)


def test_catches_sync_in_ledger_submit_path(tmp_path):
    bad = tmp_path / "bad_ledger.py"
    bad.write_text(
        textwrap.dedent(
            """
            class ApplyLedger:
                def begin(self, table, members, rows):
                    return object()

                def mark_host(self):
                    pass

                def mark_h2d(self):
                    pass

                def submit(self, tok, ref, fallback):
                    ref.block_until_ready()        # device sync on submit
                    self._q.append(tok)

                def overloaded(self):
                    return bool(np.asarray(self._gauge))  # D2H sync
            """
        )
    )
    problems = check_wrappers.check_push_ack_sync_free(
        bad,
        check_wrappers.LEDGER_SYNC_FREE_FUNCS,
        "LEDGER_SYNC_FREE_FUNCS",
    )
    assert len(problems) == 2
    joined = "\n".join(problems)
    assert "block_until_ready" in joined
    assert "np.asarray" in joined


def test_ledger_registry_fails_loudly_on_rename(tmp_path):
    bad = tmp_path / "renamed_ledger.py"
    bad.write_text(
        textwrap.dedent(
            """
            class ApplyLedger:
                def begin(self, table, members, rows):
                    return object()
            """
        )
    )
    problems = check_wrappers.check_push_ack_sync_free(
        bad,
        check_wrappers.LEDGER_SYNC_FREE_FUNCS,
        "LEDGER_SYNC_FREE_FUNCS",
    )
    assert len(problems) == 1
    assert "missing" in problems[0]
    assert "LEDGER_SYNC_FREE_FUNCS" in problems[0]


def test_apply_event_taxonomy_stays_registered():
    """main() loud-fails if the ``apply.*`` kinds are dropped from the
    flightrec EVENTS registry; the positive half here pins that the live
    registry still carries every required kind."""
    from parameter_server_tpu.core import flightrec

    missing = check_wrappers.REQUIRED_EVENTS - flightrec.EVENTS
    assert not missing, f"EVENTS lost required apply kinds: {sorted(missing)}"
    assert check_wrappers.main([]) == 0  # the repo itself stays clean


def test_span_registry_loads_and_repo_span_sites_clean():
    """Every literal ``span(...)`` name in the package starts with ``ps.``
    and is in the SPANS registry (ISSUE 25 satellite), and the registry the
    checker parses is the one the module exports."""
    from parameter_server_tpu.utils import trace

    spans = check_wrappers.load_span_registry(
        REPO / "parameter_server_tpu" / check_wrappers.TRACE_MODULE
    )
    assert spans == trace.SPANS
    assert all(s.startswith(check_wrappers.SPAN_PREFIX) for s in spans)
    problems = []
    for f in sorted((REPO / "parameter_server_tpu").rglob("*.py")):
        problems.extend(check_wrappers.check_span_names(f, spans))
    assert problems == [], "\n".join(problems)


@pytest.mark.parametrize(
    "call,bad",
    [
        ('self.tracer.span("ps.worker.pul", table=t)', True),  # a typo
        ('self.tracer.span("kv.push")', True),  # no ps. prefix
        ('span("ps.van.sent")', True),  # the module-level form
        ('self.tracer.span("ps.worker.pull", table=t)', False),
        ('span("ps.van.send", verb=v)', False),
        ("m.span()", False),  # re.Match.span: no name to check
        ("m.span(1)", False),
    ],
)
def test_span_name_rule(tmp_path, call, bad):
    src = tmp_path / "spans.py"
    src.write_text(f"def f(self, span, m, t, v):\n    return {call}\n")
    spans = frozenset({"ps.worker.pull", "ps.van.send"})
    problems = check_wrappers.check_span_names(src, spans)
    assert len(problems) == (1 if bad else 0)
    if bad:
        assert "SPANS" in problems[0]


def test_span_registry_load_fails_loudly(tmp_path):
    computed = tmp_path / "trace.py"
    computed.write_text('SPANS = frozenset("ps." + n for n in ("a", "b"))\n')
    with pytest.raises(ValueError):
        check_wrappers.load_span_registry(computed)
    missing = tmp_path / "moved.py"
    missing.write_text("X = 1\n")
    with pytest.raises(ValueError):
        check_wrappers.load_span_registry(missing)
