#!/usr/bin/env python3
"""Writes ``program.xplane.pb`` beside itself: 10 ms of a two-chip, one-worker,
two-server trace in the shape ``program_spans.py`` found on a TPU v5e (scopes
in the ``tf_op`` statistic of the operations' event METADATA, programs on the
``XLA Modules`` line), whose account can be worked out by hand
(``test_program_spans.py`` holds the answers).  Times below are milliseconds
from the window's start."""

import os

from jax.profiler import ProfileData

PS_PER_MS = 1_000_000_000

#: host threads: (span name, start ms, end ms, {attribute: value})
WORKER = [
    ("bench.pull", 0, 4.4, {}),
    ("ps.worker.pull", 0, 4, {"table": "w", "keys": 10, "cpu_us": 2000}),
    ("ps.worker.localize", 0, 1, {"keys": 10, "unique": 5, "cpu_us": 900}),
    ("ps.worker.submit", 1, 1.5, {"req": "W0/kv/7", "legs": 2, "cpu_us": 500}),
    ("ps.worker.wait", 1.5, 3.5, {"req": "W0/kv/7", "legs": 2, "retry": 0, "cpu_us": 0}),
    ("ps.worker.assemble", 3.5, 3.9, {"legs": 2, "rows": 8, "cpu_us": 400}),
    ("bench.grad", 4.4, 5.5, {}),
    ("bench.push", 5.5, 9.6, {}),
    ("ps.worker.push", 5.5, 9.5, {"table": "w", "keys": 10, "cpu_us": 500}),
    ("ps.worker.submit", 5.6, 6.0, {"req": "W0/kv/8", "legs": 1, "cpu_us": 400}),
    ("ps.worker.wait", 6.0, 9.0, {"req": "W0/kv/8", "legs": 1, "retry": 0, "cpu_us": 0}),
]
WORKER_RECV = [
    ("ps.van.deliver", 3.4, 3.45, {"req": "W0/kv/7", "verb": "PULL", "sender": "S0",
                                  "is_request": 0, "wait_us": 10}),
]
SERVER_0 = [
    ("ps.van.deliver", 1.6, 3.4, {"req": "W0/kv/7", "verb": "PULL", "sender": "W0",
                                  "is_request": 1, "wait_us": 100}),
    ("ps.server.pull", 1.7, 3.3, {"req": "W0/kv/7", "rows": 3, "bucket": 4}),
    ("ps.server.h2d", 1.8, 1.9, {"bytes": 16}),
    ("ps.server.dispatch", 1.9, 2.0, {"op": "pull"}),
    ("ps.server.d2h", 2.0, 3.2, {"bytes": 64}),
    ("ps.van.deliver", 6.1, 7.2, {"req": "W0/kv/8", "verb": "PUSH", "sender": "W0",
                                  "is_request": 1, "wait_us": 300}),
    ("ps.server.push", 6.2, 7.1, {"req": "W0/kv/8", "rows": 5, "bucket": 8, "members": 1}),
    ("ps.server.h2d", 6.3, 7.05, {"bytes": 160}),
    ("ps.server.dispatch", 7.06, 7.09, {"op": "push"}),
    ("ps.van.deliver", 9.0, 9.2, {"req": "W0/kv/9", "verb": "CONTROL", "sender": "W0",
                                  "is_request": 1, "wait_us": 20}),
]
SERVER_1 = [
    # a request submitted before the profiler started: no submit to join
    ("ps.van.deliver", 0.1, 0.3, {"req": "W0/kv/3", "verb": "PUSH", "sender": "W0",
                                  "is_request": 1, "wait_us": 50}),
    ("ps.server.push", 0.15, 0.25, {"req": "W0/kv/3", "rows": 1, "bucket": 4, "members": 1}),
    ("ps.van.deliver", 1.6, 2.6, {"req": "W0/kv/7", "verb": "PULL", "sender": "W0",
                                  "is_request": 1, "wait_us": 200}),
    ("ps.server.pull", 1.7, 2.5, {"req": "W0/kv/7", "rows": 2, "bucket": 4}),
    ("ps.server.dispatch", 1.8, 1.9, {"op": "pull"}),
    ("ps.server.d2h", 2.0, 2.4, {"bytes": 32}),
]
TRACER = [("bench.traced_window", 0, 10, {})]
HOST = [("bench-worker-0", WORKER), ("W0-recv", WORKER_RECV),
        ("S0-recv", SERVER_0), ("S1-recv", SERVER_1), ("bench-tracer", TRACER)]

PUSH, PULL, ADD = 11, 22, 33  # program ids
FUSED = "jit(_push_impl)/ps.table.apply/ps.apply.fused/scatter:"
#: a chip's operations: (HLO text, start, end, program, tf_op, source)
CHIP_0 = [
    ("%while.1 = f32[9] while(f32[9] %p)", 0, 3, PUSH, "", ""),  # the compiler's
    ("%fusion.2 = f32[9] fusion(f32[9] %a)", 1, 2, PUSH, FUSED, "scatter.py:86"),
    ("%copy.9 = f32[9] copy(f32[9] %b)", 2, 2.5, PUSH, "", ""),  # in the while
    ("%dus.3 = f32[9] fusion(f32[9] %c)", 3, 4, PUSH,
     "jit(_push_impl)/ps.table.apply/ps.apply.trash_reset/scatter:", "table.py:171"),
    ("%fusion.7 = f32[4] fusion(f32[9] %v)", 5, 7, PULL,
     "jit(_pull_impl)/ps.table.pull/ps.gather/jit(_take)/gather:", "scatter.py:78"),
    ("%add.1 = f32[4] add(f32[4] %x, f32[4] %y)", 8, 8.05, ADD, "jit(add)/add:", "linear.py:36"),
]
CHIP_1 = [("%fusion.2 = f32[9] fusion(f32[9] %a)", 2, 4, PUSH, FUSED, "scatter.py:86")]
MODULES = {
    0: [("jit__push_impl(11)", 0, 4), ("jit__pull_impl(22)", 5, 7), ("jit_add(33)", 8, 8.05)],
    1: [("jit__push_impl(11)", 2, 4)],
}
DEVICE_STATS = {1: "tf_op", 2: "source", 3: "program_id"}


def _events(items, ids):
    return "".join(
        "    events { metadata_id: %d offset_ps: %d duration_ps: %d%s }\n"
        % (ids[name], round(a * PS_PER_MS), round((b - a) * PS_PER_MS), stats)
        for name, a, b, stats in items
    )


def _host_plane():
    stat_ids, ev_ids, lines = {}, {}, []
    for i, (thread, spans) in enumerate(HOST):
        items = []
        for name, a, b, attrs in spans:
            ev_ids.setdefault(name, len(ev_ids) + 1)
            stats = ""
            for k, v in attrs.items():
                sid = stat_ids.setdefault(k, len(stat_ids) + 1)
                value = 'str_value: "%s"' % v if isinstance(v, str) else "int64_value: %d" % v
                stats += " stats { metadata_id: %d %s }" % (sid, value)
            items.append((name, a, b, stats))
        lines.append('  lines { id: %d name: "%s" timestamp_ns: 1000000\n%s  }\n'
                     % (i + 1, thread, _events(items, ev_ids)))
    meta = "".join('  event_metadata { key: %d value { id: %d name: "%s" } }\n' % (i, i, n)
                   for n, i in ev_ids.items())
    meta += "".join('  stat_metadata { key: %d value { id: %d name: "%s" } }\n' % (i, i, n)
                    for n, i in stat_ids.items())
    return 'planes {\n  id: 9 name: "/host:CPU"\n%s%s}\n' % ("".join(lines), meta)


def _device_plane(chip, ops):
    ev_ids, meta = {}, ""
    for name, _a, _b, program, tf_op, source in ops:
        i = ev_ids.setdefault(name, len(ev_ids) + 1)
        stats = " stats { metadata_id: 3 uint64_value: %d }" % program
        if tf_op:
            stats += ' stats { metadata_id: 1 str_value: "%s" }' % tf_op
        if source:
            stats += ' stats { metadata_id: 2 str_value: "%s" }' % source
        if tf_op or source:  # the compiler's own operations carry no metadata
            meta += '  event_metadata { key: %d value { id: %d name: "%s"%s } }\n' % (
                i, i, name, stats)
        else:
            meta += '  event_metadata { key: %d value { id: %d name: "%s" } }\n' % (i, i, name)
    mod_ids = {name: 100 + j for j, (name, _a, _b) in enumerate(MODULES[chip])}
    meta += "".join('  event_metadata { key: %d value { id: %d name: "%s" } }\n' % (i, i, n)
                    for n, i in mod_ids.items())
    meta += "".join('  stat_metadata { key: %d value { id: %d name: "%s" } }\n' % (i, i, n)
                    for i, n in DEVICE_STATS.items())
    return (
        'planes {\n  id: %d name: "/device:TPU:%d"\n'
        '  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000000\n%s  }\n'
        '  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000000\n%s  }\n%s}\n'
    ) % (
        chip + 1, chip,
        _events([(n, a, b, "") for n, a, b in MODULES[chip]], mod_ids),
        _events([(n, a, b, "") for n, a, b, *_ in ops], ev_ids), meta,
    )


TEXT = _device_plane(0, CHIP_0) + _device_plane(1, CHIP_1) + _host_plane()


def write(path, text=TEXT):
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "program.xplane.pb")
    write(path)
    print(path, os.path.getsize(path))
