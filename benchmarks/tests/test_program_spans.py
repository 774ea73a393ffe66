"""``harness/program_spans.py`` on ``data/program.xplane.pb`` (its text is in
``data/make_program_fixture.py``), and the twelve per-layer metrics that read
it.  By hand, in milliseconds from the window's start (window 10, two chips):

- worker thread: ``ps.worker.pull`` [0, 4] holds localize [0, 1], submit
  [1, 1.5] (``W0/kv/7``, 2 legs), wait [1.5, 3.5], assemble [3.5, 3.9]: self
  0.1.  ``ps.worker.push`` [5.5, 9.5] holds submit [5.6, 6] (``W0/kv/8``, 1
  leg) and wait [6, 9]: self 0.6.
- server 0's recv thread delivers the pull [1.6, 3.4], the push [6.1, 7.2]
  and a CONTROL [9, 9.2]: busy 3.1 of 10.  Server 1's delivers a push
  submitted before the trace [0.1, 0.3] and the pull [1.6, 2.6]: 1.2 of 10.
- chip 0: a compiler-made ``while`` [0, 3] with a fused scatter [1, 2] and a
  bare copy [2, 2.5] in it, the trash reset [3, 4], the gather [5, 7], an
  eager add [8, 8.05]; chip 1: the fused scatter [2, 4].  Busy 6.05 + 2.
- idle gaps: chip 0 [4, 5] (the benchmark's grad), [7, 8] (push waits, no
  server holds it), [8.05, 10] (in push, after its wait); chip 1 [0, 2]
  (midpoint 1: the submit), [4, 10] (midpoint 7: push waits, server 0 in its
  H2D).
"""

import importlib.util
import os
import types

import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import program_spans as ps
from benchmarks.harness.bytes_model import apply_bytes, pull_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
PB = os.path.join(HERE, "data", "program.xplane.pb")
MS = 1e-3
NEW_METRICS = [
    "worker_localize_ms_p50", "worker_wait_ms_p50", "server_queue_wait_ms_p50",
    "recv_thread_busy_pct", "server_pull_busy_ms_p50", "server_push_busy_ms_p50",
    "server_d2h_ms_p50", "gather_kernel_ms", "apply_kernel_ms",
    "gather_kernel_roofline", "apply_kernel_roofline", "scoped_device_pct",
]


@pytest.fixture(scope="module")
def acc():
    return ps.load(PB)


def test_the_recorded_file_is_what_the_generator_writes(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "make_program_fixture", os.path.join(HERE, "data", "make_program_fixture.py")
    )
    fixture = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fixture)
    again = str(tmp_path / "again.xplane.pb")
    fixture.write(again)
    # the bytes differ (a protobuf map has no order): the accounts do not
    assert ps.render(ps.load(again)).split("\n")[1:] == ps.render(ps.load(PB)).split("\n")[1:]


def test_spans_nest_and_self_time_is_less_the_children(acc):
    assert acc.window_s == pytest.approx(10 * MS) and acc.chips == 2
    (pull,), (push,) = acc.by_name["ps.worker.pull"], acc.by_name["ps.worker.push"]
    assert pull.parent is None and pull.self_s == pytest.approx(0.1 * MS)
    assert push.self_s == pytest.approx(0.6 * MS)
    (loc,) = acc.by_name["ps.worker.localize"]
    assert loc.parent is pull and loc.attrs == {"keys": 10, "unique": 5, "cpu_us": 900}
    d2h = sorted(acc.by_name["ps.server.d2h"], key=lambda s: -s.dur)[0]
    assert d2h.parent.name == "ps.server.pull"
    assert d2h.parent.parent.name == "ps.van.deliver"
    assert d2h.parent.self_s == pytest.approx((1.6 - 0.1 - 0.1 - 1.2) * MS)
    # the benchmark's own spans are no part of the account
    assert all(sp.name.startswith("ps.") for sp in acc.spans)
    assert acc.bench_ms["bench.pull"] == pytest.approx([4.4])


def test_server_spans_join_a_root_by_req(acc):
    by_req = {}
    for kind in ("pull", "push"):
        for sp in acc.by_name[f"ps.server.{kind}"]:
            by_req.setdefault(sp.attrs["req"], []).append(acc.root_of(sp))
    assert [r.name for r in by_req["W0/kv/7"]] == ["ps.worker.pull"] * 2
    assert [r.name for r in by_req["W0/kv/8"]] == ["ps.worker.push"]
    assert by_req["W0/kv/3"] == [None]  # submitted before the trace began
    c = ps.checks(acc)
    assert (c["server_spans"], c["server_spans_joined"]) == (4, 3)
    assert c["server_spans_before_trace"] == 1 and c["roots"] == 2
    assert c["max_children_over_root"] == pytest.approx(3.9 / 4)
    # each leg counts 1 / legs of its request; the early push counts nothing
    assert acc.requests("pull") == pytest.approx(1.0)
    assert acc.requests("push") == pytest.approx(1.0)
    assert (acc.dispatches("pull"), acc.dispatches("push")) == (2, 1)


def test_device_seconds_by_scope_add_up_to_busy(acc):
    want = {
        "ps.table.apply": 6.0, "ps.apply.fused": 3.0,
        "ps.apply.trash_reset": 1.0, "ps.table.pull": 2.0, "ps.gather": 2.0,
    }
    assert acc.scope_s == pytest.approx({k: v * MS for k, v in want.items()})
    assert acc.busy_s == pytest.approx(8.05 * MS)
    assert acc.top_s == pytest.approx({
        "ps.table.apply": 6 * MS, "ps.table.pull": 2 * MS, ps.UNSCOPED: 0.05 * MS,
    })
    assert sum(acc.top_s.values()) == pytest.approx(acc.busy_s)
    # the while takes its program's scope, the copy in it its holder's
    held = acc.ops["ps.table.apply"]
    assert held["while.1", "f32[9] while(f32[9] %p)"] == pytest.approx(3 * MS)
    assert held["copy.9", "f32[9] copy(f32[9] %b)"] == pytest.approx(0.5 * MS)
    assert acc.ops["ps.apply.fused"] == pytest.approx({("fusion.2", "scatter.py:86"): 3 * MS})
    assert acc.ops[ps.UNSCOPED] == pytest.approx({("add.1", "linear.py:36"): 0.05 * MS})


def test_idle_gaps_by_the_span_in_flight(acc):
    assert acc.idle_gaps == pytest.approx({
        "ps.worker.wait>ps.server.h2d": 3.0 * MS,
        "ps.worker.submit": 1.0 * MS,
        "ps.worker.push": 0.975 * MS,
        "ps.worker.wait>queue": 0.5 * MS,
        "bench.grad": 0.5 * MS,
    })
    # the gaps add up to one chip's mean idle time
    assert sum(acc.idle_gaps.values()) == pytest.approx((20 - 8.05) / 2 * MS)


def test_recv_threads_are_those_that_deliver_requests(acc):
    shares = acc.recv_threads()
    assert sorted(shares.values()) == pytest.approx([0.12, 0.31])
    assert all("recv" in t and "W0" not in t for t in shares)


def _run(tmp_path, trace=1, pb=PB):
    bench_dir = tmp_path / "bench"
    logdir = bench_dir / "out" / "trace" / "cell" / "plugins" / "profile" / "t"
    logdir.mkdir(parents=True)
    if pb is not None:
        os.symlink(pb, logdir / "host.xplane.pb")
    return types.SimpleNamespace(
        name="cell", trace=trace, bench_dir=str(bench_dir),
        peaks={"hbm_bytes_per_s": 819e9}, unique_rows_per_step=100.0, planes=2,
        config={"table": {"dim": 4}},
    )


def test_the_twelve_metrics_on_the_fixture(tmp_path):
    run = _run(tmp_path)
    got = {
        name: cell_lib.load_module("layer_metrics", name).read(run)
        for name in NEW_METRICS
    }
    assert got == pytest.approx({
        "worker_localize_ms_p50": 1.0,
        "worker_wait_ms_p50": 2.5,
        "server_queue_wait_ms_p50": 0.15,  # of 50, 100, 200, 300 us
        "recv_thread_busy_pct": 21.5,
        "server_pull_busy_ms_p50": 1.2,
        "server_push_busy_ms_p50": 0.5,
        "server_d2h_ms_p50": 0.8,
        "gather_kernel_ms": 1.0,  # 2 ms under ps.table.pull, 2 dispatches
        "apply_kernel_ms": 6.0,  # 6 ms under ps.table.apply, 1 dispatch
        "gather_kernel_roofline": 100 * pull_bytes(100, 4) / 819e9 / (2 * MS),
        "apply_kernel_roofline": 100 * apply_bytes(100, 4, 1) / 819e9 / (6 * MS),
        "scoped_device_pct": 100 * 8.0 / 8.05,
    })
    roof = cell_lib.load_module("layer_metrics", "apply_kernel_roofline")
    assert roof.check(99.0) == [] and len(roof.check(100.5)) == 1
    scoped = cell_lib.load_module("layer_metrics", "scoped_device_pct")
    assert scoped.check(3.0) == [] and len(scoped.check(0.0)) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("how", ["untraced", "no_file", "no_ps_spans"])
def test_a_metric_with_nothing_to_read_is_left_out(tmp_path, name, how):
    """An untraced run, a run that left no file, and the trace of a program
    that has no ``ps.`` spans (the parent of PR 25) all read ``None``."""
    old = os.path.join(HERE, "data", "small.xplane.pb")
    run = {
        "untraced": lambda: _run(tmp_path, trace=0),
        "no_file": lambda: _run(tmp_path, pb=None),
        "no_ps_spans": lambda: _run(tmp_path, pb=old),
    }[how]()
    assert cell_lib.load_module("layer_metrics", name).read(run) is None


def test_the_account_prints(acc):
    text = ps.render(acc)
    for needle in ("ps.worker.localize", "ps.table.apply", "ps.worker.wait>queue",
                   "3 of 4 ps.server.pull/push", "scatter.py:86"):
        assert needle in text
