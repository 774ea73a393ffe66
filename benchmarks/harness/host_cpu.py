"""Where the host's time goes inside the program's own spans: a server
request's stages and what they leave unnamed, the CPU a thread used beside
the wall time it was busy, the worker's host chain and the bounded-delay
turn.  Nine readings of ``program_spans``' account of a traced run (PR 37):

    python3 -m benchmarks.harness.host_cpu <file.xplane.pb>

prints them as one JSON line after the account they come from, and exits 1
where a reading cannot be (``recv_thread_cpu_pct`` above 105).  Since PR 39
each is a ``BENCHMARK.json`` entry whose ``layer_metrics`` file calls
``read`` below; ``METRICS`` holds its unit, direction, layer and ``moves``.

**How a CPU share is read.**  Every ``ps.`` span carries ``cpu_us``, its
thread's CPU time (``time.thread_time``) between its edges.  Wall less CPU
in a span without I/O is time the thread stood without the GIL: one
interpreter lock serves the workers' threads and the servers' recv threads
of a process, so a thread that is "busy" all the window may compute for half
of it and queue for the lock for the rest.  A wait for the device is wall
and no CPU either: a pull's D2H (``ps.server.d2h``, the ``np.asarray`` that
waits for the gather) is inside a recv thread's busy time and outside its
CPU, so subtract it before calling the rest queueing.  Read CPU over sums:
on the chip's machine a thread's CPU clock ticks every 10 ms (my chip runs,
PR 37), so one span reads 0 or 10,000 us and only many of them read their
share.  The two CPU readings here are left out where the sum is under
``MIN_TICKS`` ticks of the clock as the account shows it (its smallest
``cpu_us`` above 0): the recv threads of a ``pretrain8k`` cell are busy for
20-90 ms of a 4 s window, and 2-9 ticks read anything from 45 to 150 %.
Sum over a thread's outermost spans only: a span's CPU holds its children's.

A reader returns ``None`` where the account holds nothing to read (a program
without the span, a window without a server or a worker).
"""

from __future__ import annotations

import collections
import json
import statistics
import sys
from typing import Callable, Dict, List, NamedTuple, Optional

from benchmarks.harness import program_spans
from benchmarks.harness.program_spans import Account, Span
from benchmarks.harness.trace_reduce import union_seconds
from parameter_server_tpu.utils.trace import SPANS

#: the spans a server opens for a request
SERVER_SPANS = ("ps.server.pull", "ps.server.push")
#: one a worker-step in every driver (``push_sync``; the hybrid trainer's
#: ``push_device`` under its ``ps.hybrid.push_submit``, where the pull is
#: split into ``pull`` and ``pull_result_device`` and opens no span of its
#: own): the divisor of a step's CPU
STEP_ROOT = "ps.worker.push"
TURN = "ps.worker.turn"


#: a CPU sum of fewer ticks of the thread's CPU clock is not read
MIN_TICKS = 100


def cpu_s(spans: List[Span]) -> float:
    return 1e-6 * sum(sp.attrs.get("cpu_us", 0) for sp in spans)


def enough_cpu(acc: Account, seconds: float) -> bool:
    """Whether ``seconds`` of CPU are ``MIN_TICKS`` ticks or more of the
    clock that wrote the account's ``cpu_us`` (module docstring)."""
    ticks = [sp.attrs["cpu_us"] for sp in acc.spans if sp.attrs.get("cpu_us", 0) > 0]
    return bool(ticks) and seconds >= MIN_TICKS * 1e-6 * min(ticks)


def outermost(acc: Account) -> List[Span]:
    """The window's spans that no other span of the window holds (a parent
    that crosses the window's edge is not in the account)."""
    inside = {id(sp) for sp in acc.spans}
    return [
        sp for sp in acc.spans
        if sp.parent is None or id(sp.parent) not in inside
    ]


def span_ms_p50(name: str) -> Callable[[Account], Optional[float]]:
    """The reader of the median duration of the window's ``name`` spans."""
    def read(acc: Account) -> Optional[float]:
        durs = acc.durations_ms(name)
        return statistics.median(durs) if durs else None
    return read


def recv_deliveries(acc: Account) -> Dict[str, List[Span]]:
    """The ``ps.van.deliver`` spans of each server recv thread."""
    out: Dict[str, List[Span]] = {t: [] for t in acc.recv_threads()}
    for sp in acc.by_name.get("ps.van.deliver", []):
        if sp.thread in out:
            out[sp.thread].append(sp)
    return out


def recv_cpu_pct(acc: Account) -> Optional[float]:
    """CPU the servers' recv threads used inside their deliveries, over the
    wall time those deliveries cover (the union a thread, summed)."""
    threads = recv_deliveries(acc).values()
    wall = sum(
        union_seconds([(sp.start, sp.end) for sp in mine])[0] for mine in threads
    )
    cpu = sum(cpu_s(mine) for mine in threads)
    return 100.0 * cpu / wall if wall and enough_cpu(acc, cpu) else None


def cpu_ms_per_step(acc: Account) -> Optional[float]:
    """CPU under the outermost ``ps.`` spans of every thread, workers' and
    servers' alike, a worker-step: what a process under one GIL pays a
    step for the PS plane."""
    steps = len(acc.by_name.get(STEP_ROOT, []))
    cpu = cpu_s(outermost(acc))
    return 1e3 * cpu / steps if steps and enough_cpu(acc, cpu) else None


def server_self_ms_p50(acc: Account) -> Optional[float]:
    """Median over the servers' request spans of what their stages leave
    uncovered: the coverage reading of the server's host path, as
    ``scoped_device_pct`` is the device's."""
    mine = [sp for name in SERVER_SPANS for sp in acc.by_name.get(name, [])]
    return 1e3 * statistics.median(sp.self_s for sp in mine) if mine else None


def turn_wait_ms_p50(acc: Account) -> Optional[float]:
    """Median ``ps.worker.turn``.  A window with worker steps and no turn
    reads 0.0 when the program knows the span (``SPANS``: a trainer that
    holds no ``ConsistencyController`` waits for nobody) and nothing when it
    does not (a program from before the span)."""
    turns = acc.durations_ms(TURN)
    if turns:
        return statistics.median(turns)
    if TURN in SPANS and acc.by_name.get(STEP_ROOT):
        return 0.0
    return None


class Metric(NamedTuple):
    """What a ``BENCHMARK.json`` entry of the reading would say."""

    unit: str
    better: str
    layer: str
    moves: str
    read: Callable[[Account], Optional[float]]


METRICS: Dict[str, Metric] = {
    "server_localize_ms_p50": Metric(
        "ms", "lower", "server apply", "step_ms_p50",
        span_ms_p50("ps.server.localize")),
    "server_ack_ms_p50": Metric(
        "ms", "lower", "server apply", "step_ms_p50",
        span_ms_p50("ps.server.ack")),
    "server_self_ms_p50": Metric(
        "ms", "lower", "server apply", "step_ms_p50", server_self_ms_p50),
    "recv_thread_cpu_pct": Metric(
        "%", "higher", "van", "examples_per_s", recv_cpu_pct),
    "host_cpu_ms_per_step": Metric(
        "ms", "lower", "worker wire", "step_ms_p50", cpu_ms_per_step),
    "turn_wait_ms_p50": Metric(
        "ms", "lower", "consistency", "step_ms_p50", turn_wait_ms_p50),
    "worker_submit_ms_p50": Metric(
        "ms", "lower", "worker wire", "step_ms_p50",
        span_ms_p50("ps.worker.submit")),
    "worker_combine_ms_p50": Metric(
        "ms", "lower", "worker wire", "step_ms_p50",
        span_ms_p50("ps.worker.combine")),
    "worker_assemble_ms_p50": Metric(
        "ms", "lower", "worker wire", "step_ms_p50",
        span_ms_p50("ps.worker.assemble")),
}


def read(run, name: str) -> Optional[float]:
    """The reading ``name`` of a traced run, for its ``layer_metrics`` file."""
    acc = program_spans.for_run(run)
    return METRICS[name].read(acc) if acc is not None else None


def read_all(acc: Account) -> Dict[str, Optional[float]]:
    return {name: m.read(acc) for name, m in METRICS.items()}


def checks(values: Dict[str, Optional[float]]) -> List[str]:
    """A thread cannot use more CPU than wall: 5 points for the clocks."""
    share = values.get("recv_thread_cpu_pct")
    if share is not None and share > 105:
        return [f"recv_thread_cpu_pct = {share:.2f} % is above 105"]
    return []


# -- the account, printed -------------------------------------------------------
def _row(key: str, spans: List[Span], wall: float, cpu: float, p50_ms: float) -> str:
    share = f"{100 * cpu / wall:6.1f}" if wall > 0 else "     -"
    return f"{key:28s} {len(spans):6d} {wall:9.4f} {p50_ms:8.3f} {share}"


def render(acc: Account) -> str:
    out = [f"[host_cpu] {acc.path}"]
    if not acc.spans:
        return out[0] + ": no ps. spans in a traced window"
    out.append(f"{'server span and its stages':28s} {'count':>6s} {'total s':>9s} "
               f"{'p50 ms':>8s} {'cpu %':>6s}")
    for name in SERVER_SPANS:
        mine = acc.by_name.get(name, [])
        if not mine:
            continue
        stages: Dict[str, List[Span]] = {}
        for sp in acc.spans:
            if sp.parent is not None and sp.parent.name == name:
                stages.setdefault(sp.name, []).append(sp)
        wall, cpu = sum(sp.dur for sp in mine), cpu_s(mine)
        out.append(_row(name, mine, wall, cpu, statistics.median(1e3 * sp.dur for sp in mine)))
        for stage, sps in stages.items():
            out.append(_row(
                "  " + stage, sps, sum(sp.dur for sp in sps), cpu_s(sps),
                statistics.median(1e3 * sp.dur for sp in sps),
            ))
        held = [sp for sps in stages.values() for sp in sps]
        out.append(_row(
            "  self", mine, wall - sum(sp.dur for sp in held), cpu - cpu_s(held),
            1e3 * statistics.median(sp.self_s for sp in mine),
        ))
    busy = acc.recv_threads()
    for thread, mine in recv_deliveries(acc).items():
        wall = sum(sp.dur for sp in mine)
        d2h = sum(
            sp.dur for sp in acc.by_name.get("ps.server.d2h", [])
            if sp.thread == thread
        )
        out.append(
            f"recv thread {thread}: busy {100 * busy[thread]:.1f} % of the window; "
            f"of busy, cpu {100 * cpu_s(mine) / wall:.1f} %, d2h {100 * d2h / wall:.1f} %"
        )
    tops: Dict[str, float] = {}
    for sp in outermost(acc):
        tops[sp.name] = tops.get(sp.name, 0.0) + cpu_s([sp])
    out.append(
        f"cpu under outermost spans, s, over {len(acc.by_name.get(STEP_ROOT, []))} "
        f"{STEP_ROOT}: "
        + ", ".join(f"{k} {v:.3f}" for k, v in sorted(tops.items(), key=lambda kv: -kv[1]) if v)
    )
    turns = acc.by_name.get(TURN, [])
    if turns:
        blocked = [sp for sp in turns if sp.attrs.get("blocked")]
        leads = collections.Counter(sp.attrs.get("lead", 0) for sp in turns)
        out.append(
            f"{TURN}: {len(turns)} turns, {sum(sp.dur for sp in turns):.4f} s in all, "
            f"{len(blocked)} blocked ({sum(sp.dur for sp in blocked):.4f} s), "
            f"turns by lead {dict(sorted(leads.items()))}"
        )
    return "\n".join(out)


def main(argv: List[str]) -> int:
    acc = program_spans.load(argv[0])
    values = read_all(acc)
    fails = checks(values)
    print(render(acc))
    print(json.dumps({"metrics": values, "fails": fails}))
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
