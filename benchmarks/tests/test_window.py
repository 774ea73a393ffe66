"""The window arithmetic: whole counted steps over their own time."""

import threading
import time

import pytest

from benchmarks.harness.window import Step, StepClock, window_metrics

BATCH = 100


def series(worker, first, dur, n, gap=0.0):
    """``n`` back-to-back steps of ``dur`` seconds from ``first``."""
    out, t = [], first
    for i in range(n):
        out.append(Step(worker=worker, index=i, start=t, end=t + dur))
        t += dur + gap
    return out


def test_rate_is_the_series_true_rate_whatever_the_edges():
    # worker 0: 0.4 s steps, worker 1: 0.5 s steps, both from before T0 to
    # after the close; the true rate is batch/0.4 + batch/0.5
    true = BATCH / 0.4 + BATCH / 0.5
    t0, seconds = 10.0, 20.0
    steps = series(0, 9.87, 0.4, 80) + series(1, 9.61, 0.5, 64)
    m = window_metrics(steps, t0, seconds, BATCH)
    assert m["examples_per_s"] == pytest.approx(true, rel=1e-9)
    # an odd step at each edge: one straddles T0, one straddles the close;
    # neither is counted, and the rate does not move
    edged = steps + [Step(0, 999, 9.99, 10.01), Step(1, 999, 29.99, 30.4)]
    assert window_metrics(edged, t0, seconds, BATCH)["examples_per_s"] == (
        pytest.approx(true, rel=1e-9)
    )
    # a fixed-window count would be off by the edge steps: 49 or 50 of 0.4 s
    naive = sum(s.start >= t0 and s.end <= t0 + seconds for s in steps)
    assert abs(naive * BATCH / seconds - true) / true > 0.01


def test_a_drain_tail_does_not_move_the_rate():
    t0, seconds = 0.0, 10.0
    steady = series(0, 0.0, 0.25, 60) + series(1, 0.0, 0.25, 60)
    base = window_metrics(steady, t0, seconds, BATCH)["examples_per_s"]
    assert base == pytest.approx(2 * BATCH / 0.25)
    # worker 1 runs dry at 8 s and worker 0 drains alone at half the pace
    # AFTER the close: the tail is outside every counted step
    tail = series(0, 15.0, 0.5, 8)
    assert window_metrics(steady + tail, t0, seconds, BATCH)[
        "examples_per_s"
    ] == pytest.approx(base)


def test_percentiles_pool_counted_steps_and_count_failures():
    steps = series(0, 0.0, 0.1, 50) + series(1, 0.0, 0.3, 10)
    steps[3].ok = False
    m = window_metrics(steps, 0.0, 100.0, BATCH)
    assert m["counted_steps"] == 59 and m["attempted"] == 60 and m["failed"] == 1
    assert m["step_ms_p50"] == pytest.approx(100.0)
    assert m["step_ms_p95"] == pytest.approx(300.0)


def test_every_worker_steps_until_every_clock_has_passed():
    """A fast and a slow worker: the fast one keeps stepping past the close
    until the slow one's clock has passed too, and nobody stops early."""
    opened = []
    clock = StepClock(2, warmup_steps=2, seconds=0.3,
                      on_open=lambda: opened.append(time.perf_counter()))

    def worker(dur):
        while clock.take() is not None:
            time.sleep(dur)

    ts = [threading.Thread(target=worker, args=(d,)) for d in (0.01, 0.08)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in ts)
    assert len(opened) == 1 and clock.t0 is not None
    close = clock.t0 + 0.3
    by = {}
    for s in clock.steps:
        by.setdefault(s.worker, []).append(s)
    # warm-up steps all ended before T0; both workers' last step ends after
    # the close, and the fast one's steps cover the slow one's last step
    assert all(s.end <= clock.t0 for w in by.values() for s in w[:2])
    last = {w: max(s.end for s in ss) for w, ss in by.items()}
    assert all(t >= close for t in last.values())
    assert abs(last[0] - last[1]) < 0.1
    m = window_metrics(clock.window_steps(), clock.t0, 0.3, BATCH)
    assert set(m["steps_per_worker"]) == {0, 1}
    assert m["steps_per_worker"][0] > m["steps_per_worker"][1] >= 2
