"""The measured window: a step clock shared by the drivers, and the
arithmetic from a step series to the end-to-end metrics.

A *step* is one worker iteration: from the moment the worker takes a batch
to the moment its push is acknowledged and the iteration is finished.  The
clock is keyed by thread, so a driver whose loop lives in the program (the
``ElasticTrainer``) can be clocked from the batch iterator it is handed.

Phases, per worker thread:

1. warm-up: exactly ``warmup_steps`` steps (one pass over the pre-made
   cycle), so every padded shape the window uses has compiled or loaded;
2. a barrier: the last worker to arrive runs ``on_open`` (collector frozen,
   counters snapshotted) and sets ``T0``;
3. the window: a worker's *counted* steps start at or after ``T0`` and end
   at or before ``T0 + seconds``.  Every worker keeps stepping until every
   worker's clock has passed ``T0 + seconds``, so no counted step runs
   beside an idle peer; then ``take`` returns ``None`` for all of them.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

from benchmarks.harness.stats import percentile


@dataclasses.dataclass
class Step:
    worker: int
    index: int  # position in the worker's own sequence, warm-up included
    start: float
    end: float = 0.0
    ok: bool = True
    #: traced run only: ``[(name, start, end), ...]`` of pull / grad / push
    spans: list = dataclasses.field(default_factory=list)


class StepClock:
    def __init__(
        self,
        n_workers: int,
        warmup_steps: int,
        seconds: float,
        on_open: Optional[Callable[[], None]] = None,
        *,
        barrier_timeout: float = 300.0,
        now: Callable[[], float] = time.perf_counter,
    ) -> None:
        self.n_workers = n_workers
        self.warmup_steps = warmup_steps
        self.seconds = seconds
        self.on_open = on_open
        self.now = now
        self.t0: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.steps: List[Step] = []
        self._lock = threading.Lock()
        self._slot: Dict[int, int] = {}  # thread ident -> worker slot
        self._count: Dict[int, int] = {}  # slot -> steps taken
        self._open: Dict[int, Step] = {}  # slot -> step in flight
        self._passed: set = set()
        self._waited: set = set()
        self._stop = False
        self._barrier = threading.Barrier(
            n_workers, action=self._open_window, timeout=barrier_timeout
        )

    # -- worker side -----------------------------------------------------
    def slot(self) -> int:
        """This thread's worker slot, in order of first appearance."""
        ident = threading.get_ident()
        with self._lock:
            s = self._slot.get(ident)
            if s is None:
                s = self._slot[ident] = len(self._slot)
                if s >= self.n_workers:
                    raise RuntimeError(
                        f"step clock built for {self.n_workers} workers saw "
                        f"thread number {s + 1}"
                    )
                self._count[s] = 0
            return s

    def current(self) -> Optional[Step]:
        """The calling thread's step in flight (spans attach to it)."""
        return self._open.get(self._slot.get(threading.get_ident(), -1))

    def finish(self, ok: bool = True) -> None:
        """Close the calling thread's step in flight, if any."""
        t = self.now()
        s = self.slot()
        step = self._open.pop(s, None)
        if step is not None:
            step.end, step.ok = t, ok
            with self._lock:
                self.steps.append(step)

    def take(self) -> Optional[int]:
        """Close the step in flight and open the next one.  Returns the
        index of the step in this worker's sequence (the driver maps it onto
        its cycle of batches), or ``None`` once the run is over."""
        self.finish()
        s = self.slot()
        n = self._count[s]
        if n == self.warmup_steps and s not in self._waited:
            self._waited.add(s)  # own slot only: no lock needed
            self._barrier.wait()
        if n >= self.warmup_steps and self.t0 is not None:
            if self.now() >= self.t0 + self.seconds:
                with self._lock:
                    self._passed.add(s)
                    if len(self._passed) == self.n_workers and not self._stop:
                        self._stop = True
                        self.t_stop = self.now()
            if self._stop:
                return None
        self._count[s] = n + 1
        self._open[s] = Step(worker=s, index=n, start=self.now())
        return n

    def abort(self) -> None:
        """Release everybody: a worker died before the window opened."""
        self._stop = True
        self._barrier.abort()

    def _open_window(self) -> None:
        if self.on_open is not None:
            self.on_open()
        self.t0 = self.now()

    # -- after the run ---------------------------------------------------
    def window_steps(self) -> List[Step]:
        """Every step that started at or after ``T0`` (counted or not)."""
        if self.t0 is None:
            return []
        return [s for s in self.steps if s.start >= self.t0]


def window_metrics(steps, t0: float, seconds: float, batch: int) -> dict:
    """End-to-end arithmetic over a step series.

    ``examples_per_s`` is the sum over workers of (counted steps x batch) /
    (end of its last counted step - start of its first): whole steps over
    their own time, so nothing is quantised by the window's edges and no
    start-up or drain is inside it.  ``step_ms_*`` pool the counted steps.
    """
    t1 = t0 + seconds
    started = [s for s in steps if t0 <= s.start < t1]
    counted = [s for s in steps if s.start >= t0 and s.end <= t1 and s.ok]
    by_worker: Dict[int, list] = {}
    for s in counted:
        by_worker.setdefault(s.worker, []).append(s)
    rate = 0.0
    for ws in by_worker.values():
        span = max(s.end for s in ws) - min(s.start for s in ws)
        if span > 0:
            rate += len(ws) * batch / span
    durs = [1e3 * (s.end - s.start) for s in counted]
    return {
        "examples_per_s": rate if counted else None,
        "step_ms_p50": percentile(durs, 50),
        "step_ms_p95": percentile(durs, 95),
        "counted_steps": len(counted),
        "attempted": len(started),
        "failed": sum(1 for s in started if not s.ok),
        "steps_per_worker": {w: len(ws) for w, ws in sorted(by_worker.items())},
    }
