"""Host spans of the traced run, recorded from the benchmark's side around
the calls into each layer: the step clock's step in flight gets ``(name,
start, end)`` and the profiler's trace gets a ``TraceAnnotation`` of the same
extent, so that idle gaps of the device can be laid against them."""

from __future__ import annotations

import functools

from benchmarks.harness.trace_reduce import SPAN_PREFIX as PREFIX


def spanned(clock, name, fn, *, block=False):
    """``fn`` with a span ``name`` around every call; ``block`` ends the
    span in ``block_until_ready`` on what ``fn`` returned."""
    import jax

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        t0 = clock.now()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            out = fn(*args, **kwargs)
            if block:
                jax.block_until_ready(out)
        step = clock.current()
        if step is not None:
            step.spans.append((name, t0, clock.now()))
        return out

    return wrapped


def span_workers(clock, workers) -> None:
    """Wrap ``pull_sync`` / ``push_sync`` of the KVWorker instances the
    harness built (instance attributes: the class is not touched)."""
    for kv in workers:
        kv.pull_sync = spanned(clock, "pull", kv.pull_sync)
        kv.push_sync = spanned(clock, "push", kv.push_sync)
