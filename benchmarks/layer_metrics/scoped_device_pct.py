"""Share of the device's busy time in the traced window that lies under any
``ps.`` scope (``program_spans``: an operation's own scope, else its holder's,
else its program's).  A run whose program has ``ps.`` host spans and no
scoped device time fails: the scopes are gone."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "scoped_device_pct", "%", "device", "step_ms_p50"


def read(run):
    acc = program_spans.for_run(run)
    if acc is None or not acc.busy_s:
        return None
    scoped = sum(s for k, s in acc.top_s.items() if k != program_spans.UNSCOPED)
    return 100.0 * scoped / acc.busy_s


def check(value):
    return [] if value > 0 else ["no device time lies under a ps. scope"]
