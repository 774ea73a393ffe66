"""What decides ``correct``.  Every function returns a list of failures in
words; an empty list passes.

Tolerance of the reference comparison, with ``chip_smoke.py``'s argument: a
float32 NumPy optimizer against the chip.  The chip's transcendental
functions (``sqrt``, ``pow``) are not correctly rounded; the first chip run
of PR 21 was off by 4.2e-6 relative after three steps, and 1e-5 admits
twice that.  A wrong row, a lost duplicate or a missed push is off by a
whole step, a thousand times more."""

from __future__ import annotations

import numpy as np

from benchmarks.harness.keys import draw_check_keys, rows_of
from benchmarks.reference.adagrad import AdaGradRows

REF_RTOL, REF_ATOL = 1e-5, 1e-5
TIMEOUT = 120.0


def check_draws(seed: int, *, n_keys: int, rows: int, dim: int, pushes: int,
                localizer: str):
    """What :func:`push_pull_check` pushes: first the distinct keys, drawn
    from the localizer's domain, then for each push ``(idx, grads)``: most
    keys once, a quarter twice, a tenth four times, shuffled (a count that
    makes the bucket pad), and a gradient row per position.  Keys and
    duplicates are the same in every run (the same shapes, so nothing
    compiles for a new seed); the gradients come from the seed."""
    fixed = np.random.default_rng(0x2EF)
    rng = np.random.default_rng([int(seed), 0x2EF])
    distinct = draw_check_keys(fixed, n_keys, rows, localizer)
    yield distinct
    n = distinct.size
    for _ in range(pushes):
        idx = np.concatenate([
            fixed.permutation(n)[: (5 * n) // 6],
            fixed.integers(0, n // 4, size=n // 4),
            np.repeat(fixed.integers(0, n, size=n // 10), 3),
        ])
        fixed.shuffle(idx)
        yield idx, (0.1 * rng.standard_normal((idx.size, dim))).astype(
            np.float32
        )


def push_pull_check(cluster, seed: int, *, n_keys: int = 3000, rounds: int = 2):
    """Every worker in turn, ``rounds`` times over, pushes a seeded batch
    with duplicate keys (and a key count that makes the bucket pad) through
    ``push_sync``; then the touched keys are pulled back from every shard
    that owns one, and the rows must agree with NumPy AdaGrad on the touched
    rows.  The keys come from the domain of the localizer the cluster was
    built with, and the reference finds their rows by it.  In turn, because
    the servers' gate counts every worker's pushes: one worker pushing alone
    would start the run ``rounds`` steps ahead of its peers and be held at
    the gate for good.  Returns ``(failures, info)``; ``info["pushes"]`` is
    what the servers' push counts owe."""
    table = cluster.table
    opt = table.optimizer
    if opt.kind != "adagrad" or opt.l1:
        return [f"no plain reference for optimizer {opt.kind!r}"], {}
    fails = []
    workers = list(cluster.workers.values())
    worker = workers[0]
    pushes = rounds * len(workers)
    draws = check_draws(
        seed, n_keys=n_keys, rows=table.rows, dim=table.dim, pushes=pushes,
        localizer=cluster.localizer,
    )
    distinct = next(draws)
    slots = rows_of(distinct, table.rows, cluster.localizer)
    ns = len(cluster.servers)
    owners = np.unique(slots * ns // table.rows)
    if owners.size != ns:
        fails.append(f"reference keys reach shards {owners.tolist()} of {ns}")
    ref = AdaGradRows(table.dim, opt.learning_rate, opt.eps, opt.l2)
    init = worker.pull_sync(table.name, distinct, timeout=TIMEOUT)
    ref.seed_rows(slots, init)
    for i, (idx, grads) in enumerate(draws):
        workers[i % len(workers)].push_sync(
            table.name, distinct[idx], grads, timeout=TIMEOUT
        )
        ref.push(slots[idx], grads)
    got = worker.pull_sync(table.name, distinct, timeout=TIMEOUT)
    want = ref.rows(slots)
    got = np.asarray(got).reshape(want.shape)
    if not np.isfinite(got).all():
        fails.append("reference check: pulled rows are not finite")
    err = np.abs(got - want)
    bad = err > REF_ATOL + REF_RTOL * np.abs(want)
    if bad.any():
        fails.append(
            f"reference check: {int(bad.any(axis=-1).sum())} of {distinct.size} "
            f"rows differ from NumPy AdaGrad (max abs error {float(err.max()):.3e})"
        )
    if np.array_equal(got, np.asarray(init).reshape(want.shape)):
        fails.append("reference check: the pushes changed no row")
    return fails, {"pushes": pushes, "max_abs_err": float(err.max()),
                   "rows": int(distinct.size), "localizer": cluster.localizer}


def unique_rows_per_step(cluster, batches, keys_of) -> float:
    """Mean unique rows a step touches, over the pool every worker cycles
    through: the byte model's input, from the window's own batches, by the
    localizer the cluster was built with."""
    table = cluster.table
    counts = [
        np.unique(rows_of(keys_of(b), table.rows, cluster.localizer)).size
        for b in batches
    ]
    return float(np.mean(counts))


def compare_grads(got, want, what, examples, *, median, worst):
    """Gradient rows of the first step against the plain reference: per
    example, the largest error relative to the batch's largest gradient.
    The median example has to agree within ``median`` and every example
    within ``worst``.  Returns ``(failures, {"median", "p99", "worst"})``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if got.shape != want.shape:
        return [f"{what}: shape {got.shape} against {want.shape}"], {}
    if not np.isfinite(got).all():
        return [f"{what}: not finite"], {}
    scale = float(np.abs(want).max()) or 1.0
    err = np.abs(got - want).reshape(examples, -1).max(axis=1) / scale
    info = {"median": float(np.median(err)),
            "p99": float(np.percentile(err, 99)), "worst": float(err.max())}
    if info["median"] > median or info["worst"] > worst:
        return [
            f"{what}: the median example is off by {info['median']:.3e} "
            f"(limit {median:.0e}), the worst of {examples} by "
            f"{info['worst']:.3e} (limit {worst:.0e})"
        ], info
    return [], info


#: counters that must not move at all in a healthy run
MUST_STAY_ZERO = (
    "error_replies", "gate_sheds", "gate_forced", "push_retries",
    "pull_retries", "dead_nodes", "ledger_fatal", "retransmits", "gave_up",
    "dropped", "wire_undeliverable",
)


def loss_stretches(losses, cycle_steps: int = 0):
    """``(n, mean of the first n losses, mean of the last n)``: see
    :func:`window_checks` for what a stretch is."""
    losses = np.asarray(losses, np.float64)
    n = min(max(losses.size // 10, cycle_steps, 1), losses.size // 2)
    return n, float(losses[:n].mean()), float(losses[-n:].mean())


def window_checks(counters: dict, losses, retired, compiles: int,
                  cycle_steps: int = 0) -> list:
    """In the window: no error reply, gate shed, forced push, retransmit,
    retired worker or dead node; no backend compile or cache load (a shape
    was not warmed), traced or not; every loss finite; the mean loss of the
    window's last stretch below its first stretch's.  A stretch is a tenth
    of the losses, or one pass of every worker over the cycle
    (``cycle_steps``) where that is longer, and never over half: two whole
    passes hold the same batches, so what differs is what was learned and
    not which batches fell into the stretch (a 10 s window compared by
    tenths of 4 losses read a rise of 0.004 on the chip)."""
    # a gate defer is a fence-shaped ``__error__`` reply that the worker
    # retries: it is counted as a wait, and is not an error
    counters = dict(
        counters,
        error_replies=counters.get("error_replies", 0)
        - counters.get("gate_waits", 0),
    )
    fails = [
        f"{k} = {counters[k]}" for k in MUST_STAY_ZERO if counters.get(k, 0)
    ]
    if compiles:
        fails.append(f"compiles_in_window = {compiles}")
    if retired:
        fails.append(f"retired workers: {sorted(retired)}")
    losses = np.asarray(losses, np.float64)
    if losses.size < 20:
        fails.append(f"only {losses.size} losses in the window")
        return fails
    if not np.isfinite(losses).all():
        fails.append("a loss in the window is not finite")
    n, first, last = loss_stretches(losses, cycle_steps)
    if not last < first:
        fails.append(
            f"loss did not fall: first {n} losses {first:.5f}, last {n} {last:.5f}"
        )
    return fails


def after_checks(cluster, sample_keys, acked_pushes: int) -> list:
    """After the window, with nothing in flight: two pulls of a sample of
    the touched keys agree bit for bit, and the servers' push counts equal
    the legs the workers had acknowledged (a push has one leg per server)."""
    fails = []
    table = cluster.table
    worker = next(iter(cluster.workers.values()))
    a = np.asarray(worker.pull_sync(table.name, sample_keys, timeout=TIMEOUT))
    b = np.asarray(worker.pull_sync(table.name, sample_keys, timeout=TIMEOUT))
    if not np.array_equal(a, b):
        fails.append("two pulls of the same keys after the window differ")
    if not np.isfinite(a).all():
        fails.append("rows pulled after the window are not finite")
    legs = acked_pushes * len(cluster.servers)
    got = sum(s.pushes for s in cluster.servers.values())
    if got != legs:
        fails.append(f"servers counted {got} pushes, workers had {legs} legs acknowledged")
    return fails
