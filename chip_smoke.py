#!/usr/bin/env python3
"""chip_smoke.py: does the parameter server still start on the chip?

Drives the main path once, in ONE process, through the entry points a user
calls (``app.load_config`` -> ``app.create(cfg)()``, what ``psx run cfg``
does), at the full width of the repo's first workload: hashed-Criteo sparse
LR, table 2^22 x 1 with AdaGrad, batches of 16384 x 39 keys, pushed and
pulled through KVWorker -> Van -> KVServer -> device-resident KVTable under
the ElasticTrainer.  It counts and checks; it is not a benchmark and prints
no rate.

    python3 chip_smoke.py            # on a machine with a TPU; anything
                                     # else exits non-zero with no result
    python3 chip_smoke.py --dry-run  # the sandbox: tiny sizes on 4 virtual
                                     # CPU devices, Pallas interpreted on
                                     # request; catches typos, proves nothing

Phases (the first failure ends the run with its own traceback):
  a  async_lr through app.create on the chip: 2 workers, 2 servers, SSP 2
  b  the table kernels at every row width the system serves (XLA at dim 16,
     128, 2048; Pallas fused and three-pass at dim 128, 2048), through a
     2-server KVServer/KVWorker cluster, against a float32 NumPy Adam
  c  what a row costs in device memory, printed beside a and b's tables
  d  phase a again with 4 servers and 4 workers, one per chip, when the host
     has four
  e  every other registered app takes two steps

The counts go out as a ``[summary] {...}`` line.  After every phase passed on
a TPU, and only then, the last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import contextlib
import gc
import importlib.metadata
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

#: full size, and what --dry-run cuts it to
FULL = dict(rows=1 << 22, key_space=1 << 22, nnz=39, batch=16384, steps=32,
            kernel_rows=50_304, kernel_keys=1000, kernel_dups=200)
TINY = dict(rows=1 << 12, key_space=1 << 12, nnz=8, batch=256, steps=16,
            kernel_rows=512, kernel_keys=96, kernel_dups=32)

#: NumPy reference vs the chip, three Adam steps of size <= lr = 0.01 in
#: float32.  The chip's pow is not correctly rounded and Adam's bias
#: correction 1 - beta2**t cancels to t * 1e-3, so a pow error of e becomes
#: e / (2e-3 t) relative in the step: the first chip run was off by 4.2e-6
#: (pow ~8 ulp).  1e-5 admits twice that; a wrong row, a lost duplicate or a
#: missed push is off by a whole step, a thousand times more.
REF_RTOL, REF_ATOL = 1e-5, 1e-5
#: Pallas vs XLA on the same chip: the same float32 ops in the same order;
#: only the compiler's fusion and fma choices can differ.
IMPL_RTOL, IMPL_ATOL = 1e-6, 1e-7


class CompileMeter:
    """Seconds jax spent in backend compiles (persistent-cache lookups
    included), and the cache's hits and misses, since the last reset."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        with self._lock:
            self.compile_s = 0.0
            self.hits = self.misses = 0

    def _duration(self, event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            with self._lock:  # server and worker threads compile concurrently
                self.compile_s += seconds

    def _event(self, event, **_):
        with self._lock:
            if event.endswith("/cache_hits"):
                self.hits += 1
            elif event.endswith("/cache_misses"):
                self.misses += 1


@contextlib.contextmanager
def phase(name, meter, report):
    """Time one phase; print and keep what it returned plus set-up costs."""
    gc.collect()  # earlier phases' tables must not muddy this one's memory
    meter.reset()
    counts = {}
    t0 = time.perf_counter()
    yield counts
    counts.update(
        wall_s=round(time.perf_counter() - t0, 2),
        compile_s=round(meter.compile_s, 2),
        cache_hits=meter.hits,
        cache_misses=meter.misses,
    )
    report[name] = counts
    print(f"[phase {name}] {json.dumps(counts)}", flush=True)


def cache_entries(cache_dir):
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def load_cfg(tmp, name, raw):
    """A config the way a user hands one over: a file for load_config."""
    from parameter_server_tpu import app as app_lib

    path = os.path.join(tmp, f"{name}.json")
    with open(path, "w") as f:
        json.dump(raw, f)
    return app_lib.load_config(path)


# --------------------------------------------------------------- phase a, d --


def run_async_lr(tmp, size, *, workers, servers, tag):
    """The main path through app.create; returns (checked counts, losses)."""
    from parameter_server_tpu import app as app_lib
    from parameter_server_tpu import checkpoint, evaluation
    from parameter_server_tpu.data.synthetic import SyntheticCTR
    from parameter_server_tpu.utils.keys import HashLocalizer

    shards = size["steps"] // 4  # the app cuts the stream into 4-batch loads
    ckpt_root = os.path.join(tmp, f"ckpt_{tag}")
    raw = {
        "app": "async_lr",
        "steps": size["steps"],
        "table": {
            "name": "w", "rows": size["rows"], "dim": 1,
            "optimizer": {"kind": "adagrad", "learning_rate": 0.1},
        },
        "data": {
            "kind": "synthetic", "key_space": size["key_space"],
            "nnz": size["nnz"], "batch_size": size["batch"], "seed": 0,
        },
        "consistency": {"mode": "ssp", "max_delay": 2},
        "topology": {"num_workers": workers, "num_servers": servers},
        "ckpt_root": ckpt_root,
        "ckpt_every": shards,  # one checkpoint, after the last workload
    }
    out = app_lib.create(load_cfg(tmp, f"async_lr_{tag}", raw))()

    losses = np.asarray(out["losses"], np.float64)
    assert losses.shape == (size["steps"],) and np.isfinite(losses).all(), losses
    assert losses[-4:].mean() < losses[:4].mean(), f"losses not falling: {losses}"
    assert out["workloads_done"] == shards, out["workloads_done"]
    for key in ("retired_workers", "dead_nodes"):
        assert out[key] == [], f"{key}: {out[key]}"
    for key in ("error_replies", "gate_sheds"):
        assert out[key] == 0, f"{key}: {out[key]}"
    net = out["net"]
    assert net.get("retransmits", 0) == 0 and net.get("dropped", 0) == 0, net

    # the checkpoint, read back by the repo's own reader: the rows AdaGrad
    # touched are the slots the stream's keys hash to, on every shard (a few
    # slots' gradients cancel to exactly 0.0 while every p is still 0.5)
    step = out["last_ckpt_step"]
    assert step == shards, f"checkpoint at {step}, expected {shards}"
    arrays = checkpoint.load_global_arrays(ckpt_root, step, "w")
    stream = SyntheticCTR(
        key_space=size["key_space"], nnz=size["nnz"],
        batch_size=size["batch"], seed=0,
    )
    loc = HashLocalizer(size["rows"])
    expected = np.unique(
        np.concatenate(
            [loc.assign(stream.next_batch()[0]).ravel()
             for _ in range(size["steps"])]
        )
    )
    touched = np.flatnonzero(arrays["state.sum_sq"][:, 0])
    assert np.isin(touched, expected).all(), "a row nobody pushed was touched"
    assert touched.size >= 0.98 * expected.size, (
        f"rows touched {touched.size} of {expected.size} slots pushed"
    )
    assert np.isfinite(arrays["value"]).all()
    # ... and scores held-out batches better than the zero model does
    held_out = [stream.next_batch() for _ in range(2)]
    report = evaluation.evaluate_checkpoint(ckpt_root, "w", held_out)
    assert report["logloss"] < np.log(2.0), report

    pushes = sum(s["pushes"] for s in out["servers"].values())
    pulls = sum(s["pulls"] for s in out["servers"].values())
    assert pushes >= size["steps"] and pulls >= size["steps"], (pushes, pulls)
    counts = {
        "workers": workers, "servers": servers, "steps": int(losses.size),
        "pushes": pushes, "pulls": pulls, "rows_touched": int(touched.size),
        "slots_pushed": int(expected.size),
        "error_replies": out["error_replies"], "retired_workers": 0,
        "first_loss": round(float(losses[0]), 6),
        "last_loss": round(float(losses[-1]), 6),
        "held_out_logloss": round(report["logloss"], 6),
        "checkpoint_step": step,
        "servers_placed": out["servers"],
    }
    return counts, losses


def print_row_cost(label, rows, planes, nominal, allocated):
    """Phase c: allocator growth beside the nominal rows x dim x 4 x planes;
    returns device bytes per row of ONE plane (value or a state array)."""
    if allocated is None:
        print(f"[phase c] {label}: nominal {nominal} B; this backend's "
              "allocator reports nothing", flush=True)
        return None
    per_row = allocated / (rows * planes)
    print(f"[phase c] {label}: nominal {nominal} B, allocated {allocated} B "
          f"(x{allocated / nominal:.4f}) = {per_row:.2f} B per row per plane, "
          f"{planes} planes", flush=True)
    return round(per_row, 3)


# ------------------------------------------------------------------ phase b --


def adam_reference(value, grads_by_slot, cfg, steps):
    """float32 NumPy Adam with per-row step counts (kv/optim.py::Adam)."""
    f = np.float32
    v = value.astype(f)
    m = np.zeros_like(v)
    s = np.zeros_like(v)
    for t in range(1, steps + 1):
        g = grads_by_slot
        m = f(cfg.beta1) * m + f(1 - cfg.beta1) * g
        s = f(cfg.beta2) * s + f(1 - cfg.beta2) * g * g
        m_hat = m / f(1 - cfg.beta1 ** t)
        s_hat = s / f(1 - cfg.beta2 ** t)
        v = v - f(cfg.learning_rate) * m_hat / (np.sqrt(s_hat) + f(cfg.eps))
    return v


def run_kernels(size, *, dry_run):
    """Three pushes (duplicate keys, bucket pads) and a pull per variant."""
    import jax

    from parameter_server_tpu.config import OptimizerConfig, TableConfig
    from parameter_server_tpu.core.postoffice import Postoffice
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.utils.keys import HashLocalizer
    from parameter_server_tpu.utils.platform import bytes_in_use, role_device

    rows, pushes = size["kernel_rows"], 3
    opt = OptimizerConfig(kind="adam", learning_rate=0.01)
    rng = np.random.default_rng(0)
    distinct = rng.choice(8 * rows, size=size["kernel_keys"], replace=False)
    keys = np.concatenate([distinct, distinct[: size["kernel_dups"]]])
    keys = keys.astype(np.uint64)
    loc = HashLocalizer(rows)
    slots = loc.assign(keys)
    variants = [("xla", True, 16), ("xla", True, 128), ("xla", True, 2048)]
    variants += [
        ("pallas", fused, dim) for dim in (128, 2048) for fused in (True, False)
    ]
    pulled, row_cost, error_replies = {}, {}, 0
    for impl, fused, dim in variants:
        name = f"{impl}{'' if fused else '-3pass'}-d{dim}"
        cfgs = {
            "e": TableConfig(
                name="e", rows=rows, dim=dim, optimizer=opt, init_scale=0.01,
                scatter_impl=impl, fused_apply=fused,
            )
        }
        # the same gradients for every variant of one width: parity below
        grads = np.random.default_rng(dim).standard_normal(
            (keys.size, dim)
        ).astype(np.float32)
        van = LoopbackVan()
        try:
            gc.collect()
            devices = list(dict.fromkeys(role_device(i) for i in range(2)))
            before = [bytes_in_use(d) for d in devices]
            servers = [
                KVServer(
                    Postoffice(f"S{i}", van), cfgs, i, 2,
                    pallas_interpret=dry_run and impl == "pallas",
                )
                for i in range(2)
            ]
            tables = [s.tables["e"] for s in servers]
            jax.block_until_ready([(t.value, t.state) for t in tables])
            planes = 1 + len(tables[0].state)
            nominal = sum(t.nominal_bytes for t in tables)
            allocated = (
                None if before[0] is None
                else sum(bytes_in_use(d) - b for d, b in zip(devices, before))
            )
            row_cost[name] = print_row_cost(
                f"kernels {name} rows={rows} adam", rows, planes, nominal,
                allocated,
            )
            for t in tables:
                assert t._interpret is (dry_run and impl == "pallas"), name
            worker = KVWorker(
                Postoffice("W0", van), cfgs, 2, min_bucket=16,
                localizers={"e": loc},
            )
            init = worker.pull_sync("e", keys, timeout=120)
            for _ in range(pushes):
                worker.wait(worker.push("e", keys, grads), timeout=120)
            got = worker.pull_sync("e", keys, timeout=120)
            error_replies += worker.error_replies
            for s in servers:
                assert s.ledger is None or s.ledger.fatal is None, s.ledger.fatal
        finally:
            van.close()
        assert got.shape == (keys.size, dim) and np.isfinite(got).all(), name
        # the server sums duplicate keys (and colliding slots) per push
        uniq, inverse = np.unique(slots, return_inverse=True)
        combined = np.zeros((uniq.size, dim), np.float32)
        np.add.at(combined, inverse, grads)
        first = np.zeros((uniq.size, dim), np.float32)
        first[inverse] = init
        want = adam_reference(first, combined, opt, pushes)[inverse]
        np.testing.assert_allclose(
            got, want, rtol=REF_RTOL, atol=REF_ATOL,
            err_msg=f"{name} vs float32 NumPy Adam",
        )
        pulled[(impl, fused, dim)] = got
    assert error_replies == 0, error_replies
    parity = []
    for dim in (128, 2048):
        for fused in (True, False):
            np.testing.assert_allclose(
                pulled[("pallas", fused, dim)], pulled[("xla", True, dim)],
                rtol=IMPL_RTOL, atol=IMPL_ATOL,
                err_msg=f"pallas fused={fused} vs xla at dim {dim}",
            )
            parity.append(f"pallas{'' if fused else '-3pass'}-d{dim}==xla")
    return {
        "variants": [f"{i}{'' if f else '-3pass'}-d{d}" for i, f, d in variants],
        "rows": rows, "keys": int(keys.size), "pushes_each": pushes,
        "error_replies": error_replies, "parity": parity,
        "bytes_per_row_per_plane": row_cost,
    }


# ------------------------------------------------------------------ phase e --


def run_other_apps(tmp, n_devices):
    """Two steps of every other registered app at its tests/test_app.py
    size: ring attention's shard_map/ppermute, the hybrid's embedding plane
    and the GSPMD bodies must lower on this backend."""
    from parameter_server_tpu import app as app_lib

    small = {"kind": "synthetic", "key_space": 256, "nnz": 2,
             "batch_size": 512, "seed": 0}
    emb = {"name": "emb", "rows": 256, "dim": 1,
           "optimizer": {"kind": "adagrad"}}
    sp, tp = (n_devices // 2, 2) if n_devices % 2 == 0 else (n_devices, 1)
    raws = [
        {"app": "sparse_lr",
         "table": {"name": "w", "rows": 4096,
                   "optimizer": {"kind": "adagrad", "learning_rate": 0.1}},
         "data": {"kind": "synthetic", "key_space": 8192, "nnz": 8,
                  "batch_size": 256, "seed": 1}},
        {"app": "fm",
         "table": {"name": "fm", "rows": 64, "dim": 3, "init_scale": 0.1,
                   "optimizer": {"kind": "adagrad", "learning_rate": 0.1}},
         "data": {"kind": "synthetic", "key_space": 128, "nnz": 4,
                  "batch_size": 64}},
        {"app": "llama_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 2},
         "topology": {"num_servers": 2}},
        {"app": "kimi_linear_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 1},
         "topology": {"num_servers": 2}},
        {"app": "lfm2_moe_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 1},
         "topology": {"num_servers": 2}},
        {"app": "laguna_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 1},
         "topology": {"num_servers": 2}},
        {"app": "sdar_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 1},
         "topology": {"num_servers": 2}},
        {"app": "qwen3_next_hybrid", "table": emb, "data": small,
         "consistency": {"mode": "ssp", "max_delay": 1},
         "topology": {"num_servers": 2}},
        {"app": "sp_lm", "table": emb, "data": small},
        {"app": "sptp_lm", "table": emb, "data": small,
         "topology": {"mesh_shape": [sp, tp]}},
    ]
    ran = {}
    for raw in raws:
        cfg = load_cfg(tmp, raw["app"], {**raw, "steps": 2})
        losses = np.asarray(app_lib.create(cfg)()["losses"], np.float64)
        assert losses.shape == (2,) and np.isfinite(losses).all(), (
            raw["app"], losses,
        )
        ran[raw["app"]] = [round(float(x), 4) for x in losses]
    missing = set(app_lib.registered_apps()) - set(ran) - {"async_lr"}
    assert not missing, f"registered apps the smoke does not run: {missing}"
    return {"apps": ran}


# --------------------------------------------------------------------- main --


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--dry-run", action="store_true",
        help="sandbox check: tiny sizes on 4 virtual CPU devices, Pallas in "
        "the interpreter; prints no pass",
    )
    args = parser.parse_args()

    from parameter_server_tpu import native
    from parameter_server_tpu.utils import platform

    if args.dry_run:
        platform.force_cpu(4)
    cache_dir = platform.enable_compile_cache()
    import jax
    import jaxlib

    device = platform.device_stamp()
    if not args.dry_run and device["platform"] != "tpu":
        print(f"chip_smoke: jax found no TPU ({device}); nothing was run",
              file=sys.stderr)
        return 1
    n_local = jax.local_device_count()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    entries_before = cache_entries(cache_dir)
    # the chip path takes no quiet Python fallback for key localization
    native.load("keymap", required=True)
    print(json.dumps({
        "platform": device["platform"], "device_kind": device["kind"],
        "local_devices": n_local, "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": libtpu,
        "compile_cache": cache_dir, "cache_entries_before": entries_before,
        "native": native.loaded(), "dry_run": args.dry_run,
    }), flush=True)

    size = TINY if args.dry_run else FULL
    meter, report = CompileMeter(), {}
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        with phase("a", meter, report) as counts:
            got, losses_a = run_async_lr(
                tmp, size, workers=2, servers=2, tag="a"
            )
            counts.update(got)
        row_cost = {}
        for sid, s in report["a"]["servers_placed"].items():
            row_cost[f"async_lr-d1/{sid}"] = print_row_cost(
                f"async_lr {sid} on {s['device']} rows={s['rows']} dim=1 "
                "adagrad", s["rows"], 2, s["nominal_bytes"],
                s["allocated_bytes"],
            )

        with phase("b", meter, report) as counts:
            counts.update(run_kernels(size, dry_run=args.dry_run))
        row_cost.update(report["b"].pop("bytes_per_row_per_plane"))
        report["c"] = {"bytes_per_row_per_plane": row_cost}

        if n_local >= 4:
            with phase("d", meter, report) as counts:
                got, losses_d = run_async_lr(
                    tmp, size, workers=4, servers=4, tag="d"
                )
                counts.update(got)
            placed = report["d"]["servers_placed"]
            assert len({s["device"] for s in placed.values()}) == 4, placed
            for sid, s in placed.items():
                # dry run: the CPU allocator reports nothing to compare
                assert args.dry_run or s["allocated_bytes"] >= s["nominal_bytes"], (
                    sid, s,
                )
            assert losses_d[0] == losses_a[0], (losses_d[0], losses_a[0])
            four_chip = {"devices": sorted(s["device"] for s in placed.values()),
                         "first_loss_equals_one_chip": True}
        else:
            four_chip = f"not run: {n_local} device"

        with phase("e", meter, report) as counts:
            counts.update(run_other_apps(tmp, len(jax.devices())))

    summary = {
        "device": device,
        "phases": report,
        "four_chip": four_chip,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": cache_entries(cache_dir),
        },
        "compile_s": round(
            sum(p.get("compile_s", 0.0) for p in report.values()), 2
        ),
        "wall_s": round(time.perf_counter() - t_start, 2),
        "native": native.loaded(),
        "claim": None,
    }
    print(f"[summary] {json.dumps({'dry_run': args.dry_run, **summary})}",
          flush=True)
    if not args.dry_run:
        # the result line: these two keys and nothing else, printed last.  A
        # CPU run is never a pass, so a dry run prints no such line at all.
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
