"""Dual-plane config #5 launch: TcpVan embedding plane + jax.distributed body.

The deployment shape BASELINE config #5 actually describes (SURVEY.md §5
two-plane design; the composition VERDICT r3 flagged as never-run): KVServers
serving the embedding table live in their OWN OS processes on the native
TcpVan (wire filters on), while the transformer body runs as a
``jax.distributed`` GSPMD job across N more processes — two independent
communication planes crossing real process boundaries:

- **embedding plane (DCN analogue)**: every body process registers as a Van
  worker and pulls/pushes ONLY its ``local_batch_slice`` of every global
  batch over real sockets (key-cached, int8-quantized, zlib-compressed);
- **dense plane (ICI analogue)**: the body processes form one global mesh;
  XLA/Gloo inserts the gradient allreduce inside the jit step.

Consistency across the plane: ``--bsp`` (default) drains every push and
barriers the body processes (``sync_global_devices``) each step, so all
pushes land before anyone's next pull — the cross-process run then matches
the in-process hybrid loss-for-loss (with an ``sgd`` embedding optimizer the
two-halves-pushed-separately update equals the one-push update up to float
summation order).  ``--no-bsp`` enables the production overlap instead:
``max_delay`` pushes in flight, prefetched pulls — bounded staleness, no
parity guarantee (the reference's SSP regime).

Roles mirror ``launch.py`` (scheduler H / servers S* / bodies W*); the
scheduler is the same Manager barrier host.

This is a CPU-simulation harness for the wire and for ``jax.distributed``:
every process it spawns is pinned to the CPU by environment (several
processes on one host would race for the same chips), and ``cpu_devices=0``
is refused.  On a chip the hybrid runs in one process (``psx run`` a
``llama_hybrid`` config).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Optional

from parameter_server_tpu.core.filters import DEFAULT_SPEC

from parameter_server_tpu.launch import (
    _build_cluster,
    _free_port,
    _log,
    run_scheduler,
)


def _tfm_cfg(args):
    from parameter_server_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=args.vocab,
        n_layers=args.layers,
        n_heads=args.heads,
        d_model=args.d_model,
        d_ff=args.d_ff,
        max_seq=args.seq,
        causal=True,
        tie_embeddings=False,
    )


def _table_cfgs(args):
    from parameter_server_tpu.learner import hybrid

    return {
        "emb": hybrid.embedding_table_cfg(
            _tfm_cfg(args),
            learning_rate=args.emb_lr,
            optimizer=args.emb_optimizer,
        )
    }


def run_server(args) -> int:
    """One embedding KVServer shard in its own process (TcpVan, filters)."""
    from parameter_server_tpu.kv.server import KVServer

    index = int(args.node_id[1:])
    van, post, mgr, _server = _build_cluster(
        args,
        0,
        setup=lambda post: KVServer(
            post, _table_cfgs(args), index, args.num_servers
        ),
    )
    try:
        _log(args, "emb shard serving; waiting on shutdown barrier")
        n_nodes = args.num_workers + args.num_servers
        ok = mgr.barrier("shutdown", n_nodes + 1, timeout=args.run_timeout)
        _log(args, f"shutdown barrier -> {ok}")
        return 0
    finally:
        van.close()


def run_body(args) -> int:
    """One GSPMD body process: mesh member AND Van embedding worker."""
    from parameter_server_tpu.parallel import distributed

    proc_id = int(args.node_id[1:])
    # dense plane first: jax.distributed must initialize before any backend
    # use; the Van attaches afterwards (independent plane)
    distributed.initialize(
        args.coordinator, args.num_workers, proc_id,
        cpu_devices=args.cpu_devices,
    )
    import numpy as np
    from jax.experimental import multihost_utils

    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.learner import hybrid

    cfg = _tfm_cfg(args)
    mesh = distributed.global_mesh()
    van, post, mgr, _ = _build_cluster(args, 0)
    try:
        worker = KVWorker(
            post,
            _table_cfgs(args),
            args.num_servers,
            localizers=hybrid.embedding_localizers(cfg),
        )
        tr = hybrid.HybridLMTrainer(
            cfg,
            mesh,
            worker,
            learning_rate=args.lr,
            max_delay=0 if args.bsp else args.max_delay,
            seed=args.seed,
        )
        # deterministic global batch stream, identical on every body process
        # (the reference's coordination-free WorkloadPool determinism)
        rng = np.random.default_rng(args.seed + 1)
        batches = [
            rng.integers(
                0, cfg.vocab_size, size=(args.global_batch, args.seq)
            ).astype(np.int32)
            for _ in range(args.steps + 1)
        ]
        _log(args, f"training on mesh {dict(mesh.shape)}")
        losses = []
        for s in range(args.steps):
            nxt = None if args.bsp else batches[s + 1]
            loss = tr.step(batches[s], next_tokens=nxt)
            if args.bsp:
                # BSP across the embedding plane: all pushes applied (drain
                # acks) on every process before anyone's next pull
                tr.drain()
                multihost_utils.sync_global_devices(f"emb-step{s}")
            losses.append(loss)
        tr.drain()
        if args.outdir:
            chain = getattr(van, "filter_chain", None)
            out = os.path.join(args.outdir, f"{args.node_id}.json")
            with open(out, "w") as f:
                json.dump(
                    {
                        "node": args.node_id,
                        "losses": losses,
                        # socket + colocated-shm-ring bytes: the cross-
                        # process traffic proof must not read zero just
                        # because colocated links negotiated the fast path
                        "wire_sent": van.payload_bytes_sent(),
                        "wire_recv": van.payload_bytes_recv(),
                        "filter_overhead": (
                            chain.overhead() if chain is not None else None
                        ),
                    },
                    f,
                )
        n_nodes = args.num_workers + args.num_servers
        ok = mgr.barrier("shutdown", n_nodes + 1, timeout=args.run_timeout)
        _log(args, f"shutdown barrier -> {ok}")
        return 0
    finally:
        van.close()


def launch_hybrid(
    *,
    num_body: int = 2,
    cpu_devices: int = 4,
    num_servers: int = 2,
    steps: int = 4,
    vocab: int = 256,
    layers: int = 2,
    heads: int = 2,
    d_model: int = 32,
    d_ff: int = 64,
    seq: int = 16,
    global_batch: int = 8,
    lr: float = 1e-3,
    emb_lr: float = 0.05,
    emb_optimizer: str = "adagrad",
    bsp: bool = True,
    max_delay: int = 2,
    seed: int = 0,
    filters: str = DEFAULT_SPEC,
    run_timeout: float = 300.0,
    python: str = sys.executable,
) -> dict:
    """Spawn the dual-plane job: scheduler + emb servers + GSPMD bodies.

    Returns per-body losses and true socket byte counters (the evidence
    that embedding traffic crossed process boundaries).
    """
    from parameter_server_tpu.core.filters import make_chain

    make_chain(filters)  # validate the spec HERE, not in five children
    if cpu_devices <= 0:
        raise ValueError(
            "cpu_devices=0: the body and server processes on this host "
            "would all claim the same chips; this launcher is a CPU "
            "simulation (cpu_devices>0)"
        )
    sched_port = _free_port()
    coord_port = _free_port()
    outdir = tempfile.mkdtemp(prefix="psx_hybrid_")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pypath = os.environ.get("PYTHONPATH", "")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=f"{repo_root}:{pypath}" if pypath else repo_root,
    )

    def spawn(role: str, node_id: str) -> subprocess.Popen:
        cmd = [
            python, "-m", "parameter_server_tpu.launch_hybrid",
            "--role", role, "--node-id", node_id,
            "--scheduler-port", str(sched_port),
            "--coordinator", f"127.0.0.1:{coord_port}",
            "--num-body", str(num_body),
            "--cpu-devices", str(cpu_devices),
            "--num-servers", str(num_servers),
            "--steps", str(steps),
            "--vocab", str(vocab), "--layers", str(layers),
            "--heads", str(heads), "--d-model", str(d_model),
            "--d-ff", str(d_ff), "--seq", str(seq),
            "--global-batch", str(global_batch),
            "--lr", str(lr), "--emb-lr", str(emb_lr),
            "--emb-optimizer", emb_optimizer,
            "--max-delay", str(max_delay),
            "--seed", str(seed),
            "--filters", filters,
            "--outdir", outdir,
            "--run-timeout", str(run_timeout),
        ] + (["--bsp"] if bsp else ["--no-bsp"])
        return subprocess.Popen(cmd, env=env)

    procs = [spawn("scheduler", "H")]
    time.sleep(0.3)  # scheduler binds its fixed port first
    procs += [spawn("server", f"S{i}") for i in range(num_servers)]
    procs += [spawn("body", f"W{i}") for i in range(num_body)]

    deadline = time.monotonic() + run_timeout
    rcs = []
    try:
        for p in procs:
            try:
                rcs.append(
                    p.wait(timeout=max(deadline - time.monotonic(), 1.0))
                )
            except subprocess.TimeoutExpired:
                rcs.append(None)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass
    rcs = [p.poll() if rc is None else rc for rc, p in zip(rcs, procs)]
    losses = {}
    wire = {}
    overheads = {}
    for i in range(num_body):
        path = os.path.join(outdir, f"W{i}.json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            losses[i] = rec["losses"]
            wire[i] = {
                "sent": rec["wire_sent"], "recv": rec["wire_recv"],
            }
            overheads[i] = rec.get("filter_overhead")
    shutil.rmtree(outdir, ignore_errors=True)
    return {
        "returncodes": rcs,
        "losses": losses,
        "wire": wire,
        "filter_overhead": overheads,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--role", required=True,
                   choices=["scheduler", "server", "body"])
    p.add_argument("--node-id", required=True)
    p.add_argument("--scheduler-port", type=int, required=True)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--num-body", type=int, default=2)
    p.add_argument("--cpu-devices", type=int, default=4)
    p.add_argument("--num-servers", type=int, default=2)
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--d-ff", type=int, default=64)
    p.add_argument("--seq", type=int, default=16)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--emb-lr", type=float, default=0.05)
    p.add_argument("--emb-optimizer", default="adagrad")
    p.add_argument("--bsp", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--max-delay", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--filters", default=DEFAULT_SPEC)
    p.add_argument("--outdir", default=None)
    p.add_argument("--heartbeat-timeout", type=float, default=30.0)
    p.add_argument("--run-timeout", type=float, default=300.0)
    args = p.parse_args(argv)
    # Manager/launch code sizes barriers by num_workers: the bodies ARE the
    # workers of this topology
    args.num_workers = args.num_body
    from parameter_server_tpu.utils.platform import (
        enable_compile_cache,
        force_cpu,
    )

    if args.role != "body":
        # host-side roles never touch a chip (or jax.distributed); bodies
        # are pinned by distributed.initialize(cpu_devices=...)
        force_cpu()
    enable_compile_cache()
    return {
        "scheduler": run_scheduler,
        "server": run_server,
        "body": run_body,
    }[args.role](args)


if __name__ == "__main__":
    sys.exit(main())
