"""Multi-host SPMD job launch: one process per pod host, global GSPMD mesh.

The counterpart of ``launch.py`` (which spawns the *PS role* topology over
TcpVan) for the pure-GSPMD data plane: a v5e-16 pod runs 4 host processes,
each owning 4 chips, joined by ``jax.distributed`` into one global mesh
(SURVEY.md §7 step 4; VERDICT r1 missing #2).  On dev machines the same job
runs as N processes x K virtual CPU devices — identical program, Gloo
collectives instead of ICI.

Per-process flow (:func:`main`): ``distributed.initialize`` -> global
``(data, model)`` mesh -> :class:`~parameter_server_tpu.parallel.lr_spmd.SpmdLRTrainer`
row-sharded across all hosts -> each step, every process generates the SAME
deterministic global batch (seeded stream, the reference's WorkloadPool
determinism) and feeds only its :func:`~parameter_server_tpu.parallel.distributed.local_batch_slice`
of it.  Process 0 writes the loss trajectory for the launcher to aggregate.

``launch_spmd`` spawns the whole job locally and returns the losses — used
by tests and ``__graft_entry__.dryrun_multichip`` to prove multi-process
GSPMD training matches single-process loss-for-loss.  It is a CPU-simulation
harness for ``jax.distributed``: several processes on one host would race
for the same chips, so it refuses ``cpu_devices=0`` with more than one
process and pins its children to the CPU by environment.  On a real pod,
start ``python -m parameter_server_tpu.launch_spmd`` once per HOST.
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

from parameter_server_tpu.launch import _free_port


def _assign_shards(num_procs: int, n_shards: int) -> dict:
    """Deterministic WorkloadPool shard assignment, same on every process.

    Every process replays the identical request order against a local
    :class:`~parameter_server_tpu.learner.workload.WorkloadPool`, so the
    assignment is coordination-free (no scheduler RPC needed for the static
    SPMD schedule) yet uses the same pool machinery the PS topology uses
    dynamically.  Shards are CONTIGUOUS blocks per process — shard i is
    global-batch rows [i*B/n, (i+1)*B/n), and a process's devices address a
    contiguous 1/num_procs slice — and the shard streams themselves are
    process-count-independent, so a 1-process job and an N-process job see
    byte-identical global batches (the mesh-shape-defined-program invariant).
    """
    from parameter_server_tpu.learner.workload import WorkloadPool

    if n_shards % num_procs:
        raise ValueError(f"data shards {n_shards} % procs {num_procs} != 0")
    per = n_shards // num_procs
    pool = WorkloadPool(list(range(n_shards)))
    assignment: dict = {}
    for p in range(num_procs):  # block order: proc p owns [p*per, (p+1)*per)
        assignment[p] = [pool.get(f"proc{p}").payload for _ in range(per)]
    return assignment


def _ckpt_path(root: str, step: int) -> str:
    return os.path.join(root, f"spmd_step{step:06d}.npz")


def _latest_ckpt_step(root: str) -> Optional[int]:
    if not root or not os.path.isdir(root):
        return None
    steps = []
    for name in os.listdir(root):
        if name.startswith("spmd_step") and name.endswith(".npz"):
            steps.append(int(name[len("spmd_step") : -4]))
    return max(steps) if steps else None


def run_job(
    *,
    coordinator: Optional[str],
    num_procs: int,
    proc_id: int,
    cpu_devices: int,
    steps: int,
    rows: int,
    global_batch: int,
    nnz: int,
    mesh_data: int,
    seed: int = 0,
    data_shards: Optional[int] = None,
    ckpt_root: Optional[str] = None,
    ckpt_every: int = 0,
    resume: bool = False,
    die_after_step: Optional[int] = None,
    die_proc: int = 1,
) -> dict:
    """One process's share of the SPMD LR job.

    Returns ``{"losses": [...], "data_digest": ..., "start_step": ...}``.
    Losses are global (replicated out of the jit step), so every process
    returns the same trajectory — asserting them equal across processes is
    part of the test contract.

    Data is genuinely PER-PROCESS sharded (VERDICT r2 #6): each process owns
    WorkloadPool-assigned shard streams and generates ONLY its local share
    of every global batch — no generate-everything-and-slice.  With
    ``ckpt_root``/``ckpt_every`` the full sharded state checkpoints every K
    steps (barriered, then process 0 writes atomically); ``resume`` restarts
    from the newest checkpoint with data streams fast-forwarded, which is
    how a killed process (or whole job) rejoins.  ``die_after_step`` is the
    fault-injection hook: ``die_proc`` exits hard after that step.
    """
    from parameter_server_tpu.parallel import distributed

    distributed.initialize(
        coordinator, num_procs, proc_id, cpu_devices=cpu_devices
    )
    import jax
    import jax.numpy as jnp
    import numpy as np_  # shadow-proof alias under the function scope
    from jax.experimental import multihost_utils

    from parameter_server_tpu.config import OptimizerConfig, TableConfig
    from parameter_server_tpu.data.synthetic import SyntheticCTR
    from parameter_server_tpu.parallel import lr_spmd

    n_dev = len(jax.devices())
    if n_dev % mesh_data:
        raise ValueError(f"{n_dev} devices not divisible by data={mesh_data}")
    mesh = distributed.global_mesh((mesh_data, n_dev // mesh_data))
    cfg = TableConfig(
        name="w",
        rows=rows,
        dim=1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
    trainer = lr_spmd.SpmdLRTrainer(cfg, mesh, seed=seed)

    # -- per-process data shards (each proc generates ONLY its share) -------
    # A process feeds the batch rows its own devices address.  When the data
    # axis spans the processes (mesh_data >= num_procs) each process
    # generates exactly its own shards; otherwise (batch replicated along
    # the model axis) every process must feed the full batch, i.e. it owns
    # ALL shards — the streams are identical either way, so the global batch
    # is process-count-invariant.
    n_shards = data_shards or max(2 * num_procs, 4)
    if global_batch % n_shards:
        raise ValueError(f"global_batch {global_batch} % shards {n_shards}")
    shard_batch = global_batch // n_shards
    sharded_feed = mesh_data >= num_procs and mesh_data % num_procs == 0
    if sharded_feed:
        my_shards = _assign_shards(num_procs, n_shards)[proc_id]
    else:
        my_shards = list(range(n_shards))

    def _stream(shard: int) -> SyntheticCTR:
        return SyntheticCTR(
            key_space=4 * rows, nnz=nnz, batch_size=shard_batch,
            seed=seed + 7919 * (shard + 1),
        )

    streams = {shard: _stream(shard) for shard in my_shards}
    digest = None  # first local batch fingerprint (test observability)

    # -- resume --------------------------------------------------------------
    start_step = 0
    if resume and ckpt_root:
        last = _latest_ckpt_step(ckpt_root)
        if last is not None:
            with np_.load(_ckpt_path(ckpt_root, last)) as z:
                host_state = {k: z[k] for k in z.files}
            st = trainer.state
            shardings = jax.tree.map(lambda a: a.sharding, st)

            def put(np_arr, sharding):
                return jax.make_array_from_callback(
                    np_arr.shape, sharding, lambda idx: np_arr[idx]
                )

            trainer.state = lr_spmd.ShardedLRState(
                value=put(host_state["value"], shardings.value),
                state={
                    k: put(host_state[f"state.{k}"], shardings.state[k])
                    for k in st.state
                },
                bias=put(host_state["bias"], shardings.bias),
                bias_state={
                    k: put(host_state[f"bias_state.{k}"], shardings.bias_state[k])
                    for k in st.bias_state
                },
            )
            start_step = last
    # absolute-step indexed feeding: regenerate and skip consumed batches so
    # a resumed run sees exactly the batches the lost steps would have seen
    for _ in range(start_step):
        for stream in streams.values():
            stream.next_batch()

    losses = []
    for s in range(start_step, steps):
        parts = [streams[sh].next_batch() for sh in my_shards]
        keys = np_.concatenate([p[0] for p in parts])
        labels = np_.concatenate([p[1] for p in parts])
        if digest is None:
            digest = int(np_.asarray(keys, dtype=np_.uint64).sum())
        losses.append(trainer.step(keys, labels, global_batch=global_batch))
        done = s + 1
        if ckpt_root and ckpt_every and done % ckpt_every == 0 and done < steps:
            # gather the full state on every process; proc 0 writes atomically
            full = jax.tree.map(
                lambda a: np_.asarray(multihost_utils.process_allgather(a, tiled=True)),
                trainer.state,
            )
            if proc_id == 0:
                os.makedirs(ckpt_root, exist_ok=True)
                arrays = {"value": full.value, "bias": full.bias}
                arrays.update({f"state.{k}": v for k, v in full.state.items()})
                arrays.update(
                    {f"bias_state.{k}": v for k, v in full.bias_state.items()}
                )
                tmp = _ckpt_path(ckpt_root, done) + ".tmp"
                with open(tmp, "wb") as f:
                    np_.savez(f, **arrays)
                os.replace(tmp, _ckpt_path(ckpt_root, done))
            multihost_utils.sync_global_devices(f"ckpt{done}")
        if (
            die_after_step is not None
            and (die_proc < 0 or proc_id == die_proc)
            and done == die_after_step
        ):
            # fault injection: hard kill mid-job.  die_proc=-1 kills EVERY
            # process at that step (a whole-job death): a single-proc kill
            # leaves the survivors blocked in the next Gloo collective until
            # the launch timeout, which is realistic but burns minutes of
            # suite wall clock (ADVICE r3) — resume semantics are identical.
            os._exit(17)
    return {"losses": losses, "data_digest": digest, "start_step": start_step}


def main(argv=None) -> int:
    from parameter_server_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--num-procs", type=int, default=1)
    p.add_argument("--proc-id", type=int, default=0)
    p.add_argument("--cpu-devices", type=int, default=0)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--rows", type=int, default=1 << 12)
    p.add_argument("--global-batch", type=int, default=256)
    p.add_argument("--nnz", type=int, default=8)
    p.add_argument("--mesh-data", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--outdir", default=None)
    p.add_argument("--data-shards", type=int, default=None)
    p.add_argument("--ckpt-root", default=None)
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--die-after-step", type=int, default=None)
    p.add_argument("--die-proc", type=int, default=1)
    args = p.parse_args(argv)
    result = run_job(
        coordinator=args.coordinator,
        num_procs=args.num_procs,
        proc_id=args.proc_id,
        cpu_devices=args.cpu_devices,
        steps=args.steps,
        rows=args.rows,
        global_batch=args.global_batch,
        nnz=args.nnz,
        mesh_data=args.mesh_data,
        seed=args.seed,
        data_shards=args.data_shards,
        ckpt_root=args.ckpt_root,
        ckpt_every=args.ckpt_every,
        resume=args.resume,
        die_after_step=args.die_after_step,
        die_proc=args.die_proc,
    )
    if args.outdir:
        path = os.path.join(args.outdir, f"proc{args.proc_id}.json")
        with open(path, "w") as f:
            json.dump({"proc": args.proc_id, **result}, f)
    return 0


def launch_spmd(
    *,
    num_procs: int = 2,
    cpu_devices: int = 4,
    steps: int = 8,
    rows: int = 1 << 12,
    global_batch: int = 256,
    nnz: int = 8,
    mesh_data: int = 2,
    seed: int = 0,
    timeout: float = 300.0,
    python: str = sys.executable,
    data_shards: Optional[int] = None,
    ckpt_root: Optional[str] = None,
    ckpt_every: int = 0,
    resume: bool = False,
    die_after_step: Optional[int] = None,
    die_proc: int = 1,
) -> dict:
    """Spawn the CPU-sim pod: ``num_procs`` processes x ``cpu_devices``.

    Returns ``{"returncodes": [...], "losses": {proc_id: [...]},
    "digests": {...}, "start_steps": {...}}``.
    """
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pypath = os.environ.get("PYTHONPATH", "")
    env = dict(
        os.environ,
        PYTHONPATH=f"{repo_root}:{pypath}" if pypath else repo_root,
    )
    if cpu_devices > 0:
        env["JAX_PLATFORMS"] = "cpu"
    elif num_procs > 1:
        raise ValueError(
            f"cpu_devices=0 with num_procs={num_procs}: every process on "
            "this host would claim the same chips.  Use cpu_devices>0 (CPU "
            "simulation) here, or start one process per host on a pod."
        )
    port = _free_port()
    outdir = tempfile.mkdtemp(prefix="psx_spmd_")

    extra = []
    if data_shards is not None:
        extra += ["--data-shards", str(data_shards)]
    if ckpt_root:
        extra += ["--ckpt-root", ckpt_root, "--ckpt-every", str(ckpt_every)]
    if resume:
        extra += ["--resume"]
    if die_after_step is not None:
        extra += [
            "--die-after-step", str(die_after_step), "--die-proc", str(die_proc)
        ]
    procs = [
        subprocess.Popen(
            [
                python, "-m", "parameter_server_tpu.launch_spmd",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-procs", str(num_procs),
                "--proc-id", str(i),
                "--cpu-devices", str(cpu_devices),
                "--steps", str(steps), "--rows", str(rows),
                "--global-batch", str(global_batch), "--nnz", str(nnz),
                "--mesh-data", str(mesh_data), "--seed", str(seed),
                "--outdir", outdir,
                *extra,
            ],
            env=env,
        )
        for i in range(num_procs)
    ]
    deadline = time.monotonic() + timeout
    rcs = []
    try:
        for p_ in procs:
            try:
                rcs.append(
                    p_.wait(timeout=max(deadline - time.monotonic(), 1.0))
                )
            except subprocess.TimeoutExpired:
                # e.g. the coordinator died and a peer hangs in initialize:
                # report which processes hung instead of raising, so callers
                # see the real failing rc alongside the -9s
                rcs.append(None)
    finally:
        for p_ in procs:
            if p_.poll() is None:
                p_.kill()
        for p_ in procs:
            # reap: SIGKILL delivery is asynchronous, so an immediate poll()
            # can still read None — wait bounds it and makes the reported
            # returncode deterministically -9 (ADVICE r2)
            if p_.poll() is None:
                try:
                    p_.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    pass  # unkillable (D-state): leave rc as None
    rcs = [p_.poll() if rc is None else rc for rc, p_ in zip(rcs, procs)]
    losses = {}
    digests = {}
    start_steps = {}
    for i in range(num_procs):
        path = os.path.join(outdir, f"proc{i}.json")
        if os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            losses[i] = rec["losses"]
            digests[i] = rec.get("data_digest")
            start_steps[i] = rec.get("start_step", 0)
    shutil.rmtree(outdir, ignore_errors=True)
    return {
        "returncodes": rcs,
        "losses": losses,
        "digests": digests,
        "start_steps": start_steps,
    }


if __name__ == "__main__":
    sys.exit(main())
