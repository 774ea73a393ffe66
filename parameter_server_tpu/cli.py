"""psx — the command-line launcher.

Reference analogue: ``script/local.sh`` + the gflags/`main.cc` entry point
(SURVEY.md §2 #23 [U]): one binary, behavior selected by config.  Here::

    psx run config.yaml [--steps N]     # run a registered app from a config
    psx eval CKPT_ROOT --table w ...    # offline AUC from a checkpoint
    psx apps                            # list registered apps

Installed as a console script (``pyproject.toml``) and runnable as
``python -m parameter_server_tpu.cli``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

from parameter_server_tpu.core.filters import DEFAULT_SPEC


def _cmd_run(args: argparse.Namespace) -> int:
    from parameter_server_tpu import app as app_lib

    cfg = app_lib.load_config(args.config)
    if args.steps is not None:
        cfg = dataclasses.replace(cfg, steps=args.steps)
    if getattr(args, "tail_filter", None) is not None:
        cfg = dataclasses.replace(
            cfg,
            data=dataclasses.replace(cfg.data, tail_threshold=args.tail_filter),
        )
    from parameter_server_tpu import native
    from parameter_server_tpu.utils.platform import device_stamp

    device = device_stamp()
    if device["platform"] == "tpu":
        # a chip run does not take the Python key path quietly: a failed
        # build raises here with the compiler's stderr
        native.load("keymap", required=True)
    run = app_lib.create(cfg)
    result = run()
    losses = result.pop("losses", [])
    if losses:
        result["first_loss"] = round(float(np.mean(losses[:10])), 6)
        result["final_loss"] = round(float(np.mean(losses[-10:])), 6)
    result["device"] = device
    result["native"] = native.loaded()
    print(json.dumps({"app": cfg.app, **result}))
    return 0


def _cmd_eval(args: argparse.Namespace) -> int:
    from parameter_server_tpu import evaluation
    from parameter_server_tpu.utils.keys import HashLocalizer

    from parameter_server_tpu.data.synthetic import SyntheticCTR

    stream = SyntheticCTR(
        key_space=args.key_space,
        nnz=args.nnz,
        batch_size=args.batch_size,
        seed=args.seed,
    )
    batches = [stream.next_batch() for _ in range(args.batches)]
    report = evaluation.evaluate_checkpoint(
        args.ckpt_root,
        args.table,
        batches,
        step=args.step,
        model=args.model,
        localizer=(
            HashLocalizer(args.rows, hash_bits=args.hash_bits or 64)
            if args.rows
            else None
        ),
        hash_bits=args.hash_bits or None,
    )
    print(json.dumps(report))
    return 0


def _cmd_serve(args: argparse.Namespace) -> int:
    """Run the psfs shard file server (reference file.h/HDFS host role)."""
    import threading

    from parameter_server_tpu.data.fs import FileServer

    srv = FileServer(
        args.root, host=args.host, port=args.port,
        advertise_host=args.advertise_host,
    ).start()
    print(json.dumps({"url": srv.url, "root": srv.root}), flush=True)
    try:
        threading.Event().wait()  # serve until interrupted
    except KeyboardInterrupt:
        srv.stop()
    return 0


def _cmd_apps(_args: argparse.Namespace) -> int:
    from parameter_server_tpu import app as app_lib

    for name in app_lib.registered_apps():
        print(name)
    return 0


_LAUNCH_HELP = (
    "CPU harness for the wire: spawn scheduler+servers+workers as "
    "OS processes over TcpVan, every role pinned to the CPU (chip runs "
    "of the PS stack are in-process: psx run)"
)


_SPMD_HELP = (
    "CPU simulation of a multi-host GSPMD job: N processes joined "
    "by jax.distributed, each on --cpu-devices virtual CPU devices "
    "(on a pod, start launch_spmd once per host instead)"
)


_HYBRID_HELP = (
    "CPU simulation of the dual-plane config #5: TcpVan embedding "
    "servers in their own processes + a jax.distributed GSPMD body, "
    "every process pinned to the CPU"
)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="psx", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run an app from a yaml/json config")
    run.add_argument("config")
    run.add_argument("--steps", type=int, default=None, help="override steps")
    run.add_argument(
        "--tail-filter", type=int, default=None, metavar="K",
        help="override data.tail_threshold: mask keys seen < K times "
        "(count-min tail filter on the input stream; 0 disables)",
    )
    run.set_defaults(fn=_cmd_run)

    ev = sub.add_parser("eval", help="offline eval of a saved checkpoint")
    ev.add_argument("ckpt_root")
    ev.add_argument("--table", default="w")
    ev.add_argument("--model", default="lr", choices=["lr", "fm"])
    ev.add_argument("--step", type=int, default=None)
    ev.add_argument("--rows", type=int, default=0, help="localizer capacity")
    ev.add_argument(
        "--hash-bits", type=int, default=0, choices=[0, 32, 64],
        help="hash width of the training localizer (0 = manifest/default); "
        "device-hash tables need 32",
    )
    ev.add_argument("--batches", type=int, default=8)
    ev.add_argument("--batch-size", type=int, default=1024)
    ev.add_argument("--key-space", type=int, default=1 << 22)
    ev.add_argument("--nnz", type=int, default=39)
    ev.add_argument("--seed", type=int, default=0)
    ev.set_defaults(fn=_cmd_eval)

    apps = sub.add_parser("apps", help="list registered apps")
    apps.set_defaults(fn=_cmd_apps)

    se = sub.add_parser(
        "serve",
        help="serve a shard directory over psfs:// (readers stream from it)",
    )
    se.add_argument("root")
    se.add_argument("--host", default="0.0.0.0")
    se.add_argument("--port", type=int, default=0)
    se.add_argument("--advertise-host", default="127.0.0.1")
    se.set_defaults(fn=_cmd_serve)

    la = sub.add_parser(
        "launch",
        help=_LAUNCH_HELP,
        description=_LAUNCH_HELP,
    )
    la.add_argument("--workers", type=int, default=2)
    la.add_argument("--servers", type=int, default=2)
    la.add_argument("--steps", type=int, default=20)
    la.add_argument("--rows", type=int, default=1 << 14)
    la.add_argument("--batch-size", type=int, default=256)
    la.add_argument("--ckpt-root", default=None)
    la.add_argument(
        "--filters", default=DEFAULT_SPEC,
        help="wire filter stack on the TcpVan: 'none' to opt out, "
        "'lossless' (=key_caching+zlib, default — bit-exact wire), 'full' "
        "(adds the LOSSY int8 quantizer; explicit opt-in), or a "
        "'+'-joined subset of {key_caching, int8, zlib, noise}",
    )
    la.set_defaults(fn=_cmd_launch)

    sp = sub.add_parser(
        "launch-spmd",
        help=_SPMD_HELP,
        description=_SPMD_HELP,
    )
    sp.add_argument("--num-procs", type=int, default=2)
    sp.add_argument("--cpu-devices", type=int, default=4,
                    help="virtual CPU devices per process; 0 (the host's "
                    "chips) is an error with more than one process")
    sp.add_argument("--steps", type=int, default=8)
    sp.add_argument("--rows", type=int, default=1 << 12)
    sp.add_argument("--global-batch", type=int, default=256)
    sp.add_argument("--mesh-data", type=int, default=2)
    sp.set_defaults(fn=_cmd_launch_spmd)

    hy = sub.add_parser(
        "launch-hybrid",
        help=_HYBRID_HELP,
        description=_HYBRID_HELP,
    )
    hy.add_argument("--num-body", type=int, default=2)
    hy.add_argument("--cpu-devices", type=int, default=4,
                    help="virtual CPU devices per body process (> 0)")
    hy.add_argument("--num-servers", type=int, default=2)
    hy.add_argument("--steps", type=int, default=4)
    hy.add_argument("--vocab", type=int, default=256)
    hy.add_argument("--layers", type=int, default=2)
    hy.add_argument("--heads", type=int, default=4)
    hy.add_argument("--d-model", type=int, default=32)
    hy.add_argument("--d-ff", type=int, default=64)
    hy.add_argument("--seq", type=int, default=16)
    hy.add_argument("--global-batch", type=int, default=8)
    hy.add_argument("--emb-optimizer", default="adagrad")
    hy.add_argument("--bsp", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="barrier the embedding plane every step (parity "
                    "mode, the default — matches launch_hybrid()); "
                    "--no-bsp enables the SSP overlap shape")
    hy.add_argument("--max-delay", type=int, default=2)
    hy.add_argument("--filters", default=DEFAULT_SPEC)
    hy.set_defaults(fn=_cmd_launch_hybrid)
    return p


def _cmd_launch_hybrid(args: argparse.Namespace) -> int:
    from parameter_server_tpu.launch_hybrid import launch_hybrid

    result = launch_hybrid(
        num_body=args.num_body,
        cpu_devices=args.cpu_devices,
        num_servers=args.num_servers,
        steps=args.steps,
        vocab=args.vocab, layers=args.layers, heads=args.heads,
        d_model=args.d_model, d_ff=args.d_ff, seq=args.seq,
        global_batch=args.global_batch,
        emb_optimizer=args.emb_optimizer,
        bsp=args.bsp, max_delay=args.max_delay,
        filters=args.filters,
    )
    losses = result["losses"].get(0, [])
    print(json.dumps({
        "returncodes": result["returncodes"],
        "losses": losses,
        "wire": result["wire"],
    }))
    return 0 if all(rc == 0 for rc in result["returncodes"]) else 1


def _cmd_launch_spmd(args: argparse.Namespace) -> int:
    from parameter_server_tpu.launch_spmd import launch_spmd

    result = launch_spmd(
        num_procs=args.num_procs,
        cpu_devices=args.cpu_devices,
        steps=args.steps,
        rows=args.rows,
        global_batch=args.global_batch,
        mesh_data=args.mesh_data,
    )
    losses = result["losses"].get(0, [])
    print(json.dumps({
        "returncodes": result["returncodes"],
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
    }))
    return 0 if all(rc == 0 for rc in result["returncodes"]) else 1


def _cmd_launch(args: argparse.Namespace) -> int:
    from parameter_server_tpu.launch import launch

    result = launch(
        num_workers=args.workers,
        num_servers=args.servers,
        steps=args.steps,
        rows=args.rows,
        batch_size=args.batch_size,
        ckpt_root=args.ckpt_root,
        filters=args.filters,
    )
    print(json.dumps(result))
    return 0 if all(rc == 0 for rc in result["returncodes"]) else 1


def main(argv=None) -> int:
    from parameter_server_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
