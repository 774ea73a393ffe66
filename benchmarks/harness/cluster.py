"""The in-process PS cluster every driver runs on, wired the way
``parameter_server_tpu/app.py::_build_async_lr`` wires it: scheduler +
servers + workers over ``MeteredVan(LoopbackVan())`` from
``launch_local_cluster``, a ``FleetMonitor`` on the scheduler, server ``i``
and worker ``i`` on local device ``i % n`` (``utils.platform.role_device``),
keys localised as the configuration's ``table.localizer`` says
(``harness/keys.py``).  Sizes and counts come from the caller."""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict


@dataclasses.dataclass
class Cluster:
    van: object
    sched: object
    managers: Dict[str, object]
    servers: Dict[str, object]
    workers: Dict[str, object]
    table: object  # TableConfig
    placement: Dict[str, dict]
    #: the key-to-row map the workers were built with (``harness/keys.py``):
    #: the reference check and the byte model find rows by it
    localizer: str

    def close(self) -> None:
        for srv in self.servers.values():
            if srv.ledger is not None:
                srv.ledger.close()
        self.van.close()


def consistency_config(spec: dict):
    """``ConsistencyConfig`` from a ``{"mode", "max_delay"}`` group."""
    from parameter_server_tpu.config import ConsistencyConfig, ConsistencyMode

    return ConsistencyConfig(
        mode=ConsistencyMode(spec["mode"]), max_delay=spec["max_delay"]
    )


def table_config(spec: dict, rows: int):
    """``TableConfig`` from a configuration file's ``table`` group."""
    from parameter_server_tpu.config import OptimizerConfig, TableConfig

    cons = spec.get("consistency")
    return TableConfig(
        name=spec["name"],
        rows=rows,
        dim=spec["dim"],
        dtype=spec.get("dtype", "float32"),
        optimizer=OptimizerConfig(**spec["optimizer"]),
        init_scale=spec.get("init_scale", 0.0),
        consistency=consistency_config(cons) if cons else None,
    )


def program_localizer(kind: str, rows: int):
    """The program's localizer of that kind, for ``KVWorker``."""
    from parameter_server_tpu.utils import keys as program_keys

    cls = {"hash": program_keys.HashLocalizer,
           "identity": program_keys.IdentityLocalizer}[kind]
    return cls(rows)


def build_cluster(table, *, workers: int, servers: int, localizer: str,
                  **server_kwargs) -> Cluster:
    """``localizer``: what ``keys.localizer_of`` read from the configuration.
    ``server_kwargs``: keyword arguments of ``KVServer`` a driver needs (the
    hybrid path's ``device_replies``); none by default."""
    import jax

    from parameter_server_tpu.core.fleet import FleetMonitor
    from parameter_server_tpu.core.manager import launch_local_cluster
    from parameter_server_tpu.core.messages import server_id, worker_id
    from parameter_server_tpu.core.netmon import MeteredVan
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker

    van = MeteredVan(LoopbackVan())
    try:
        sched, managers, posts = launch_local_cluster(
            van, num_workers=workers, num_servers=servers
        )
        sched.fleet = FleetMonitor()
        tables = {table.name: table}
        loc = {table.name: program_localizer(localizer, table.rows)}
        srvs, placement = {}, {}
        for i in range(servers):
            srv = KVServer(
                posts[server_id(i)], tables, i, servers, **server_kwargs
            )
            tbl = srv.tables[table.name]
            jax.block_until_ready((tbl.value, tbl.state))
            srvs[server_id(i)] = srv
            placement[server_id(i)] = {
                "device": str(srv.device), "rows": tbl.rows,
                "planes": 1 + len(tbl.state),
                "nominal_bytes": tbl.nominal_bytes,
            }
        wrks = {
            worker_id(i): KVWorker(
                posts[worker_id(i)], tables, servers, localizers=loc
            )
            for i in range(workers)
        }
    except BaseException:
        van.close()
        raise
    return Cluster(van, sched, managers, srvs, wrks, table, placement,
                   localizer)


def in_background(fn, *args, **kwargs):
    """Start ``fn(*args, **kwargs)`` on a thread; the returned callable
    joins it and gives its result or raises what it raised."""
    box = {}

    def work():
        try:
            box["value"] = fn(*args, **kwargs)
        except BaseException as e:  # re-raised by the caller below
            box["error"] = e

    t = threading.Thread(target=work, name="bench-background", daemon=True)
    t.start()

    def result():
        t.join()
        if "error" in box:
            raise box["error"]
        return box["value"]

    return result


def cluster_and_batches(run, **server_kwargs):
    """What every driver's set-up starts with: the configuration's table,
    the cluster at the cell's topology with the workers' localizer the
    configuration states (read here, once), and the cell's batches from its
    generator.  ``server_kwargs`` go to every ``KVServer`` (``build_cluster``).
    NumPy makes the batches on a thread while the tables are made on the
    device.  Returns ``(table, cluster, batches, keys_of)``."""
    from benchmarks.harness.cell import load_module
    from benchmarks.harness.keys import localizer_of

    sz = run.sizes
    cfg_file = next(
        c["file"] for c in run.bench["configs"] if c["name"] == run.config_name
    )
    localizer = localizer_of(run.config["table"], cfg_file)
    table = table_config(run.config["table"], sz["rows"])
    gen = load_module("generators", run.config["generator"], run.bench_dir)
    making = in_background(
        gen.make, dict(run.config["generator_params"], key_space=sz["rows"]),
        run.traffic, seed=run.seed, n_workers=sz["workers"],
        cycle=sz["cycle"], batch=sz["batch"],
    )
    cluster = build_cluster(
        table, workers=sz["workers"], servers=sz["servers"],
        localizer=localizer, **server_kwargs,
    )
    try:
        return table, cluster, making(), gen.keys_of
    except BaseException:
        cluster.close()
        raise


class Heartbeats:
    """What ``ElasticTrainer.run`` starts around its worker loops, for a
    driver whose loop is the benchmark's own: every managed node reports to
    the scheduler each ``interval`` seconds and the scheduler's monitor
    sweeps for silent ones."""

    def __init__(self, cluster: Cluster, interval: float = 0.5) -> None:
        self.cluster, self.interval = cluster, interval
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="bench-heartbeat", daemon=True
        )

    def _loop(self) -> None:
        from parameter_server_tpu.core.messages import SCHEDULER

        while not self._stop.wait(self.interval):
            for nid, mgr in self.cluster.managers.items():
                if nid != SCHEDULER:
                    mgr.send_heartbeat()

    def __enter__(self):
        self._thread.start()
        self.cluster.sched.start_monitor(interval=self.interval)
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self.cluster.sched.stop_monitor()


def cluster_counters(cluster: Cluster) -> dict:
    """One flat snapshot of every count the checks and the per-layer
    readers use.  Cumulative: the window's figures are differences."""
    from parameter_server_tpu.utils.metrics import transport_counters

    out = dict(transport_counters(cluster.van))
    ws = cluster.workers.values()
    out.update(
        server_pushes=sum(s.pushes for s in cluster.servers.values()),
        server_pulls=sum(s.pulls for s in cluster.servers.values()),
        error_replies=sum(w.error_replies for w in ws),
        gate_sheds=sum(w.consist_sheds for w in ws),
        gate_forced=sum(w.consist_forced for w in ws),
        gate_waits=sum(w.consist_waits for w in ws),
        push_retries=sum(w.push_retries for w in ws),
        pull_retries=sum(w.pull_retries for w in ws),
        dead_nodes=sum(1 for n in cluster.sched.nodes() if not n.alive),
        ledger_fatal=sum(
            1 for s in cluster.servers.values()
            if s.ledger is not None and s.ledger.fatal is not None
        ),
    )
    return out


def ledger_digests(cluster: Cluster) -> Dict[str, dict]:
    """``{server: {histogram name: to_dict digest}}`` of the ApplyLedgers."""
    return {
        sid: (s.ledger.latency_digests() if s.ledger is not None else {})
        for sid, s in cluster.servers.items()
    }
