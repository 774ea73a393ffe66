"""App factory: registry + config-file driven app construction.

Reference analogue: ``src/system/app.h/.cc`` — ``App::Create(conf)`` reads the
text-proto config, looks up the app class by its config type, and the
scheduler calls ``app->Run()`` (SURVEY.md §2 #7 [U — reference mount empty,
public layout]).  Here the registry is keyed by a string ``app:`` field in a
yaml/json config file, apps are callables returning a result dict, and the
same config vocabulary (data / optimizer / penalty / consistency) carries
over via the dataclasses in ``config.py``.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, Optional

from parameter_server_tpu.config import (
    ConsistencyConfig,
    ConsistencyMode,
    OptimizerConfig,
    TableConfig,
    TopologyConfig,
)


@dataclasses.dataclass
class DataConfig:
    """Input source: synthetic CTR stream or an on-disk text dataset."""

    kind: str = "synthetic"  # synthetic | libsvm | criteo
    path: Optional[str] = None
    batch_size: int = 1024
    #: synthetic stream parameters (ignored for file inputs)
    key_space: int = 1 << 22
    nnz: int = 39
    seed: int = 0
    #: > 0 enables count-min tail filtering on the key stream: keys whose
    #: estimated frequency is below the threshold mask to the trash row
    #: (the reference's DARLIN preprocessing countmin filter, on the
    #: production input path — VERDICT r3 #4).
    tail_threshold: int = 0


@dataclasses.dataclass
class AppConfig:
    """One training/eval job — the reference's app-level text proto."""

    app: str
    table: TableConfig
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    consistency: ConsistencyConfig = dataclasses.field(
        default_factory=ConsistencyConfig
    )
    topology: TopologyConfig = dataclasses.field(default_factory=TopologyConfig)
    steps: int = 100
    eval_batches: int = 0
    ckpt_root: Optional[str] = None
    ckpt_every: int = 0


_REGISTRY: Dict[str, Callable[[AppConfig], Callable[[], dict]]] = {}


def register_app(name: str):
    """Decorator: register an app builder under ``name``.

    A builder takes the :class:`AppConfig` and returns a zero-arg ``run``
    callable producing a result dict (losses, metrics, ...).
    """

    def deco(builder):
        if name in _REGISTRY:
            raise ValueError(f"app {name!r} already registered")
        _REGISTRY[name] = builder
        return builder

    return deco


def registered_apps() -> list[str]:
    return sorted(_REGISTRY)


def create(cfg: AppConfig) -> Callable[[], dict]:
    """The ``App::Create`` seam: config -> runnable app."""
    try:
        builder = _REGISTRY[cfg.app]
    except KeyError:
        raise ValueError(
            f"unknown app {cfg.app!r}; registered: {registered_apps()}"
        ) from None
    return builder(cfg)


# --------------------------------------------------------------- config IO --


def _hydrate(cls, obj: Any):
    """Recursively build a dataclass from a plain dict (yaml/json)."""
    if obj is None or not dataclasses.is_dataclass(cls):
        return obj
    if not isinstance(obj, dict):
        raise TypeError(f"expected mapping for {cls.__name__}, got {type(obj)}")
    kwargs = {}
    fields = {f.name: f for f in dataclasses.fields(cls)}
    for k, v in obj.items():
        if k not in fields:
            raise ValueError(f"unknown field {k!r} for {cls.__name__}")
        ftype = fields[k].type
        target = _FIELD_TYPES.get((cls.__name__, k))
        if target is not None:
            v = _hydrate(target, v) if isinstance(v, dict) else target(v)
        kwargs[k] = v
        del ftype
    return cls(**kwargs)


#: nested dataclass/enum fields (dataclass field types are strings under
#: ``from __future__ import annotations``, so map them explicitly)
_FIELD_TYPES = {
    ("AppConfig", "table"): TableConfig,
    ("AppConfig", "data"): DataConfig,
    ("AppConfig", "consistency"): ConsistencyConfig,
    ("AppConfig", "topology"): TopologyConfig,
    ("TableConfig", "optimizer"): OptimizerConfig,
    ("ConsistencyConfig", "mode"): ConsistencyMode,
}


def load_config(path: str) -> AppConfig:
    """Read a yaml/json app config file into an :class:`AppConfig`."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        raw = json.loads(text)
    else:
        import yaml

        raw = yaml.safe_load(text)
    if not isinstance(raw, dict) or "app" not in raw:
        raise ValueError(f"{path}: config must be a mapping with an 'app' key")
    return _hydrate(AppConfig, raw)


# ------------------------------------------------------------ built-in apps --


def _tail_wrap(batch_fn, data: DataConfig):
    """Apply the count-min tail filter when configured (else pass through)."""
    if data.tail_threshold <= 0:
        return batch_fn
    from parameter_server_tpu.data.tailfilter import TailFilteredStream

    return TailFilteredStream(batch_fn, data.tail_threshold)


def _tail_stats(batch_fn) -> dict:
    """Result-dict stats for a tail-filtered batch source (empty if none)."""
    frac = getattr(batch_fn, "masked_fraction", None)
    if frac is None:
        return {}
    return {
        "tail_masked_fraction": round(float(frac), 6),
        "tail_seen_positions": int(batch_fn.seen),
    }


def _make_batch_fn(data: DataConfig):
    if data.kind == "synthetic":
        from parameter_server_tpu.data.synthetic import SyntheticCTR

        stream = SyntheticCTR(
            key_space=data.key_space,
            nnz=data.nnz,
            batch_size=data.batch_size,
            seed=data.seed,
        )
        return _tail_wrap(stream.next_batch, data)
    if data.kind in ("libsvm", "criteo"):
        from parameter_server_tpu.data import fs
        from parameter_server_tpu.data.reader import StreamReader

        if not data.path:
            raise ValueError(f"data.kind={data.kind!r} requires data.path")
        # the path may be a glob and/or a psfs:// url — shard expansion and
        # remote streaming both go through the fs layer (file.h/HDFS role).
        # An empty expansion is a config error NOW, not a FileNotFoundError
        # three layers deep at the first batch — unless the "glob" is really
        # a literal filename containing metacharacters (day[1].csv) that
        # exists on disk, which must keep working.
        import os as os_lib

        files = fs.list_files(data.path)
        if not files:
            literal = (
                data.path[len("file://") :]
                if data.path.startswith("file://")
                else data.path
            )
            if not data.path.startswith("psfs://") and os_lib.path.exists(literal):
                files = [data.path]
            else:
                raise FileNotFoundError(
                    f"data.path {data.path!r} matched no files"
                )
        reader = StreamReader(
            files, data.batch_size, format=data.kind, epochs=None
        )
        it = iter(reader)

        def next_batch():
            keys, _vals, labels = next(it)
            return keys, labels

        return _tail_wrap(next_batch, data)
    raise ValueError(f"unknown data kind {data.kind!r}")


@register_app("sparse_lr")
def _build_sparse_lr(cfg: AppConfig) -> Callable[[], dict]:
    """Single-device fused sparse LR (BASELINE config #1 shape)."""
    from parameter_server_tpu.learner.sgd import LocalLRTrainer

    def run() -> dict:
        trainer = LocalLRTrainer(cfg.table)
        batch_fn = _make_batch_fn(cfg.data)
        losses = [trainer.step(*batch_fn()) for _ in range(cfg.steps)]
        out = {"losses": losses, "steps": cfg.steps, **_tail_stats(batch_fn)}
        if cfg.eval_batches:
            out["auc"] = trainer.eval_auc(batch_fn, cfg.eval_batches)
        return out

    return run


@register_app("fm")
def _build_fm(cfg: AppConfig) -> Callable[[], dict]:
    """Single-device fused factorization machine (table dim = 1 + k)."""
    from parameter_server_tpu.learner.fm import LocalFMTrainer

    def run() -> dict:
        trainer = LocalFMTrainer(cfg.table)
        batch_fn = _make_batch_fn(cfg.data)
        losses = [trainer.step(*batch_fn()) for _ in range(cfg.steps)]
        out = {"losses": losses, "steps": cfg.steps, **_tail_stats(batch_fn)}
        if cfg.eval_batches:
            out["auc"] = trainer.eval_auc(batch_fn, cfg.eval_batches)
        return out

    return run


def _run_hybrid(cfg: AppConfig, model_cfg) -> dict:
    """What the registered hybrid apps run: ``model_cfg``'s body under
    ``learner/hybrid.py::HybridLMTrainer``, its embedding table on
    ``cfg.topology.num_servers`` ``KVServer``s over a loopback Van."""
    import numpy as np

    from parameter_server_tpu.core.postoffice import Postoffice
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.learner import hybrid
    from parameter_server_tpu.parallel import mesh as mesh_lib

    ns = cfg.topology.num_servers
    van = LoopbackVan()
    try:
        table = dataclasses.replace(
            hybrid.embedding_table_cfg(model_cfg),
            optimizer=cfg.table.optimizer,
        )
        tables = {"emb": table}
        _servers = [
            KVServer(Postoffice(f"S{i}", van), tables, i, ns)
            for i in range(ns)
        ]
        worker = KVWorker(
            Postoffice("W0", van), tables, ns,
            localizers=hybrid.embedding_localizers(model_cfg),
        )
        import jax

        n_dev = len(jax.devices())
        trainer = hybrid.HybridLMTrainer(
            model_cfg,
            mesh_lib.make_mesh((n_dev, 1)),
            worker,
            max_delay=cfg.consistency.max_delay,
        )
        rng = np.random.default_rng(cfg.data.seed)
        B, S = 2 * n_dev, 32  # batch divisible by the data axis
        losses = []
        for _ in range(cfg.steps):
            base = rng.integers(0, model_cfg.vocab_size, size=(B, 1))
            tokens = (base + np.arange(S)[None]) % model_cfg.vocab_size
            losses.append(trainer.step(tokens.astype(np.int32)))
        trainer.drain()
        out = {"losses": losses, "steps": cfg.steps}
        if trainer.counters:
            out["counters"] = dict(trainer.counters)
        return out
    finally:
        van.close()


@register_app("llama_hybrid")
def _build_llama_hybrid(cfg: AppConfig) -> Callable[[], dict]:
    """BASELINE config #5: PS-served embedding table over the Van + sync
    GSPMD transformer body (``learner/hybrid.py``).  ``cfg.table.optimizer``
    is the embedding optimizer; the vocab is ``data.key_space`` (kept tiny
    by default so the app runs anywhere); ``consistency.max_delay`` bounds
    in-flight embedding pushes (SSP)."""

    def run() -> dict:
        from parameter_server_tpu.models import transformer as tfm

        return _run_hybrid(cfg, tfm.tiny_config(
            causal=True, tie_embeddings=False,
            vocab_size=min(cfg.data.key_space, 1 << 16),
        ))

    return run


@register_app("kimi_linear_hybrid")
def _build_kimi_linear_hybrid(cfg: AppConfig) -> Callable[[], dict]:
    """The same hybrid path under a body with a layer pattern
    (``models/kimi_linear.py``: delta-rule and latent-attention mixers, a
    dense MLP and a held share of routed experts), tiny sizes by default so
    the app runs anywhere; the benchmark's ``kimi_linear_a3b`` configuration
    runs the published widths through the same trainer."""

    def run() -> dict:
        from parameter_server_tpu.models import kimi_linear

        return _run_hybrid(cfg, kimi_linear.tiny_config(
            vocab_size=min(cfg.data.key_space, 1 << 16),
        ))

    return run


@register_app("lfm2_moe_hybrid")
def _build_lfm2_moe_hybrid(cfg: AppConfig) -> Callable[[], dict]:
    """The same hybrid path under a body of gated short convolutions and
    grouped-query attention (``models/lfm2_moe.py``: a dense MLP and a held
    share of bias-selected experts), tiny sizes by default so the app runs
    anywhere; the benchmark's ``lfm2_8b_a1b`` configuration runs the
    published widths through the same trainer."""

    def run() -> dict:
        from parameter_server_tpu.models import lfm2_moe

        return _run_hybrid(cfg, lfm2_moe.tiny_config(
            vocab_size=min(cfg.data.key_space, 1 << 16),
        ))

    return run


@register_app("laguna_hybrid")
def _build_laguna_hybrid(cfg: AppConfig) -> Callable[[], dict]:
    """The same hybrid path under a body of window and full attention
    layers (``models/laguna.py``: each kind with its own head count, rotary
    table and key range, a gate a head on the attention output, a dense MLP
    and a held share of routed experts with a shared one), tiny sizes by
    default so the app runs anywhere; the benchmark's ``laguna_xs2``
    configuration runs the published widths through the same trainer."""

    def run() -> dict:
        from parameter_server_tpu.models import laguna

        return _run_hybrid(cfg, laguna.tiny_config(
            vocab_size=min(cfg.data.key_space, 1 << 16),
        ))

    return run


def _sp_app_knobs(cfg: AppConfig, round_to: int):
    """Shared knobs of the long-context apps (sp_lm / sptp_lm).

    One source for the model config, sequence length (``data.nnz * 64``
    rounded up to ``round_to`` — nnz reused as a length knob so the app
    config stays one schema), batch rows, and the synthetic token stream.
    """
    import numpy as np

    from parameter_server_tpu.models import transformer as tfm

    model_cfg = tfm.tiny_config(
        causal=True, tie_embeddings=False,
        vocab_size=min(cfg.data.key_space, 1 << 16),
        max_seq=1 << 16,
    )
    seq = max(cfg.data.nnz, 1) * 64
    seq = ((seq + round_to - 1) // round_to) * round_to
    B = max(cfg.data.batch_size // 256, 1)
    rng = np.random.default_rng(cfg.data.seed)

    def next_tokens() -> np.ndarray:
        base = rng.integers(0, model_cfg.vocab_size, size=(B, 1))
        return (
            (base + np.arange(seq)[None]) % model_cfg.vocab_size
        ).astype(np.int32)

    return model_cfg, seq, next_tokens


@register_app("sp_lm")
def _build_sp_lm(cfg: AppConfig) -> Callable[[], dict]:
    """Long-context causal LM: the sequence axis sharded over EVERY device
    (``parallel/sp_lm.py``), ring attention inside the transformer.  The
    vocab is ``data.key_space`` (kept small by default); ``data.batch_size``
    is the batch; seq-length knob per ``_sp_app_knobs``."""

    def run() -> dict:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from parameter_server_tpu.parallel.sp_lm import SpLMTrainer

        devices = jax.devices()
        model_cfg, seq, next_tokens = _sp_app_knobs(cfg, len(devices))
        mesh = Mesh(np.asarray(devices), ("sp",))
        trainer = SpLMTrainer(model_cfg, mesh, learning_rate=3e-3)
        losses = [trainer.step(next_tokens()) for _ in range(cfg.steps)]
        return {"losses": losses, "steps": cfg.steps, "seq": seq}

    return run


@register_app("sptp_lm")
def _build_sptp_lm(cfg: AppConfig) -> Callable[[], dict]:
    """The COMPOSED long-context causal LM (``parallel/sp_fsdp.py``): ring
    attention over an ``sp`` axis x tensor parallelism over ``model`` x
    adamw moments FSDP over ``sp``, one GSPMD program.  Mesh shape comes
    from ``topology.mesh_shape`` (data, model) reinterpreted as
    (sp, model) — ``None`` (the schema default, "unset") falls back to
    all-devices-on-sp x model 1, while an EXPLICIT shape — (1, 1)
    included — is validated against the available devices (ADVICE r5 #4).
    Sequence length knob as in the ``sp_lm`` app (``data.nnz * 64``,
    rounded to a multiple of sp)."""

    def run() -> dict:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from parameter_server_tpu.parallel.sp_fsdp import SpTpLMTrainer

        devices = jax.devices()
        n_dev = len(devices)
        mesh_cfg = (
            None
            if cfg.topology.mesh_shape is None
            else tuple(cfg.topology.mesh_shape)
        )
        if mesh_cfg is None:  # unset: all devices on sp, no TP
            sp_n, tp_n = n_dev, 1
        elif len(mesh_cfg) == 2 and mesh_cfg[0] * mesh_cfg[1] == n_dev:
            sp_n, tp_n = mesh_cfg
        else:
            # a silently-substituted mesh would run the "composed SP x TP"
            # app with no TP at all; fail the misconfiguration loudly
            raise ValueError(
                f"topology.mesh_shape {mesh_cfg} does not factor the "
                f"{n_dev} available devices into (sp, model)"
            )
        model_cfg, seq, next_tokens = _sp_app_knobs(cfg, sp_n)
        mesh = Mesh(
            np.asarray(devices).reshape(sp_n, tp_n), ("sp", "model")
        )
        trainer = SpTpLMTrainer(
            model_cfg, mesh, learning_rate=3e-3, fsdp="state",
            loss_chunk=max(seq // (4 * sp_n), 8),
        )
        losses = [trainer.step(next_tokens()) for _ in range(cfg.steps)]
        return {
            "losses": losses, "steps": cfg.steps, "seq": seq,
            "mesh": {"sp": sp_n, "model": tp_n},
        }

    return run


@register_app("async_lr")
def _build_async_lr(cfg: AppConfig) -> Callable[[], dict]:
    """Classic PS topology on one host: scheduler + servers + worker threads
    over the LoopbackVan with BSP/SSP/ASP gating and elastic workloads."""

    def run() -> dict:
        import jax
        import numpy as np

        from parameter_server_tpu.core.fleet import FleetMonitor
        from parameter_server_tpu.core.manager import launch_local_cluster
        from parameter_server_tpu.core.messages import server_id, worker_id
        from parameter_server_tpu.core.netmon import MeteredVan
        from parameter_server_tpu.core.van import LoopbackVan
        from parameter_server_tpu.kv.server import KVServer
        from parameter_server_tpu.kv.worker import KVWorker
        from parameter_server_tpu.learner.elastic import ElasticTrainer
        from parameter_server_tpu.utils.keys import HashLocalizer
        from parameter_server_tpu.utils.metrics import transport_counters
        from parameter_server_tpu.utils.platform import (
            bytes_in_use,
            role_device,
        )

        nw, ns = cfg.topology.num_workers, cfg.topology.num_servers
        # metered outermost: per-link wire accounting on every logical
        # message; heartbeats carry the digests to the scheduler's fleet
        # monitor (SURVEY §5 observability plane)
        van = MeteredVan(LoopbackVan())
        try:
            sched, managers, posts = launch_local_cluster(
                van, num_workers=nw, num_servers=ns
            )
            sched.fleet = FleetMonitor()
            tables = {cfg.table.name: cfg.table}
            loc = {cfg.table.name: HashLocalizer(cfg.table.rows)}
            # server i keeps its shard on local device i % n; what a shard
            # costs there is the allocator's growth around its construction
            servers, placement = {}, {}
            for i in range(ns):
                before = bytes_in_use(role_device(i))
                srv = KVServer(posts[server_id(i)], tables, i, ns)
                tbl = srv.tables[cfg.table.name]
                jax.block_until_ready((tbl.value, tbl.state))
                servers[server_id(i)] = srv
                placement[server_id(i)] = {
                    "device": str(srv.device),
                    "rows": tbl.rows,
                    "nominal_bytes": tbl.nominal_bytes,
                    "allocated_bytes": (
                        None
                        if before is None
                        else bytes_in_use(srv.device) - before
                    ),
                }
            workers = {
                worker_id(i): KVWorker(
                    posts[worker_id(i)], tables, ns, localizers=loc
                )
                for i in range(nw)
            }
            batch_fn = _make_batch_fn(cfg.data)
            batches_per_shard = 4
            n_shards = max(1, cfg.steps // batches_per_shard)
            shards = [
                [batch_fn() for _ in range(batches_per_shard)]
                for _ in range(n_shards)
            ]
            trainer = ElasticTrainer(
                workers,
                sched,
                shards,
                cfg.consistency,
                managers=managers,
                table=cfg.table.name,
                ckpt_root=cfg.ckpt_root,
                ckpt_every=cfg.ckpt_every,
            )
            losses = trainer.run()
            for sid, srv in servers.items():
                placement[sid].update(pushes=srv.pushes, pulls=srv.pulls)
            return {
                "losses": losses,
                "steps": len(losses),
                "mean_loss_tail": float(np.mean(losses[-10:])),
                "last_ckpt_step": trainer.last_ckpt_step,
                "workloads_done": trainer.pool.num_done(),
                # workers the Van cut off and survivors covered for, nodes
                # the heartbeat sweep declared dead, legs answered
                # ``__error__``, gate deadlines that fired: none on a
                # healthy run
                "retired_workers": sorted(trainer._killed),
                "dead_nodes": [
                    n.node_id for n in sched.nodes() if not n.alive
                ],
                "error_replies": sum(
                    w.error_replies for w in workers.values()
                ),
                "gate_sheds": sum(
                    w.consist_sheds + w.consist_forced
                    for w in workers.values()
                ),
                "servers": placement,
                "net": transport_counters(van),
                "fleet": sched.fleet.snapshot(),
                "stragglers": sched.fleet.stragglers(),
            }
        finally:
            van.close()

    return run
