"""Configuration dataclasses.

The reference uses a two-level config split: process-topology gflags (role,
scheduler address, worker/server counts) and a text-format protobuf app config
(data, loss, penalty, learning rate, consistency window).  (Reference:
``src/app/main.cc`` gflags + ``config/*.conf`` text protos [U].)  We keep the
same split and much of the field vocabulary, as plain dataclasses.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple, Union


class ConsistencyMode(str, enum.Enum):
    """Consistency spectrum of the reference's Executor task DAG.

    BSP = depend on all prior iterations; ASP = no dependencies; SSP =
    bounded staleness of ``max_delay`` iterations.  (Reference:
    ``src/system/executor.h`` ``Task.time``/``wait_time`` semantics [U].)
    """

    BSP = "bsp"
    SSP = "ssp"
    ASP = "asp"


@dataclasses.dataclass(frozen=True)
class ConsistencyConfig:
    mode: ConsistencyMode = ConsistencyMode.BSP
    #: SSP staleness bound (the reference's ``max_delay`` flag); ignored for
    #: BSP (effectively 0) and ASP (effectively unbounded).
    max_delay: int = 0
    #: graceful-degradation deadline (ISSUE 20): when a wire-enforced gate
    #: (a ``__wait__`` defer loop) has held a request longer than this,
    #: pulls shed to the stale serving path (bounded by the advertised
    #: ``__sver__`` watermark) and pushes force through — never dropped.
    #: <= 0 disables shedding (wait forever; tests assert invariants with
    #: this).
    gate_deadline_s: float = 5.0
    #: base sleep between gate retries when the server's ``__wait__`` reply
    #: does not advertise its own ``retry_after`` hint.
    gate_retry_s: float = 0.005

    @property
    def bound(self) -> Optional[int]:
        """Staleness bound as an int, or None for unbounded (ASP)."""
        if self.mode == ConsistencyMode.BSP:
            return 0
        if self.mode == ConsistencyMode.SSP:
            return self.max_delay
        return None

    def __post_init__(self) -> None:
        if self.max_delay < 0:
            raise ValueError(
                f"max_delay must be >= 0, got {self.max_delay!r}"
            )
        if self.gate_retry_s <= 0:
            raise ValueError(
                f"gate_retry_s must be > 0, got {self.gate_retry_s!r}"
            )


@dataclasses.dataclass(frozen=True)
class TopologyConfig:
    """Process/device topology — the reference's gflags layer.

    On TPU the "servers" are shards of a device mesh axis rather than separate
    processes; ``num_servers`` becomes the number of table shards and
    ``num_workers`` the number of data-parallel worker slots.
    """

    num_workers: int = 1
    num_servers: int = 1
    #: mesh axis sizes (data, model); data axis carries DP gradient psum
    #: (the NCCL-pre-reduction replacement), model axis carries table shards.
    #: None = unset: apps pick their own default layout (e.g. sptp_lm puts
    #: all devices on sp).  An explicit shape — including (1, 1) — is
    #: validated against the available devices like any other.
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axis_names: Tuple[str, ...] = ("data", "model")


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Server-side update rule for a table.

    ``kind`` in {"sgd", "adagrad", "adam", "ftrl"}; FTRL mirrors the
    reference's KVMap FTRLEntry{z,n} lazy-weight scheme
    (``src/app/linear_method/ftrl*.h`` [U]).
    """

    kind: str = "adagrad"
    learning_rate: float = 0.1
    #: L1/L2 regularization (the reference's penalty protos).
    l1: float = 0.0
    l2: float = 0.0
    #: adagrad/adam epsilon; ftrl beta.
    eps: float = 1e-8
    beta1: float = 0.9
    beta2: float = 0.999
    #: ftrl alpha/beta per the FTRL-proximal paper parameterization.
    ftrl_alpha: float = 0.05
    ftrl_beta: float = 1.0


@dataclasses.dataclass(frozen=True)
class ApplyEngineConfig:
    """Server-side apply engine knobs (the bundle-batched push path).

    The engine turns a coalesced bundle of same-table PUSHes into (ideally)
    one donated-buffer device call instead of one per request.  How
    duplicate row ids ACROSS bundle members are handled is the semantic
    knob:

    - ``"rounds"`` (default): members are partitioned into occurrence
      rounds — round *k* applies the *k*-th contribution each row received,
      one device call per round.  Because the optimizer is row-wise, this
      is **bitwise-identical to sequential per-request apply for every
      optimizer**, duplicates included; with no cross-member duplicates it
      degenerates to exactly one call.
    - ``"combine"``: duplicate rows are pre-merged on device with
      ``segment_combine`` (the reference server's ParallelOrderedMatch
      merge) and applied once — always one device call.  This sums
      gradients before the update, the classic PS merge: identical to
      sequential when members touch disjoint rows, and the standard
      sum-semantics (not bitwise-sequential) when they overlap.
    """

    #: max same-table PUSHes concatenated into one batched device apply;
    #: <= 1 disables bundling (every request applies individually).
    apply_batch: int = 16
    #: cross-member duplicate-id policy: "rounds" | "combine" (see above).
    dup_policy: str = "rounds"


@dataclasses.dataclass(frozen=True)
class LedgerConfig:
    """Device-plane observability knobs (the server's ApplyLedger).

    PR 11 made PUSH acks sync-free, so the ack no longer observes the
    device apply at all — true apply latency, device queue depth, and the
    host-assembly/H2D/compute split became invisible.  The ledger
    (``kv/ledger.py``) registers every in-flight apply at dispatch and
    retires it from a background reaper thread once ``is_ready()`` — never
    from the ack path, so the sync-free contract holds.  Between
    completions the reaper blocks inside the runtime on the oldest
    in-flight result; ``reap_interval_s`` is only the degraded-mode poll
    cadence (donated-buffer races, ``drain``).

    Backlog bounds drive the soft-backpressure hint: when any configured
    bound is exceeded, the server stamps ``__busy__`` into push acks (the
    admission-control signal the serving plane consumes) and the
    ``apply.backlog`` flight-recorder event fires edge-triggered.  A bound
    of 0 disables that bound; all bounds 0 (the default) means the ledger
    observes but never hints.
    """

    enabled: bool = True
    #: reaper poll period; also bounds device-latency measurement error.
    reap_interval_s: float = 0.001
    #: reaper self-stops after this long with nothing in flight (restarted
    #: lazily on the next submit) — idle servers pay zero poll cost.
    idle_stop_s: float = 2.0
    #: backpressure bounds (0 = unbounded): in-flight device applies ...
    backlog_bundles: int = 0
    #: ... in-flight rows across those applies ...
    backlog_rows: int = 0
    #: ... and age of the oldest un-retired apply, in seconds.
    backlog_age_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Read-heavy serving plane knobs (ISSUE 13).

    The serving plane layers three mechanisms over the training substrate:
    a worker-side hot-row cache invalidated by the piggybacked ``__sver__``
    segment version clock (``kv/cache.py``), a server-side read-only PULL
    fast path (``__ro__`` request flag), and SLO-driven admission control
    (``serve/admission.py``) consuming ``SloEngine.healthy()`` and the
    ledger's ``__busy__`` hints.
    """

    #: hot-row cache capacity, in rows per table (direct-mapped, rounded up
    #: to a power of two; collision-evicted); <= 0 disables caching.
    cache_rows: int = 65536
    #: what to do with read traffic while the plane is unhealthy (SLO breach
    #: or a live ``__busy__`` hint): "reject" answers immediately with a
    #: retry-after shed; "stale" serves watermark-invalid cache entries
    #: (bounded only by what the cache holds) and sheds uncached keys;
    #: "queue" waits up to ``queue_deadline_s`` for health, then sheds.
    policy: str = "reject"
    #: advisory client back-off carried by a reject shed, seconds.
    retry_after_s: float = 0.05
    #: max time a "queue" policy read waits for the plane to recover.
    queue_deadline_s: float = 0.5
    #: poll period while a "queue" policy read is parked.
    queue_poll_s: float = 0.005
    #: how recent a ``__busy__`` hint must be to count as live overload.
    busy_within_s: float = 1.0

    def __post_init__(self) -> None:
        if self.policy not in ("reject", "stale", "queue"):
            raise ValueError(
                f"serve policy must be reject|stale|queue, got {self.policy!r}"
            )


@dataclasses.dataclass(frozen=True)
class WireCompressionConfig:
    """Lossy wire codec for the DCN value plane (ISSUE 14).

    Selected per table (``TableConfig.compression``) and composed under
    ``CoalescingVan`` via :class:`~parameter_server_tpu.core.filters.
    QuantizingFilter` — one pass over the bundled value plane, PUSH
    requests only (PULL replies stay bit-exact so the serving plane's
    bitwise guarantees hold).

    ``error_feedback`` keeps a per-(sender, table, key) residual
    accumulator on the sender: the quantization error of each push is
    re-injected into the NEXT push for the same keys instead of lost —
    the EQuARX result (PAPERS.md) that makes lossy compression converge
    like the uncompressed run.  Residuals are dropped on ``adopt_routing``
    (new routing epoch), on a peer incarnation advance, and on a same-id
    restart, so a rebalanced or recovered fleet never replays stale error.

    ``per_row`` replaces ``FixingFloatFilter``'s old dim-based guess:
    ``True``/``False`` force per-row/per-tensor scales; ``"auto"`` keeps
    the measured heuristic (per-row only when the last dim is >= 16, since
    each row scale costs 4 header-borne bytes and would rival the int8
    payload of a dim-1 LR table).
    """

    #: wire codec: "none" (bit-exact), "int8", or "fp8".
    codec: str = "none"
    #: fp8 bit layout: "e4m3" (more mantissa) or "e5m2" (more range).
    fp8_format: str = "e4m3"
    #: "nearest" or "stochastic" (seeded from ``seed`` — deterministic).
    rounding: str = "nearest"
    #: carry quantization error forward per (sender, table, key).
    error_feedback: bool = True
    #: per-row scales: True | False | "auto" (the old dim heuristic).
    per_row: Union[bool, str] = "auto"
    #: stochastic-rounding rng seed (repo-wide seeded-replay contract).
    seed: int = 0

    def __post_init__(self) -> None:
        if self.codec not in ("none", "int8", "fp8"):
            raise ValueError(
                f"codec must be none|int8|fp8, got {self.codec!r}"
            )
        if self.fp8_format not in ("e4m3", "e5m2"):
            raise ValueError(
                f"fp8_format must be e4m3|e5m2, got {self.fp8_format!r}"
            )
        if self.rounding not in ("nearest", "stochastic"):
            raise ValueError(
                f"rounding must be nearest|stochastic, got {self.rounding!r}"
            )
        if not (self.per_row in (True, False) or self.per_row == "auto"):
            raise ValueError(
                f'per_row must be True, False, or "auto", got {self.per_row!r}'
            )


@dataclasses.dataclass(frozen=True)
class GroupConfig:
    """Hierarchical push: a worker group pre-reduces before the wire (ISSUE 15).

    Co-located workers (one host / one pod slice) sum their PUSH value
    planes locally — the MLPerf TPU-pod pattern (PAPERS.md,
    arXiv:1909.09756) of reducing over ICI before anything crosses DCN —
    and only one elected member pushes the reduced tensor, stamped
    (``kv/routing.py::GROUP_KEY``) so the server accounts it as ONE
    logical apply for the whole group.  Server inbound PUSH bytes and
    request count drop ~linearly in ``size``.

    ``election`` picks the pushing leg per ``(table, step)``:
    ``"rotate"`` (default) spreads wire load across members
    deterministically; ``"fixed"`` pins member 0 — required when the
    lossy wire codec's error-feedback residuals (ISSUE 14, keyed per
    ``(sender, table)``) should keep compressing group pushes: under
    rotation the residual owner would change every step, so group frames
    are stamped to BYPASS the codec instead (see
    ``core/filters.py::QuantizingFilter``).

    ``fallback`` is the degradation contract when the elected leader is
    dead or partitioned mid-step: ``"direct"`` (default) re-pushes the
    member's own gradient straight to the servers within the same step —
    no loss, at direct-push cost for that step; ``"none"`` raises instead
    (lockstep test topologies that must not mask a dead leader).

    ``reduce`` selects the pre-reduction path: ``"auto"`` uses an XLA
    ``psum`` when the members' contributions share one key set and enough
    local devices exist to map them (the shared-mesh case), else a
    deterministic host-side sorted-union merge (the loopback/multi-process
    topology); ``"merge"`` forces the host path; ``"psum"`` prefers the
    device path but still merges when key sets differ.
    """

    #: members per group (1 = grouping disabled).
    size: int = 1
    #: leader election per (table, step): "rotate" or "fixed".
    election: str = "rotate"
    #: leader-death degradation: "direct" (per-worker push) or "none".
    fallback: str = "direct"
    #: pre-reduction path: "auto", "psum", or "merge".
    reduce: str = "auto"
    #: seconds a member waits on the leader (contribution ack / done
    #: notify) before falling back; also the leader-side age at which an
    #: incomplete rendezvous set is flushed as a partial reduction.
    fallback_timeout: float = 0.25

    def __post_init__(self) -> None:
        if self.size < 1:
            raise ValueError(f"size must be >= 1, got {self.size!r}")
        if self.election not in ("rotate", "fixed"):
            raise ValueError(
                f"election must be rotate|fixed, got {self.election!r}"
            )
        if self.fallback not in ("direct", "none"):
            raise ValueError(
                f"fallback must be direct|none, got {self.fallback!r}"
            )
        if self.reduce not in ("auto", "psum", "merge"):
            raise ValueError(
                f"reduce must be auto|psum|merge, got {self.reduce!r}"
            )
        if self.fallback_timeout <= 0:
            raise ValueError(
                f"fallback_timeout must be > 0, got {self.fallback_timeout!r}"
            )


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Durability plane knobs (ISSUE 16): partitioned snapshot cadence.

    The partitioned snapshot path (``KVWorker.save_snapshot`` +
    ``checkpoint.finalize_snapshot``) snapshots ANY routing layout — one
    file per segment, an incremental carry when a segment's ``__sver__``
    clock has not advanced, and a dirty-row delta log that bounds the
    commit freeze.  This config feeds the ElasticTrainer's checkpoint loop
    and the ``durability_plane_specs`` SLO (``ckpt_age_s`` breaches when
    the last durable manifest is older than ``interval_s``).
    """

    #: target wall-clock seconds between durable manifests; the
    #: ``ckpt-age`` SLO breach threshold derives from it.
    interval_s: float = 60.0
    #: soft bound on the dirty-row delta a snapshot commit may export in
    #: its freeze window; a commit over the bound still lands (durability
    #: beats latency) but flags ``over_bound`` on its ``ckpt.commit``
    #: event and bumps the ``ckpt_delta_overflow`` counter.
    max_delta_rows: int = 65536
    #: snapshots kept by ``checkpoint.retain_snapshots`` (chain bases that
    #: kept manifests still reference are preserved regardless).
    retention: int = 3
    #: "auto" = legacy uniform shards while the layout allows them, the
    #: partitioned path once the fleet has rebalanced (or a snapshot chain
    #: exists to extend); "partitioned"/"legacy" force one path.
    mode: str = "auto"

    def __post_init__(self) -> None:
        if self.interval_s <= 0:
            raise ValueError(
                f"interval_s must be > 0, got {self.interval_s!r}"
            )
        if self.max_delta_rows < 1:
            raise ValueError(
                f"max_delta_rows must be >= 1, got {self.max_delta_rows!r}"
            )
        if self.retention < 0:
            raise ValueError(
                f"retention must be >= 0, got {self.retention!r}"
            )
        if self.mode not in ("auto", "legacy", "partitioned"):
            raise ValueError(
                f"mode must be auto|legacy|partitioned, got {self.mode!r}"
            )


@dataclasses.dataclass(frozen=True)
class TransportConfig:
    """Transport v2 knobs (ISSUE 17): wire backend + colocated shm rings.

    ``TcpVan`` consumes this; both knobs also answer to env overrides
    (``PS_WIRE=epoll|threaded``, ``PS_NO_SHM=1``) so tests and rollouts can
    flip backends without plumbing a config through every constructor.
    """

    #: native wire backend: "epoll" (one event-loop thread multiplexing all
    #: connections, vectored writev sends, bounded write queues —
    #: ``native/src/epollvan.cc``) or "threaded" (the PR 6 thread-per-
    #: connection core, ``native/src/tcpvan.cc``).  "epoll" quietly falls
    #: back to "threaded" when the epoll backend fails to build.
    wire: str = "epoll"
    #: negotiate shared-memory rings for colocated links (same boot id):
    #: frames bypass TCP via ``core/shm_ring.py``; any doubt (ring full,
    #: peer dead, old peer that never acks) degrades per-frame to TCP.
    shm: bool = True
    #: per-direction ring capacity in bytes.
    ring_capacity: int = 4 << 20
    #: how long a sender waits for ring space before falling back to TCP
    #: for that frame (counted in ``ring_full``).
    ring_wait_s: float = 0.0005

    def __post_init__(self) -> None:
        if self.wire not in ("epoll", "threaded"):
            raise ValueError(f"wire must be epoll|threaded, got {self.wire!r}")
        if self.ring_capacity < 4096:
            raise ValueError(
                f"ring_capacity must be >= 4096, got {self.ring_capacity!r}"
            )
        if self.ring_wait_s < 0:
            raise ValueError(
                f"ring_wait_s must be >= 0, got {self.ring_wait_s!r}"
            )


@dataclasses.dataclass(frozen=True)
class TableConfig:
    """A KV table: the unit the reference range-partitions across servers.

    (Reference: ``src/system/assigner.h`` NodeAssigner key-range split +
    ``src/parameter/kv_vector.h`` per-channel value arrays [U].)
    """

    name: str
    #: number of rows (vocabulary / feature capacity). Sparse tables index
    #: rows by localized key; dense tensors flatten to rows of ``dim``.
    rows: int
    #: value columns per key (the reference's ``k``-column KVVector).
    dim: int = 1
    dtype: str = "float32"
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    #: stddev of normal init for value rows; 0.0 = zeros (LR weights).
    init_scale: float = 0.0
    #: if True the table is sharded over the mesh "model" axis (row-wise,
    #: contiguous ranges — the NodeAssigner scheme); if False it is replicated.
    sharded: bool = True
    #: row gather/scatter kernel on the Push/Pull hot path: "auto"/"xla"
    #: (take / at[].set, the default; no roofline figure measured yet) or
    #: "pallas" (DMA kernels, ops/scatter.py: compiled on the TPU, an error
    #: elsewhere unless a test asks for the interpreter; dim == 128 or
    #: dim % 1024 == 0).
    scatter_impl: str = "auto"
    #: fused push apply: gather → optimizer step → scatter as ONE pass
    #: (``ops.scatter.apply_rows``).  Under ``scatter_impl="pallas"`` this
    #: is a single DMA kernel (one HBM row round-trip instead of three
    #: kernel groups); under XLA it traces the op-for-op identical graph as
    #: the legacy three-pass body, so flipping it is bitwise-neutral there.
    fused_apply: bool = True
    #: lossy wire codec for this table's PUSH plane; None = bit-exact wire.
    compression: Optional[WireCompressionConfig] = None
    #: wire-enforced consistency plane (ISSUE 20): when set, workers stamp
    #: their committed step (``__cstep__``) on this table's PUSH/PULL
    #: requests and servers gate them against the fleet's per-worker vector
    #: clock — block-the-laggard (SSP), rendezvous-barrier (BSP) or
    #: free-run (ASP).  None = ungated (the pre-ISSUE-20 wire, zero extra
    #: payload bytes).
    consistency: Optional[ConsistencyConfig] = None


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Sampled end-to-end request tracing (ISSUE 18).

    ``KVWorker`` consumes this to decide whether a PUSH/PULL submit stamps
    a trace context (``core/tracectx.py``) into its payload.  Sampling is
    a deterministic hash of ``(trace_id, seed)`` so seeded replays trace
    the same requests and unsampled requests carry zero trace bytes on
    the wire.
    """

    #: master switch; False stamps no contexts at all (the predicate the
    #: hot path is gated behind — see tools/check_wrappers.py).
    enabled: bool = True
    #: trace 1-in-N requests.  1 = every request (tests), 0 = never.
    sample_every: int = 1024
    #: seed folded into the sampling hash; replays with the same seed
    #: sample the same trace ids.
    seed: int = 0

    def __post_init__(self) -> None:
        if self.sample_every < 0:
            raise ValueError(
                f"sample_every must be >= 0, got {self.sample_every!r}"
            )


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Scheduler-side telemetry aggregator sizing (ISSUE 19).

    The aggregator keeps a bounded ring of derived rows per publishing
    node.  A fixed per-node window tuned for ~4 nodes does not survive a
    200-publisher war game: 256 rows x 200 nodes is ~50k retained rows on
    the control plane.  Instead the per-node ring capacity is derived from
    a FLEET-WIDE row budget — ``min(window, ring_budget_rows // fleet)``,
    floored at ``min_window`` — and re-derived (rings re-capped in place)
    as new publishers appear, so total retained rows stay near the budget
    at any fleet size while small fleets keep the full ``window``.
    """

    #: per-node ring rows for small fleets (the pre-ISSUE-19 constant).
    window: int = 256
    #: fleet-wide retained-row budget; per-node capacity shrinks as the
    #: publisher count grows so the scheduler's memory stays flat.
    ring_budget_rows: int = 8192
    #: per-node capacity floor — even a 1000-node fleet keeps enough rows
    #: per node for rate windows and pstop history.
    min_window: int = 8

    def __post_init__(self) -> None:
        if self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window!r}")
        if self.min_window < 1:
            raise ValueError(
                f"min_window must be >= 1, got {self.min_window!r}"
            )
        if self.min_window > self.window:
            raise ValueError(
                f"min_window ({self.min_window!r}) must be <= window "
                f"({self.window!r})"
            )
        if self.ring_budget_rows < self.window:
            raise ValueError(
                f"ring_budget_rows ({self.ring_budget_rows!r}) must be >= "
                f"window ({self.window!r})"
            )

    def node_window(self, fleet_size: int) -> int:
        """Per-node ring capacity for ``fleet_size`` publishers."""
        n = max(1, int(fleet_size))
        return max(self.min_window, min(self.window, self.ring_budget_rows // n))
