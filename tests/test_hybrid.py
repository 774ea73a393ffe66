"""Hybrid LM trainer (BASELINE config #5): PS embeddings + GSPMD body.

The composition test VERDICT r1 asked for: ONE training step where the
embedding rows travel as Van PUSH/PULL traffic through a real
KVWorker/KVServer topology while the dense transformer body trains
synchronously under GSPMD (XLA-inserted allreduce on the data axis), with
loss decreasing.
"""

import numpy as np
import pytest

import jax

from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.learner import hybrid
from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.parallel import mesh as mesh_lib
from parameter_server_tpu.utils.keys import IdentityLocalizer

NUM_SERVERS = 2


@pytest.fixture
def cluster():
    van = LoopbackVan()
    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    table_cfgs = {"emb": hybrid.embedding_table_cfg(cfg, learning_rate=0.1)}
    servers = []
    for s in range(NUM_SERVERS):
        post = Postoffice(f"S{s}", van)
        servers.append(KVServer(post, table_cfgs, s, NUM_SERVERS))
    wpost = Postoffice("W0", van)
    worker = KVWorker(
        wpost,
        table_cfgs,
        NUM_SERVERS,
        localizers=hybrid.embedding_localizers(cfg),
    )
    try:
        yield cfg, van, servers, worker
    finally:
        van.close()


def _tokens(cfg, rng, batch=8, seq=16):
    # structured stream (periodic patterns) so a tiny model can learn it
    base = rng.integers(0, cfg.vocab_size, size=(batch, 1))
    offs = np.arange(seq)[None, :]
    return ((base + offs) % cfg.vocab_size).astype(np.int32)


def test_hybrid_trains_and_routes_embeddings_via_van(cluster):
    cfg, van, servers, worker = cluster
    mesh = mesh_lib.make_mesh((4, 2))
    trainer = hybrid.HybridLMTrainer(
        cfg, mesh, worker, learning_rate=3e-3, max_delay=0
    )
    rng = np.random.default_rng(0)
    losses = [trainer.step(_tokens(cfg, rng)) for _ in range(12)]
    trainer.drain()
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses
    # embedding traffic went through the Van to BOTH range shards
    assert all(s.pushes > 0 and s.pulls > 0 for s in servers)
    assert van.sent_messages > 0
    # and the PS table actually learned (moved off its init)
    t0 = servers[0].tables["emb"]
    assert float(np.abs(np.asarray(t0.state["sum_sq"][:-1])).sum()) > 0


def test_hybrid_body_step_contains_allreduce(cluster):
    """The dense half really is sync-GSPMD: the compiled step carries an
    all-reduce over the data axis (the config's 'XLA allreduce')."""
    cfg, van, servers, worker = cluster
    mesh = mesh_lib.make_mesh((4, 2))
    trainer = hybrid.HybridLMTrainer(cfg, mesh, worker, max_delay=0)
    rng = np.random.default_rng(1)
    tokens = _tokens(cfg, rng)
    import jax.numpy as jnp

    emb = worker.pull_sync("emb", tokens, timeout=30)
    lowered = trainer._step.lower(
        trainer.params,
        trainer.opt_state,
        jax.device_put(jnp.asarray(emb, jnp.float32), trainer._batch3),
        jax.device_put(jnp.asarray(tokens, jnp.int32), trainer._batch2),
    )
    hlo = lowered.compile().as_text()
    assert "all-reduce" in hlo


def test_hybrid_ssp_bounded_delay(cluster):
    """max_delay=tau keeps at most tau embedding pushes un-acked (SSP)."""
    cfg, van, servers, worker = cluster
    mesh = mesh_lib.make_mesh((4, 2))
    trainer = hybrid.HybridLMTrainer(
        cfg, mesh, worker, learning_rate=3e-3, max_delay=3
    )
    rng = np.random.default_rng(2)
    losses = [trainer.step(_tokens(cfg, rng)) for _ in range(10)]
    assert len(trainer._inflight) <= 3
    trainer.drain()
    assert not trainer._inflight
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-3:]) < np.mean(losses[:3]), losses


def test_hybrid_rejects_tied_embeddings():
    cfg = tfm.tiny_config(causal=True, tie_embeddings=True)
    with pytest.raises(ValueError, match="untied"):
        hybrid.HybridLMTrainer(cfg, mesh_lib.make_mesh((2, 4)), worker=None)


def test_identity_localizer_contract():
    loc = IdentityLocalizer(100)
    from parameter_server_tpu.utils.keys import PAD_KEY

    out = loc.assign(np.array([0, 5, 99, PAD_KEY], dtype=np.uint64))
    assert out.tolist() == [0, 5, 99, 100]
    with pytest.raises(ValueError, match="outside"):
        loc.assign(np.array([150], dtype=np.uint64))


class _DelayVan(LoopbackVan):
    """Loopback with synthetic per-reply latency (a fake DCN RTT).

    The delay is CONCURRENT (timer-delivered), modeling wire latency: an
    inline sleep would serialize every reply through the delivery path and
    model a throughput limit instead, which no amount of prefetching can
    hide (the r3 flakiness of the prefetch test, ADVICE r3)."""

    def __init__(self, reply_delay_s: float):
        super().__init__()
        self.reply_delay_s = reply_delay_s

    def send(self, msg):
        import threading as _threading

        if not msg.is_request:  # delay replies: worker-visible Van latency
            t = _threading.Timer(
                self.reply_delay_s, lambda: LoopbackVan.send(self, msg)
            )
            t.daemon = True
            t.start()
            return True
        return super().send(msg)


def _hybrid_cluster(van, cfg, *, device_replies=False, lr=0.1):
    table_cfgs = {"emb": hybrid.embedding_table_cfg(cfg, learning_rate=lr)}
    servers = [
        KVServer(
            Postoffice(f"S{s}", van), table_cfgs, s, NUM_SERVERS,
            device_replies=device_replies,
        )
        for s in range(NUM_SERVERS)
    ]
    worker = KVWorker(
        Postoffice("W0", van), table_cfgs, NUM_SERVERS,
        localizers=hybrid.embedding_localizers(cfg),
    )
    return servers, worker


def test_hybrid_device_resident_plane_matches_host_plane():
    """device_replies + push_device == numpy plane, loss-for-loss.

    This is the zero-copy mode (SURVEY §2 #19): pulled rows arrive as
    jax Arrays, pushed gradients leave as jax Arrays; only int32 token ids
    touch the host.
    """
    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    losses = {}
    for mode in (False, True):
        van = LoopbackVan()
        try:
            _servers, worker = _hybrid_cluster(van, cfg, device_replies=mode)
            tr = hybrid.HybridLMTrainer(
                cfg, mesh, worker, learning_rate=1e-2, max_delay=0, seed=3
            )
            rng = np.random.default_rng(5)
            losses[mode] = [tr.step(_tokens(cfg, rng)) for _ in range(4)]
            tr.drain()
        finally:
            van.close()
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-4)
    assert losses[True][-1] < losses[True][0]


def test_hybrid_pull_replies_are_device_arrays():
    """With device_replies the Van reply payloads are jax Arrays (no D2H)."""
    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    van = LoopbackVan()
    try:
        _servers, worker = _hybrid_cluster(van, cfg, device_replies=True)
        keys = np.arange(12, dtype=np.uint64).reshape(3, 4)
        ts = worker.pull("emb", keys)
        out = worker.pull_result_device(ts, timeout=30)
        assert isinstance(out, jax.Array)
        assert out.shape == (3, 4, cfg.d_model)
        # and a device push round-trips without numpy in the values
        import jax.numpy as jnp

        g = jnp.ones((12, cfg.d_model), jnp.float32)
        worker.wait(worker.push_device("emb", keys.reshape(-1), g), timeout=30)
        after = worker.pull_result_device(worker.pull("emb", keys), timeout=30)
        assert not np.allclose(np.asarray(after), np.asarray(out))
    finally:
        van.close()


def test_hybrid_prefetch_hides_pull_latency():
    """Announced next_tokens -> the pull's Van latency hides behind the
    body step (>= 50% hidden vs the synchronous pull; VERDICT r2 #2).

    The tiny CPU body finishes in milliseconds, so the "long device step"
    the prefetch hides behind is emulated with a sleep between steps —
    exactly the pipeline position body compute occupies on hardware.  RTT
    0.2 s against a 0.3 s step leaves a wide, GC-proof margin (ADVICE r3
    medium: the old 50 ms margin was compile-noise flaky)."""
    import time as _time

    from parameter_server_tpu.utils.trace import Tracer

    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    delay = 0.2

    def run(prefetch: bool) -> float:
        van = _DelayVan(delay)
        try:
            _servers, worker = _hybrid_cluster(van, cfg, device_replies=True)
            tracer = Tracer()
            tr = hybrid.HybridLMTrainer(
                cfg, mesh, worker, learning_rate=1e-2, max_delay=2,
                tracer=tracer,
            )
            rng = np.random.default_rng(9)
            batches = [_tokens(cfg, rng, batch=16, seq=32) for _ in range(6)]
            for i, b in enumerate(batches):
                nxt = batches[i + 1] if prefetch and i + 1 < len(batches) else None
                tr.step(b, next_tokens=nxt)
                if i + 1 < len(batches):
                    _time.sleep(0.3)  # the emulated long body step
            tr.drain()
            waits = [s[2] for s in tracer.spans("ps.hybrid.pull_wait")]
            # skip step 0 (never prefetched)
            return float(np.mean(waits[1:]))
        finally:
            van.close()

    sync_wait = run(prefetch=False)
    prefetched_wait = run(prefetch=True)
    if prefetched_wait >= 0.5 * sync_wait:
        # one retry before failing: a GC pause or neighboring-test compile
        # can inflate a single measurement (ADVICE r3 medium)
        sync_wait = run(prefetch=False)
        prefetched_wait = run(prefetch=True)
    assert sync_wait > delay * 0.9  # the synthetic RTT is actually visible
    assert prefetched_wait < 0.5 * sync_wait, (sync_wait, prefetched_wait)


def test_hybrid_dashboard_reports_mfu():
    """The hybrid trainer's dashboard rows carry MFU (6ND model FLOPs)."""
    import io
    import json as json_lib

    from parameter_server_tpu.utils import metrics as metrics_lib

    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    van = LoopbackVan()
    try:
        _servers, worker = _hybrid_cluster(van, cfg)
        sink = io.StringIO()
        tr = hybrid.HybridLMTrainer(
            cfg, mesh, worker,
            # the CPU has no entry in the peak table: give the denominator
            dashboard=metrics_lib.Dashboard(
                jsonl=sink, print_every=0, peak_flops=1e12
            ),
        )
        rng = np.random.default_rng(1)
        tr.step(_tokens(cfg, rng))
        tr.drain()
        row = json_lib.loads(sink.getvalue().splitlines()[0])
        assert row["mfu_pct"] > 0
        assert row["emb_plane_mb"] > 0
    finally:
        van.close()


def test_hybrid_checkpoint_resume_continues_exactly(tmp_path):
    """Config #5 checkpoint covers BOTH planes (PS emb shards + body
    params/adamw): a fresh cluster restored at step k replays the
    uninterrupted run's suffix loss-for-loss."""
    root = str(tmp_path / "hybrid_ckpt")
    cfg = tfm.tiny_config(causal=True, tie_embeddings=False)
    mesh = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    rng = np.random.default_rng(12)
    batches = [_tokens(cfg, rng) for _ in range(6)]

    def fresh():
        van = LoopbackVan()
        _servers, worker = _hybrid_cluster(van, cfg)
        tr = hybrid.HybridLMTrainer(
            cfg, mesh, worker, learning_rate=1e-2, max_delay=0, seed=7
        )
        return van, tr

    # uninterrupted reference
    van, tr = fresh()
    try:
        for b in batches[:3]:
            tr.step(b)
        tr.save(root, step=3)
        tail_ref = [tr.step(b) for b in batches[3:]]
        tr.drain()
    finally:
        van.close()

    # fresh everything (server tables re-init, body re-init), restore, resume
    van, tr2 = fresh()
    try:
        tr2.restore(root, step=3)
        tail = [tr2.step(b) for b in batches[3:]]
        tr2.drain()
    finally:
        van.close()
    np.testing.assert_allclose(tail, tail_ref, rtol=1e-6, atol=1e-7)
