"""``ops/delta_rule.py``: the chunked gated delta rule against the recurrence
token by token, forward and gradients, at a length that is no multiple of the
chunk, with decays near 0 and near 1.

Tolerances: both sides are float32 at the highest matrix precision here, so
what is left is summation order: 1e-5 of the largest entry forward, 1e-4 for
gradients (the log-decay's gradient sums ``S`` products of small terms)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.ops import delta_rule as dr

#: log-decay a step as a function of a uniform draw in [0, 1)
DECAYS = {
    "mid": lambda u: -u,
    "near1": lambda u: -1e-4 * u,  # a ~ 1: the state never forgets
    # a down to e^-10 a step: e^-640 inside a chunk of 64, where a factor
    # e^(-G) alone overflows float32
    "near0": lambda u: -8.0 * u - 2.0,
}


def draws(decay, B=2, S=150, H=3, K=16, V=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, K))
    k = jax.random.normal(ks[1], (B, S, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, V))
    g = DECAYS[decay](jax.random.uniform(ks[3], (B, S, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_forward_is_the_recurrence(decay, chunk):
    args = draws(decay)
    with jax.default_matmul_precision("highest"):
        o, s = jax.jit(lambda *a: dr.chunk_kda(*a, chunk=chunk))(*args)
    o_ref, s_ref = dr.recurrent_kda(*args)
    assert bool(jnp.isfinite(o).all())
    assert rel(o, o_ref) < 1e-5 and rel(s, s_ref) < 1e-5


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_gradients_are_the_recurrence_s(decay, chunk):
    args = draws(decay)

    def loss(f, *a):
        o, s = f(*a)
        return jnp.sum(jnp.sin(o)) + jnp.sum(s * s)

    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(
            lambda *a: loss(lambda *b: dr.chunk_kda(*b, chunk=chunk), *a),
            argnums=(0, 1, 2, 3, 4),
        ))(*args)
    want = jax.grad(lambda *a: loss(dr.recurrent_kda, *a),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert rel(a, b) < 1e-4, (name, rel(a, b))


def test_a_sequence_continues_from_a_state():
    q, k, v, g, beta = draws("mid", S=96)
    with jax.default_matmul_precision("highest"):
        whole, s_whole = dr.chunk_kda(q, k, v, g, beta, chunk=16)
        cut = 40  # no multiple of the chunk
        first, s = dr.chunk_kda(*(a[:, :cut] for a in (q, k, v, g, beta)), chunk=16)
        second, s2 = dr.chunk_kda(
            *(a[:, cut:] for a in (q, k, v, g, beta)), chunk=16, initial_state=s
        )
    assert rel(jnp.concatenate([first, second], axis=1), whole) < 1e-5
    assert rel(s2, s_whole) < 1e-5


@pytest.mark.parametrize("inclusive", [False, True])
def test_decayed_gram_is_the_sum_it_says(inclusive):
    rng = np.random.default_rng(3)
    C, K = 64, 8
    x, y = rng.normal(size=(2, C, K)).astype(np.float32)
    G = np.cumsum(-rng.uniform(0, 6, size=(C, K)), axis=0).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(dr.decayed_gram(
            jnp.asarray(x), jnp.asarray(y), jnp.asarray(G), sub=16,
            inclusive=inclusive,
        ))
    want = np.zeros((C, C))
    for t in range(C):
        for s in range(t + 1 if inclusive else t):
            want[t, s] = np.sum(
                x[t].astype(np.float64) * y[s] * np.exp(G[t].astype(np.float64) - G[s])
            )
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
