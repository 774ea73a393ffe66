"""What the layer-pattern bodies of the hybrid path share
(``models/kimi_linear.py``, ``models/lfm2_moe.py``, ``models/laguna.py``,
``models/sdar_moe.py``, ``models/qwen3_next.py``): the held-share expert
layer, the norms and the SwiGLU around it, the checkpointed loop over a
body's layers, the counters a step returns, and the two losses: next-token
(:func:`head_loss`) and masked, weighted and unshifted
(:func:`weighted_loss`).

**The expert layer.**  ``s = sigmoid(x' W_r)`` over all routed experts
(where the body scores by a softmax, ``s = softmax(x' W_r)`` over all
routed outputs) in float32 at the highest matrix precision; ``sel =
top_k(s + bias)`` (the selection bias takes part in the selection only; a
caller without one passes none); ``w_i = scale s_i / sum_{j in sel} s_j``
(without renormalisation ``scale s_i``); ``y = sum_{i in sel, held} w_i E_i(x')``
plus a shared expert where the parameters hold one (``sigmoid(x' W_sg)
E_shared(x')`` where they hold its gate too).  **The held share**:
this process holds experts ``[first, first + held)``; the router keeps
every output; what the absent experts would add is left out, here and in
the references alike, and nothing stands in for them.  Token slots that
select a held expert are laid out by expert in rows whose groups are padded
to whole blocks; a loop over the blocks that hold rows gathers each block's
rows, runs them through its expert's three matrices (a grouped product),
weights them and adds them back to their tokens (:func:`grouped_experts`),
so the cost follows the load while the layout has room for the worst case
(every slot of every token held here: index arrays only).  No slot may be
dropped: ``moe_dropped_slots`` counts the held slots the layout gave no row
(:func:`dispatch_layout`), and a caller must find it 0.  **A static
capacity** (``capacity_factor`` c > 0): the loop runs at least the blocks of
c times the share's expected slots (:func:`capacity_blocks`), as an expert
exchange with static shapes computes its buffers whatever they hold; the
blocks past the last group hold the zero row at weight 0 and change no
result.  The work is then the capacity's until the load outgrows it; past
it the loop runs the blocks that hold rows and drops nothing.  Such a layer
counts ``moe_blocks_run``.

What differs between the bodies is :class:`ExpertLayer` (experts a token,
the scale, the share, the block, the router's scoring, the body's device
scope) and what the
layer's parameters hold (``shared``: a shared expert; ``shared_gate``: its
gate; ``expert_bias``: the selection bias, a buffer that takes no gradient
and no update), and a norm's weight (``scale``, or ``offset`` for a
zero-centred norm, :func:`norm`).

Device scopes are written as paths under the body's own
(``<root>/ps.model.<name>``, :func:`scope`): ``ps.model.moe.router`` /
``.dispatch`` / ``.experts`` / ``.combine`` / ``.shared``, ``ps.model.mlp``.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models import transformer as tfm

HIGHEST = jax.lax.Precision.HIGHEST

#: what a step returns beside the loss, summed (``max``: largest) over the
#: expert layers
COUNTERS = ("moe_held_slots", "moe_dropped_slots", "moe_max_expert_slots")


def scope(root: str, name: str):
    """Device scope ``ps.model.<name>``, written as a path under the body's
    own (``<root>/ps.model.<name>``).  An operation of a transposed
    checkpoint or of a ``custom_vjp``'s rule loses the name stack around it,
    and the profiler leaves a ``while``'s own name out of the trace: a
    reader that splits the busy time by outermost scope
    (``scoped_device_pct``) gives such a ``while`` its program's scope only
    if every scoped operation of the program starts with that one (my chip
    run, PR 28: 11 % scoped without this, the step's ``lax.map`` loops
    unscoped)."""
    return jax.named_scope(f"{root}/ps.model.{name}")


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    """What an expert layer is told by the body that calls it."""

    root: str  # the body's device scope
    n_routed: int
    #: the share held here: experts [first, first + held)
    held: int
    first: int
    top_k: int
    scale: float
    renormalize: bool
    block: int  # rows of a block of the dispatch layout
    #: the router's scores: ``sigmoid`` of each output, or ``softmax`` over
    #: all ``n_routed`` outputs (so the held share routes over every expert)
    scoring: str = "sigmoid"
    #: the share's static capacity as a multiple of its expected slots
    #: (``tokens x top_k x held / n_routed``); 0: none, the loop runs the
    #: blocks that hold rows only
    capacity_factor: float = 0.0


# -- what every layer uses ------------------------------------------------------
def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def norm(p, x, eps):
    """The RMSNorm whose weight ``p`` holds: ``scale`` (``x^ * scale``) or
    ``offset``, zero-centred (``x^ * (1 + offset)``)."""
    if "offset" in p:
        return rms_norm(x, 1.0 + p["offset"], eps)
    return rms_norm(x, p["scale"], eps)


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``x [B, S, *C]``, ``w [W,
    *C]``; ``y_t = sum_i w_i x_{t - (W - 1) + i}``."""
    width, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0)) + ((0, 0),) * (x.ndim - 2))
    return sum(xp[:, i:i + S] * w[i] for i in range(width))


def l2_normalize(x):
    """``x / |x|`` over the last axis, 1e-6 under the root."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def swiglu(p, x):
    h = jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
    return h @ p["down"]["kernel"]


def swiglu_shapes(d_model: int, width: int, lead=()) -> dict:
    return {"gate": {"kernel": (*lead, d_model, width)},
            "up": {"kernel": (*lead, d_model, width)},
            "down": {"kernel": (*lead, width, d_model)}}


def by_sequence(f, p, x):
    """``f(p, x)`` one sequence at a time (``lax.map`` over the batch): the
    mixers and the dense MLP treat sequences independently, so this changes
    no result and divides their live activations by the batch."""
    return jax.lax.map(lambda row: f(p, row[None])[0], x)


# -- parameters -----------------------------------------------------------------
def is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_tree(shapes, key, init_scale: float, special):
    """Seeded float32 parameters for the tree ``{name: ... shape}``: a leaf's
    key is the tree's folded with a hash of its path, so a leaf keeps its
    values whatever else the tree holds.  ``scale`` leaves are ones,
    ``bias`` and ``offset`` leaves zeros, ``special(leaf, key, shape)``
    gives a body's own (None: not one of them), every other leaf is normal
    at ``init_scale``."""
    def make(path, shape):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "scale":
            return jnp.ones(shape, jnp.float32)
        if leaf in ("bias", "offset"):
            return jnp.zeros(shape, jnp.float32)
        own = special(leaf, k, shape)
        if own is not None:
            return own
        return init_scale * jax.random.normal(k, shape, jnp.float32)

    return jax.tree_util.tree_map_with_path(make, shapes, is_leaf=is_shape)


def count_params(shapes, layer: ExpertLayer) -> dict:
    """``held``: parameters of the body ``shapes``; ``active``: those a
    token's forward multiplies with here, a routed expert counted by the
    chance that a slot picks it (top-k x held / routed experts a layer).
    What the 6ND rule takes for a body with experts."""
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(s)) for s in jax.tree.leaves(tree, is_leaf=is_shape)
    )
    routed = sum(
        size(v["moe"]["experts"]) for v in shapes.values() if "moe" in v
    )
    share = layer.top_k / layer.n_routed
    return {"held": size(shapes),
            "active": size(shapes) - routed + int(routed * share)}


# -- the expert layer -----------------------------------------------------------
def _expert_rows(root, xz, ex, rows, e):
    """A block's rows through expert ``e``: ``(x, silu'(a) parts, h, out)``."""
    with scope(root, "moe.dispatch"):
        xb = xz[rows]
    with scope(root, "moe.experts"):
        a, b = xb @ ex["gate"][e], xb @ ex["up"][e]
        h = jax.nn.silu(a) * b
        return xb, a, b, h, h @ ex["down"][e]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def grouped_experts(root, xz, ex, weight, rows, block_expert, n_live):
    """The held experts' weighted outputs added back to their tokens.

    ``xz [N + 1, D]``: the tokens and a zero row; ``rows [nb, bm]``: the
    token of every row of every block (``N``: none); ``weight [nb, bm]``;
    ``block_expert [nb]``; the first ``n_live`` blocks hold rows, the rest
    none; ``root``: the body's device scope.  A ``fori_loop`` over the live
    blocks only: the layout has room for every slot of every token, the
    cost is the load's.  Its backward is written out below (a loop with a
    traced trip count has no derivative of jax's own, and under ``lax.scan``
    + ``lax.cond`` every block's residuals are stacked for all ``nb``
    blocks, 20 GB at Kimi-Linear's published widths: found by compiling for
    the chip, PR 28): it recomputes a block's hidden activations and keeps
    nothing per block."""

    def block(i, y):
        _xb, _a, _b, _h, out = _expert_rows(root, xz, ex, rows[i], block_expert[i])
        with scope(root, "moe.combine"):
            return y.at[rows[i]].add(out * weight[i][:, None])

    return jax.lax.fori_loop(
        0, n_live, block, jnp.zeros(xz.shape, jnp.float32)
    )


def _grouped_fwd(root, xz, ex, weight, rows, block_expert, n_live):
    y = grouped_experts(root, xz, ex, weight, rows, block_expert, n_live)
    return y, (xz, ex, weight, rows, block_expert, n_live)


def _grouped_bwd(root, res, dy):
    xz, ex, weight, rows, block_expert, n_live = res

    def block(i, carry):
        dxz, d_ex, d_weight = carry
        e, r = block_expert[i], rows[i]
        xb, a, b, h, out = _expert_rows(root, xz, ex, r, e)
        with scope(root, "moe.combine"):
            dyb = dy[r]
            d_weight = d_weight.at[i].set(jnp.sum(out * dyb, axis=-1))
            d_out = dyb * weight[i][:, None]
        with scope(root, "moe.experts"):
            dh = d_out @ ex["down"][e].T
            sig = jax.nn.sigmoid(a)
            da = dh * b * sig * (1.0 + a * (1.0 - sig))  # silu'(a)
            db = dh * a * sig
            d_ex = {
                "gate": d_ex["gate"].at[e].add(xb.T @ da),
                "up": d_ex["up"].at[e].add(xb.T @ db),
                "down": d_ex["down"].at[e].add(h.T @ d_out),
            }
            dxb = da @ ex["gate"][e].T + db @ ex["up"][e].T
        with scope(root, "moe.dispatch"):
            return dxz.at[r].add(dxb), d_ex, d_weight

    dxz, d_ex, d_weight = jax.lax.fori_loop(0, n_live, block, (
        jnp.zeros(xz.shape, jnp.float32),
        jax.tree.map(jnp.zeros_like, ex),
        jnp.zeros(weight.shape, jnp.float32),
    ))
    # the zero row takes no gradient; rows, experts and the count are integers
    return dxz.at[-1].set(0.0), d_ex, d_weight, None, None, None


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def moe_capacity(layer: ExpertLayer, tokens: int) -> int:
    """Rows of the dispatch layout, a whole number of blocks: room for the
    worst case, every slot of every token held here and every expert's last
    block all but empty."""
    bm = layer.block
    rows = tokens * min(layer.top_k, layer.held) + layer.held * (bm - 1)
    return -(-rows // bm) * bm


def capacity_blocks(layer: ExpertLayer, tokens: int) -> int:
    """Blocks the loop runs at least under a static capacity: ``c x tokens
    x top_k x held / n_routed`` rows in whole blocks, no more than the
    layout's (:func:`moe_capacity`)."""
    rows = layer.capacity_factor * tokens * layer.top_k * layer.held / layer.n_routed
    return min(-(-int(np.ceil(rows)) // layer.block),
               moe_capacity(layer, tokens) // layer.block)


def dispatch_layout(group, held: int, block: int, rows: int):
    """Where every token slot goes.  ``group [slots]``: the held expert a slot
    selected (``held``: none of them); ``rows``: rows of the layout, a whole
    number of ``block``s.  The slots are laid out by expert, each expert's
    group padded to whole blocks.  Returns ``(slot [rows], filled [rows],
    block_expert [rows / block], live blocks, counters)``: the slot that fills
    a row, whether one does, a block's expert, how many leading blocks hold
    rows.  ``moe_dropped_slots`` counts the held slots that found no row:
    0 whenever ``rows`` is :func:`moe_capacity`'s, which a caller checks."""
    nb = rows // block
    order = jnp.argsort(group, stable=True)  # slots by expert, absent last
    # where each held expert's slots start among the sorted ones
    first_slot = jnp.searchsorted(group[order], jnp.arange(held + 1))
    counts = first_slot[1:] - first_slot[:-1]
    padded = -(-counts // block) * block  # every group a whole number of blocks
    ends = jnp.cumsum(padded)
    first_row = ends - padded
    # layout row -> its block's expert -> the sorted slot that fills it
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(nb) * block, side="right"), held - 1
    )
    row_expert = jnp.repeat(block_expert, block)
    offset = jnp.arange(rows) - first_row[row_expert]
    filled = (offset >= 0) & (offset < counts[row_expert])
    slot = order[jnp.where(filled, first_slot[row_expert] + offset, 0)]
    counters = {
        "moe_held_slots": jnp.sum(counts),
        "moe_dropped_slots": jnp.sum(counts) - jnp.sum(filled),
        "moe_max_expert_slots": jnp.max(counts),
    }
    # the blocks past the last group hold no row: they are not run
    live = jnp.sum((jnp.arange(nb) * block < ends[-1]).astype(jnp.int32))
    return slot, filled, block_expert, live, counters


def route(layer: ExpertLayer, router_kernel, xt, bias=None):
    """``xt [N, D]`` -> ``(idx [N, k] of all routed experts, w [N, k])``, in
    float32 at the highest matrix precision.  ``bias [n_routed]``, where a
    caller has one, is added to the scores for the selection alone: the
    weights are the selected experts' own scores."""
    logits = jnp.dot(xt, router_kernel, precision=HIGHEST)
    if layer.scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    _top, idx = jax.lax.top_k(s if bias is None else s + bias, layer.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if layer.renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * layer.scale


def moe_layer(layer: ExpertLayer, p, x):
    """``x [B, S, D]`` (normed) -> ``(y, counters)``: the held experts' part
    of the routed sum, plus the shared expert where ``p`` holds one, under
    its sigmoid gate ``sigmoid(x W_sg)`` where ``p`` holds ``shared_gate``.
    ``p["expert_bias"]``, where present, is the selection bias."""
    B, S, D = x.shape
    N, k, Eh, bm = B * S, layer.top_k, layer.held, layer.block
    nb = moe_capacity(layer, N) // bm
    xt = x.reshape(N, D)
    with scope(layer.root, "moe.router"):
        idx, w = route(layer, p["router"]["kernel"], xt, p.get("expert_bias"))
    with scope(layer.root, "moe.dispatch"):
        local = idx - layer.first
        group = jnp.where((local >= 0) & (local < Eh), local, Eh).reshape(-1)
        slot, filled, block_expert, live, counters = dispatch_layout(
            group, Eh, bm, nb * bm
        )
        # a row's token (N: none, the zero row) and its weight
        token = jnp.where(filled, slot // k, N).astype(jnp.int32)
        weight = jnp.where(filled, w.reshape(-1)[slot], 0.0)
        xz = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])
        if layer.capacity_factor:
            # past the last group a block holds the zero row at weight 0
            live = jnp.maximum(live, capacity_blocks(layer, N))
            counters = dict(counters, moe_blocks_run=live)
    y = grouped_experts(
        layer.root, xz, p["experts"], weight.reshape(nb, bm),
        token.reshape(nb, bm), block_expert, live,
    )
    y = y[:N]
    if "shared" in p:
        with scope(layer.root, "moe.shared"):
            shared = swiglu(p["shared"], xt)
            if "shared_gate" in p:  # [D, 1]: one gate a token
                shared = jax.nn.sigmoid(xt @ p["shared_gate"]["kernel"]) * shared
            y = y + shared
    return y.reshape(B, S, D), counters


def mlp_block(layer: ExpertLayer, kind: str, eps: float, p, x):
    """A layer's second half, ``x + FF(RMSNorm(x))`` -> ``(x, counters)``:
    ``kind`` ``dense`` (a SwiGLU, no counters) or ``experts``."""
    h = norm(p["mlp_norm"], x, eps)
    if kind == "dense":
        with scope(layer.root, "mlp"):
            return x + swiglu(p["mlp"], h), {}
    y, counters = moe_layer(layer, p["moe"], h)
    return x + y, counters


# -- the loop over a body's layers ------------------------------------------------
def trunk(layer: ExpertLayer, kinds, mixer_block, eps, per_sequence, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``
    through the layers ``kinds`` (``[(mixer, mlp), ...]``), the final norm
    included.  ``mixer_block(mixer) -> f(p, x)`` is a layer's first half;
    each mixer and each MLP is a ``jax.checkpoint`` of its own.
    ``per_sequence``: the mixers and the dense MLP run one sequence at a
    time (:func:`by_sequence`)."""
    x = x.astype(jnp.float32)
    zero = jnp.zeros((), jnp.int32)
    held = dropped = most = zero
    run = None  # blocks the expert loops ran, where a layer counts them
    for i, (mixer, mlp) in enumerate(kinds):
        p = params[f"layer_{i}"]
        mix = jax.checkpoint(mixer_block(mixer))
        ffn = jax.checkpoint(functools.partial(mlp_block, layer, mlp, eps))
        if per_sequence:
            mix = functools.partial(by_sequence, mix)
            if mlp == "dense":
                dense = ffn
                ffn = lambda p, x: (  # noqa: E731
                    by_sequence(lambda p, x: dense(p, x)[0], p, x), {}
                )
        x = mix(p, x)
        x, c = ffn(p, x)
        if c:
            held = held + c["moe_held_slots"]
            dropped = dropped + c["moe_dropped_slots"]
            most = jnp.maximum(most, c["moe_max_expert_slots"])
            if "moe_blocks_run" in c:
                run = c["moe_blocks_run"] + (zero if run is None else run)
    hidden = norm(params["final_norm"], x, eps)
    counters = dict(zip(COUNTERS, (held, dropped, most)))
    if run is not None:
        counters["moe_blocks_run"] = run
    return hidden, counters


def hybrid_body(body, cfg, seed: int, loss_chunk: int):
    """A layer-pattern body as the hybrid trainer takes one (the five of
    ``models/transformer.py::hybrid_body``).  ``body``: the body's module
    (``init_params``, ``loss_fn``, ``logits``, ``count_params``,
    ``BODY_SCOPE``); a routed expert counts among the active parameters by
    the chance that a slot picks it."""
    params = jax.jit(lambda key: body.init_params(cfg, key))(
        jax.random.PRNGKey(seed)
    )

    def body_loss(params, emb_in, targets):
        return body.loss_fn(cfg, params, emb_in, targets, loss_chunk)

    return (params, body_loss, lambda p, e: body.logits(cfg, p, e),
            body.count_params(cfg)["active"], body.BODY_SCOPE)


def head_logits(hidden, head):
    return jnp.einsum(
        "bsd,dv->bsv", hidden, head, preferred_element_type=jnp.float32
    )


def head_loss(root, hidden, head, targets, loss_chunk: int):
    """Next-token loss over the held vocabulary; ``loss_chunk > 0`` fuses the
    head into the chunked loss."""
    with scope(root, "head_loss"):
        if loss_chunk > 0:
            return tfm.chunked_causal_lm_loss(hidden, head, targets, loss_chunk)
        return tfm.causal_lm_loss(head_logits(hidden, head), targets)


def weighted_loss(root, hidden, head, targets, weights, loss_chunk: int):
    """``sum_i weights_i * -log p(targets_i | hidden_i)`` over the positions
    of ``hidden [B, S, D]``: read at the position itself (no shift), over
    the held vocabulary; a position of weight 0 adds nothing.  ``loss_chunk
    > 0`` fuses the head into a loop over chunks of positions, each a
    ``jax.checkpoint`` (``tfm.chunked_causal_lm_loss``'s form): no ``[B, S,
    vocab]`` logits are live, and the backward computes a chunk's again."""

    def nll(xc, tc, wc):
        logp = jax.nn.log_softmax(jnp.einsum(
            "bcd,dv->bcv", xc, head, preferred_element_type=jnp.float32
        ))
        return -jnp.sum(
            wc * jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        )

    with scope(root, "head_loss"):
        B, S, _ = hidden.shape
        if loss_chunk <= 0:
            return nll(hidden, targets, weights)
        chunk = min(loss_chunk, S)
        pad = (-S) % chunk
        xs = (hidden, targets, weights)
        if pad:  # the padding's weight is 0
            xs = tuple(
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in xs
            )
        xs = tuple(
            jnp.moveaxis(x.reshape(B, -1, chunk, *x.shape[2:]), 1, 0) for x in xs
        )
        part = jax.checkpoint(nll)
        total, _ = jax.lax.scan(
            lambda acc, x: (acc + part(*x), None), jnp.float32(0.0), xs
        )
        return total
