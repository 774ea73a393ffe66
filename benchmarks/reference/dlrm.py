"""DLRM forward, loss and gradient with respect to the embedding rows.

Dense features -> bottom MLP (ReLU after every layer, the last of width
``emb_dim``); the bottom output and the ``n_sparse`` embedding rows form
``F = n_sparse + 1`` vectors; their pairwise dot products (strict upper
triangle, row-major) are concatenated after the bottom output; top MLP (ReLU
between layers, none after the last of width 1) gives the logit; the loss is
the mean log loss.  Weights arrive as ``[(W [in, out], b [out]), ...]``."""

import numpy as np

F = np.float32


def _mlp_forward(x, layers, final_relu):
    acts = [x]
    for i, (w, b) in enumerate(layers):
        x = x @ w + b
        if i < len(layers) - 1 or final_relu:
            x = np.maximum(x, 0)
        acts.append(x)
    return acts


def _mlp_backward(g, acts, layers, final_relu):
    for i in reversed(range(len(layers))):
        if i < len(layers) - 1 or final_relu:
            g = g * (acts[i + 1] > 0)
        w, _b = layers[i]
        g = g @ w.T
    return g


def loss_and_row_grads(bottom, top, dense, emb, labels):
    """``emb [B, n_sparse, D]`` -> ``(loss, d loss / d emb)``, float32."""
    dense, emb, labels = (np.asarray(a, F) for a in (dense, emb, labels))
    bottom = [(np.asarray(w, F), np.asarray(b, F)) for w, b in bottom]
    top = [(np.asarray(w, F), np.asarray(b, F)) for w, b in top]
    batch = labels.shape[0]
    b_acts = _mlp_forward(dense, bottom, final_relu=True)
    bot = b_acts[-1]
    feats = np.concatenate([bot[:, None, :], emb], axis=1)  # [B, F, D]
    inter = feats @ feats.transpose(0, 2, 1)  # [B, F, F]
    iu, ju = np.triu_indices(feats.shape[1], k=1)
    top_in = np.concatenate([bot, inter[:, iu, ju]], axis=1)
    t_acts = _mlp_forward(top_in, top, final_relu=False)
    logits = t_acts[-1][:, 0]
    loss = np.mean(
        np.maximum(logits, 0) - logits * labels + np.log1p(np.exp(-np.abs(logits)))
    )
    p = F(1) / (F(1) + np.exp(-logits))
    g_logits = ((p - labels) / F(batch))[:, None]
    g_top_in = _mlp_backward(g_logits, t_acts, top, final_relu=False)
    g_inter = np.zeros_like(inter)
    g_inter[:, iu, ju] = g_top_in[:, bot.shape[1]:]
    # inter[f, g] = feats[f] . feats[g]
    g_feats = (g_inter + g_inter.transpose(0, 2, 1)) @ feats
    return float(loss), g_feats[:, 1:, :].astype(F)
