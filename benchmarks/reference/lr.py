"""Sparse logistic regression, one-hot features: the logit of an example is
the sum of the weights at its keys, and d(loss)/d(w_k) = sigmoid(logit) - y
at every position holding key k.  The loss is the mean log loss."""

import numpy as np

F = np.float32


def grad_rows(w_pos, labels):
    """``w_pos [B, nnz]``, ``labels [B]`` -> ``(per-position grads, loss)``."""
    w_pos, labels = np.asarray(w_pos, F), np.asarray(labels, F)
    logits = w_pos.sum(axis=1, dtype=F)
    p = F(1) / (F(1) + np.exp(-logits))
    residual = p - labels
    loss = np.mean(
        np.maximum(logits, 0) - logits * labels + np.log1p(np.exp(-np.abs(logits)))
    )
    return np.broadcast_to(residual[:, None], w_pos.shape), float(loss)
