"""AdaGrad on the touched rows only, as a dict of rows: 2**30 rows are no
obstacle.  Semantics of the configurations' server rule: duplicate keys of
one push are summed first, then per touched row
``sum_sq += g*g; value -= lr / (sqrt(sum_sq) + eps) * g``."""

import numpy as np

F = np.float32


class AdaGradRows:
    def __init__(self, dim, lr, eps, l2=0.0):
        self.dim, self.lr, self.eps, self.l2 = dim, F(lr), F(eps), F(l2)
        self.value, self.sum_sq = {}, {}

    def seed_rows(self, slots, values):
        """Initial values of rows, as read from the system before a push."""
        for s, v in zip(slots.tolist(), np.asarray(values, F).reshape(-1, self.dim)):
            self.value.setdefault(s, v.copy())
            self.sum_sq.setdefault(s, np.zeros(self.dim, F))

    def push(self, slots, grads):
        grads = np.asarray(grads, F).reshape(-1, self.dim)
        uniq, inv = np.unique(slots, return_inverse=True)
        comb = np.zeros((uniq.size, self.dim), F)
        np.add.at(comb, inv, grads)  # position order, float32
        for s, g in zip(uniq.tolist(), comb):
            v = self.value[s]
            g = g + self.l2 * v
            ss = self.sum_sq[s] + g * g
            self.sum_sq[s] = ss
            self.value[s] = v - self.lr / (np.sqrt(ss) + self.eps) * g

    def rows(self, slots):
        return np.stack([self.value[s] for s in slots.tolist()])
