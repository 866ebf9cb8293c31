"""Plain reference of CE-FedAvg (arXiv:2205.13054, Algorithm 1).

Written from the paper and the configuration files alone; it imports
nothing of the program and takes nothing the program made. Given the
same seed it starts from the same weights (the model's published
initialisation), draws the same batches (the algorithm's key schedule,
below) and the same cohorts (the scenario's keyed draws), and then does
the arithmetic in the most direct way: one row at a time, float32 at
``highest`` matmul precision, mixing operators built in float64 from the
federation's graph and applied one after the other.

Key schedule of a round (as the federation's configuration fixes it):
the run key is ``PRNGKey(seed + 1)``; each round splits it into the next
run key and the round key; the round key splits into one key per block
(``q`` blocks of ``tau`` local steps); a block key splits into one key
per local step. A step draws ``batch`` sample indices per row:
``randint(step_key, (n, batch))`` over the resident federation, or
``randint(fold_in(step_key, client_id), (batch,))`` per client of a
streamed population, on data shard ``client_id % shards``.

The control runs this same code in ``bfloat16``: parameters, momentum,
activations and mixing all in the precision below the configuration's.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# -- the federation's mixing operators (paper eq. 11), float64 ---------------


def ring(m: int) -> np.ndarray:
    """Adjacency of the edge servers' ring backhaul."""
    adj = np.zeros((m, m), bool)
    for i in range(m):
        j = (i + 1) % m
        if i != j:
            adj[i, j] = adj[j, i] = True
    return adj


def metropolis(adj: np.ndarray) -> np.ndarray:
    """Metropolis-Hastings weights on the backhaul graph."""
    m = adj.shape[0]
    deg = adj.sum(1)
    H = np.zeros((m, m))
    for i in range(m):
        for j in range(m):
            if adj[i, j]:
                H[i, j] = 1.0 / (max(deg[i], deg[j]) + 1.0)
    H[np.diag_indices(m)] = 1.0 - H.sum(1)
    return H


def cluster_average(labels: np.ndarray, mask: np.ndarray,
                    m: int) -> np.ndarray:
    """(m, n): row c averages cluster c's participating members, or all
    its members when none participates."""
    n = labels.shape[0]
    P = np.zeros((m, n))
    for c in range(m):
        members = labels == c
        part = members & (mask > 0)
        use = part if part.any() else members
        if use.any():
            P[c, use] = 1.0 / use.sum()
    return P


def operators(labels: np.ndarray, mask: np.ndarray, H: np.ndarray,
              pi: int):
    """(W_intra, W_inter): every device takes its cluster's participant
    average; at the backhaul boundary the cluster averages then gossip
    ``pi`` rounds over H (rows renormalised)."""
    m = H.shape[0]
    B = np.zeros((m, labels.shape[0]))
    B[labels, np.arange(labels.shape[0])] = 1.0
    P = cluster_average(labels, mask, m)
    W_inter = B.T @ np.linalg.matrix_power(H, pi) @ P
    return B.T @ P, W_inter / W_inter.sum(1, keepdims=True)


# -- keyed scenario draws (the deployment's sampling rules) -----------------


def population_cohort(seed: int, r: int, m: int, size: int, per: int,
                      dropout: float) -> np.ndarray:
    """A streamed population's cohort in round ``r``: cluster c owns ids
    [c*size, (c+1)*size) and samples ``per`` of them, keyed (seed, r,
    22, c), sorted, each dropped with probability ``dropout``."""
    parts = []
    first = None
    for c in range(m):
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(seed), int(r), 22, int(c)]))
        k = min(per, size)
        picks = c * size + np.sort(rng.choice(size, size=k, replace=False))
        if first is None:
            first = int(picks[0])
        parts.append(picks[rng.random(k) >= dropout])
    ids = np.concatenate(parts).astype(np.int64)
    return ids if ids.size else np.asarray([first], np.int64)


# -- per-leaf statistics -----------------------------------------------------


def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


def leaf_norms(tree, sub=None) -> Dict[str, float]:
    """Frobenius norm of each leaf (of ``tree - sub``), over all rows."""
    leaves = jax.tree.leaves(tree)
    subs = jax.tree.leaves(sub) if sub is not None else [None] * len(leaves)
    out = {}
    for name, a, b in zip(leaf_names(tree), leaves, subs):
        a = jnp.asarray(a, jnp.float32)
        if b is not None:
            a = a - jnp.asarray(b, jnp.float32)
        out[name] = float(jnp.sqrt(jnp.sum(a * a)))
    return out


# -- the reference trainer ---------------------------------------------------


class Reference:
    """CE-FedAvg from the configuration: ``run(rounds)`` returns the
    per-leaf norms the check compares (momentum after round 1, change
    of the parameters after each round)."""

    def __init__(self, cfg: Dict, traffic: Dict, data: Dict, seed: int,
                 model_mod, dtype=jnp.float32, precision=HIGHEST):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.precision = precision
        self.model, self.mod, self.dtype = cfg["model_sizes"], model_mod, dtype
        fed, opt = cfg["federation"], cfg["optimizer"]
        self.m, self.per = fed["num_clusters"], fed["devices_per_cluster"]
        self.tau, self.q, self.pi = fed["tau"], fed["q"], fed["pi"]
        self.lr, self.mu, self.batch = opt["lr"], opt["momentum"], opt["batch"]
        self.H = metropolis(ring(self.m))
        self.xs = jnp.asarray(data["xs"], dtype)
        self.ys = jnp.asarray(data["ys"], jnp.int32)
        self.N = int(self.xs.shape[1])
        self.p0 = model_mod.ref_init(jax.random.PRNGKey(seed), self.model,
                                     dtype)
        self._step = jax.jit(
            lambda Y, M, xb, yb, act: self._sgd(
                Y, M, self._row_grads(Y, xb, yb), act),
            donate_argnums=(0, 1))

    # one row at a time: grads of the mean cross-entropy per row
    def _row_grads(self, Y, xb, yb):
        apply = self.mod.ref_apply

        def loss(p, x, y):
            logits = apply(p, x, self.precision).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            return jnp.mean(lse - jnp.take_along_axis(
                logits, y[:, None], axis=-1)[:, 0])
        return jax.lax.map(lambda a: jax.grad(loss)(*a), (Y, xb, yb))

    def _sgd(self, Y, M, G, act):
        """Momentum SGD on the active rows (``act`` (rows,) bool)."""
        dt = self.dtype

        def upd_m(m, g):
            a = act.reshape((-1,) + (1,) * (m.ndim - 1))
            return jnp.where(a, (self.mu * m + g.astype(dt)).astype(dt), m)
        M = jax.tree.map(upd_m, M, G)

        def upd_y(y, m):
            a = act.reshape((-1,) + (1,) * (y.ndim - 1))
            return jnp.where(a, (y - self.lr * m).astype(dt), y)
        return jax.tree.map(upd_y, Y, M), M

    def _mix(self, W, Y):
        Wj = jnp.asarray(W, self.dtype)

        def mix(y):
            flat = y.reshape(y.shape[0], -1)
            out = self.mod.product(jnp.dot, Wj, flat, self.precision)
            return out.astype(self.dtype).reshape((W.shape[0],) + y.shape[1:])
        return jax.tree.map(mix, Y)

    def run(self, rounds: int) -> Dict:
        if self.cfg["engine"] == "streamed":
            return self._run_population(rounds)
        return self._run_resident(rounds)

    def _run_resident(self, rounds: int) -> Dict:
        n = self.m * self.per
        labels = np.repeat(np.arange(self.m), self.per)
        assert self.traffic.get("scenario") is None, "full participation"
        mask = np.ones(n)
        W_intra, W_inter = operators(labels, mask, self.H, self.pi)
        act = jnp.ones(n, bool)
        Y = jax.tree.map(lambda a: jnp.tile(a[None], (n,) + (1,) * a.ndim),
                         self.p0)
        M = jax.tree.map(jnp.zeros_like, Y)
        key = jax.random.PRNGKey(self.seed + 1)
        out = {"dparam": []}
        rows = jnp.arange(n)
        for r in range(rounds):
            key, k_round = jax.random.split(key)
            for b, k_block in enumerate(jax.random.split(k_round, self.q)):
                for k_step in jax.random.split(k_block, self.tau):
                    idx = jax.random.randint(k_step, (n, self.batch), 0,
                                             self.N)
                    xb = self.xs[rows[:, None], idx]
                    yb = self.ys[rows[:, None], idx]
                    Y, M = self._step(Y, M, xb, yb, act)
                Y = self._mix(W_intra, Y)
                if b == self.q - 1:
                    Y = self._mix(W_inter, Y)
            if r == 0:
                out["mom1"] = leaf_norms(M)
            out["dparam"].append(leaf_norms(Y, jax.tree.map(
                lambda a: a[None], self.p0)))
        return out

    def _encode(self, M):
        """Per-row, per-leaf int8 codes and scales of ``M``:
        scale = max(|leaf|, 1e-12) / 127, codes rounded half to even."""
        def enc(a):
            a = a.astype(jnp.float32)
            flat = a.reshape(a.shape[0], -1)
            s = jnp.maximum(jnp.max(jnp.abs(flat), axis=1), 1e-12) / 127.0
            q = jnp.clip(jnp.round(flat / s[:, None]), -127, 127)
            return q.astype(jnp.int8), s
        return jax.tree.map(enc, M)

    def _run_population(self, rounds: int) -> Dict:
        pop = self.cfg["population"]
        size, per = pop["clients_per_cluster"], pop["cohort_per_cluster"]
        assert pop["codec"] == "int8", pop["codec"]
        sc = self.traffic["scenario"]
        per = max(1, int(math.ceil(sc["sample_fraction"] * per)))
        shards = int(self.xs.shape[0])
        refs = jax.tree.map(lambda a: jnp.broadcast_to(a, (self.m,) + a.shape),
                            self.p0)
        # client id -> its momentum, per leaf: (int8 codes, scale)
        store: Dict[int, List] = {}
        key = jax.random.PRNGKey(self.seed + 1)
        out = {"dparam": []}
        for r in range(rounds):
            ids = population_cohort(self.seed, r, self.m, size, per,
                                    sc["dropout_prob"])
            home = ids // size
            Y = jax.tree.map(lambda a: a[home], refs)
            leaves, tdef = jax.tree.flatten(Y)

            def decoded(i, j, like):
                if i not in store:
                    return np.zeros(like.shape[1:], np.float32)
                q, s = store[i][j]
                return (q.astype(np.float32) * s).reshape(like.shape[1:])
            M = jax.tree.unflatten(tdef, [
                jnp.asarray(np.stack([decoded(int(i), j, lf) for i in ids]),
                            lf.dtype)
                for j, lf in enumerate(leaves)])
            # after each block of tau steps every edge averages its
            # cohort; after the last, the edges gossip pi rounds over the
            # backhaul and keep the result as their models
            P = cluster_average(home, np.ones(ids.shape[0]), self.m)
            B = np.zeros((self.m, ids.shape[0]))
            B[home, np.arange(ids.shape[0])] = 1.0
            key, k_round = jax.random.split(key)
            for k_block in jax.random.split(k_round, self.q):
                for k_step in jax.random.split(k_block, self.tau):
                    idx = jax.vmap(lambda i: jax.random.randint(
                        jax.random.fold_in(k_step, i), (self.batch,), 0,
                        self.N))(jnp.asarray(ids, jnp.int32))
                    sh = jnp.asarray(ids % shards)
                    xb = self.xs[sh[:, None], idx]
                    yb = self.ys[sh[:, None], idx]
                    Y, M = self._step(Y, M, xb, yb,
                                      jnp.ones(ids.shape[0], bool))
                Y = self._mix(B.T @ P, Y)
            refs = self._mix(np.linalg.matrix_power(self.H, self.pi) @ P, Y)
            enc = [(np.asarray(q), np.asarray(s)) for q, s in jax.tree.leaves(
                self._encode(M), is_leaf=lambda x: isinstance(x, tuple))]
            for row, i in enumerate(ids):
                store[int(i)] = [(q[row], s[row]) for q, s in enc]
            if r == 0:
                out["mom1"] = dict(zip(leaf_names(M), (
                    float(np.sqrt(np.sum(np.square(
                        q.astype(np.float32) * s[:, None]))))
                    for q, s in enc)))
            out["dparam"].append(leaf_norms(refs, jax.tree.map(
                lambda a: a[None], self.p0)))
        return out
