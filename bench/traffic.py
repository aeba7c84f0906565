"""The one traffic generator: a traffic mix file plus a configuration's
``inputs`` spec, turned into the per-step, per-site batches a cell feeds.

Everything is drawn from the run's seed, so the same seed gives the same
batches, and every seed gives the same sizes. A training cell's "traffic"
is its federation: the graph and its mixing weights, the algorithm, Q local
steps per round, and the samples each site takes per step.

A pool of ``pool_rounds`` rounds of distinct batches is made in set-up; the
timed window cycles through it, so the host's per-round work is the
trainer's own (stack Q step batches, one call, fetch the metrics), not the
generator.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

__all__ = ["rng", "jax_seed", "mixing_weights", "make_pool", "round_batch",
           "step_size"]


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any whole number) and a salt."""
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), *salt]))


def jax_seed(seed: int) -> int:
    """A 32-bit seed for ``jax.random.key`` derived from any whole number
    (``jax.random.key`` keeps only the low 32 bits of a larger one)."""
    return int(np.random.SeedSequence([abs(int(seed)), 7]).generate_state(1)[0])


def mixing_weights(graph: dict) -> np.ndarray:
    """The mixing matrix W of a traffic file's graph.

    ``metropolis``: W_ij = 1 / (1 + max(d_i, d_j)) on each edge.
    ``uniform``: a regular graph's W_ij = 1 / (d + 1) on each edge.
    The diagonal makes each row sum to one."""
    n = int(graph["n"])
    adj = np.zeros((n, n), bool)
    for i, j in graph["edges"]:
        if i == j or not (0 <= i < n and 0 <= j < n):
            raise ValueError(f"bad edge ({i}, {j}) for n={n}")
        adj[i, j] = adj[j, i] = True
    deg = adj.sum(axis=1)
    w = np.zeros((n, n))
    rule = graph["weights"]
    for i, j in zip(*np.nonzero(adj)):
        if rule == "metropolis":
            w[i, j] = 1.0 / (1.0 + max(deg[i], deg[j]))
        elif rule == "uniform":
            if not (deg == deg[0]).all():
                raise ValueError("uniform weights need a regular graph")
            w[i, j] = 1.0 / (deg[0] + 1.0)
        else:
            raise ValueError(f"unknown weight rule {rule!r}")
    np.fill_diagonal(w, 1.0 - w.sum(axis=1))
    return w


def _ehr_cohort(spec: dict, seed: int):
    """Synthetic EHR cohort matched to the paper's counts (Section 2.1):
    per-hospital feature shift and rotation, a shared separating direction,
    about a fifth of patients AD. Returns per-hospital (x, y) arrays."""
    g = rng(seed, 1)
    n_h, n_f = int(spec["n_hospitals"]), int(spec["n_features"])
    het = float(spec["heterogeneity"])
    w_true = g.normal(size=n_f)
    w_true /= np.linalg.norm(w_true)
    offsets = het * g.normal(size=(n_h, n_f))
    mixes = [np.eye(n_f) + 0.15 * g.normal(size=(n_f, n_f)) for _ in range(n_h)]

    def alloc(total):
        p = g.dirichlet(np.full(n_h, 20.0))
        counts = np.floor(p * total).astype(int)
        counts[: total - counts.sum()] += 1
        return counts

    ad, mci = alloc(int(spec["n_ad"])), alloc(int(spec["n_mci"]))
    xs, ys = [], []
    for h in range(n_h):
        z = np.concatenate([g.normal(size=(ad[h], n_f)) + 1.2 * w_true,
                            g.normal(size=(mci[h], n_f)) - 0.3 * w_true])
        y = np.concatenate([np.ones(ad[h]), np.zeros(mci[h])]).astype(np.int32)
        perm = g.permutation(len(y))
        xs.append((z @ mixes[h].T + offsets[h])[perm])
        ys.append(y[perm])
    allx = np.concatenate(xs)
    mu, sd = allx.mean(0), allx.std(0) + 1e-6
    return [((x - mu) / sd).astype(np.float32) for x in xs], ys


def make_pool(config: dict, traffic: dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """``pool_rounds * q`` per-step batches, each a dict of node-stacked
    numpy arrays ``(n_sites, batch, ...)``; no two steps share their rows."""
    n = int(traffic["graph"]["n"])
    b = int(traffic["batch"])
    steps = int(traffic["pool_rounds"]) * int(traffic["q"])
    spec = config["inputs"]
    g = rng(seed, 2)
    if spec["kind"] == "ehr_cohort":
        xs, ys = _ehr_cohort(spec, seed)
        if len(xs) != n:
            raise ValueError(f"cohort has {len(xs)} hospitals, graph {n} sites")
        pool = []
        for _ in range(steps):
            idx = [g.integers(0, len(y), size=b) for y in ys]
            pool.append({"x": np.stack([x[i] for x, i in zip(xs, idx)]),
                         "y": np.stack([y[i] for y, i in zip(ys, idx)])})
        return pool
    if spec["kind"] == "tokens":
        s = int(traffic["seq_len"]) + 1  # inputs plus the shifted labels
        toks = g.integers(0, int(config["vocab_size"]), size=(steps, n, b, s),
                          dtype=np.int32)
        return [{"tokens": t} for t in toks]
    raise ValueError(f"unknown input kind {spec['kind']!r}")


def round_batch(pool: List[Dict[str, np.ndarray]], q: int, r: int):
    """Round ``r``'s batches: Q consecutive pool steps stacked to
    ``(q, n_sites, ...)``, the trainer's own per-round host work."""
    rounds = len(pool) // q
    steps = pool[(r % rounds) * q:(r % rounds + 1) * q]
    return {k: np.stack([s[k] for s in steps]) for k in steps[0]}


def step_size(traffic: dict, step):
    """alpha at global iteration ``step`` (1-indexed, local steps counted):
    ``constant`` alpha, or ``inv_sqrt`` alpha / sqrt(step)."""
    alpha = float(traffic["alpha"])
    if traffic.get("schedule", "constant") == "constant":
        return alpha
    if traffic["schedule"] == "inv_sqrt":
        return alpha / (step ** 0.5)
    raise ValueError(f"unknown schedule {traffic['schedule']!r}")
