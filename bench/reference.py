"""Plain reference of the decentralized round: each site's Q local steps
and the compressed exchange, written from the paper's equations site by
site in float32. Imports nothing of the program.

Round (Q local steps, the last one the communication step):

  local (Q-1 times):  x_i <- x_i - alpha g_i   (alpha at the global step)
  DSGD comm:          h_i = x_i - alpha g_i
                      x_i <- W_ii h_i + sum_j W_ij C_j(h_j)
  DSGT comm:          t_i <- t_i + g_i - g_prev_i;  h_i = x_i - alpha t_i
                      x_i <- W_ii h_i + sum_j W_ij C_j(h_j)
                      t_i <- W_ii t_i + sum_j W_ij C_j(t_j);  g_prev_i <- g_i

C_j is the difference-coded int8 wire with error feedback: the payload
``p = v - recon + res`` is quantized per chunk of ``scale_chunk`` columns of
the site's flat parameter vector (leaves in tree order, zero-padded to a
whole chunk) to ``round(p / s)`` with ``s = max|p| / 127``; ``recon``
accumulates what was sent and ``res = p - sent``. The neighbours mix their
copy of ``recon``. Parameters and the tracker are stored in the cell's
storage dtype; the wire state is float32.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from traffic import step_size

__all__ = ["FlatView", "run_reference", "site_norms"]


class FlatView:
    """The reference's own flat layout of a parameter tree."""

    def __init__(self, tree, chunk: int):
        paths, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        self.names = [jax.tree_util.keystr(p) for p, _ in paths]
        self.shapes = [tuple(l.shape) for _, l in paths]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])[:-1]
        used = int(sum(self.sizes))
        self.total = -(-used // chunk) * chunk

    def flatten(self, tree) -> jnp.ndarray:
        flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in jax.tree_util.tree_leaves(tree)])
        return jnp.pad(flat, (0, self.total - flat.shape[0]))

    def unflatten(self, flat):
        leaves = [flat[o:o + n].reshape(s) for o, n, s in
                  zip(self.offsets, self.sizes, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def leaf_sq(self, flat) -> jnp.ndarray:
        """Per-leaf sums of squares of one site's flat vector."""
        return jnp.stack([jnp.sum(jnp.square(flat[o:o + n]))
                          for o, n in zip(self.offsets, self.sizes)])


def site_norms(names, sq) -> Dict[str, float]:
    """One site's per-leaf norms from its per-leaf sums of squares."""
    return {k: float(np.sqrt(v)) for k, v in
            zip(names, np.asarray(sq, np.float64))}


def _quantize(p, chunk):
    p3 = p.reshape(-1, chunk)
    s = jnp.max(jnp.abs(p3), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(p3 / jnp.where(s > 0, s, 1.0)), -127, 127)
    return (q * s).reshape(-1)


def run_reference(model, config: dict, traffic: dict, params, w: np.ndarray,
                  batches: List[dict], *, rnd=lambda a: a,
                  fault: Optional[str] = None, devices=None) -> dict:
    """Run ``len(batches)`` rounds; return the readings the cell compares.

    ``params``: the seed's parameter tree (float32), the same for every
    site. ``batches``: each round's ``(q, n_sites, ...)`` numpy batches.
    ``rnd``: matmul operand rounding (the control passes a narrower dtype).
    ``fault``: plant one of ``half_batch`` or ``no_exchange`` in place of
    the program, for the control runs. ``devices``: site i lives on
    ``devices[i % len(devices)]``.

    Returns ``losses`` (the comm step's mean loss per round),
    ``site_losses`` (each round's comm-step loss of every site, whose mean
    is ``losses``), ``grad_sites`` (each site's per-leaf norms of the
    first gradient as the optimizer state holds it after round 1) and
    ``change_sites`` (each site's per-leaf norms of the parameters' change
    after the last round)."""
    n, q = int(traffic["graph"]["n"]), int(traffic["q"])
    alpha = jnp.float32(traffic["alpha"])  # the first step's
    chunk = int(traffic["scale_chunk"])
    store = jnp.dtype(traffic["storage_dtype"])
    dsgt = traffic["algorithm"] == "dsgt"
    devices = devices or jax.devices()[:1]
    dev = [devices[i % len(devices)] for i in range(n)]
    if fault == "no_exchange":
        w = np.eye(n)
    elif fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    view = FlatView(params, chunk)

    f32 = jnp.float32
    vg = jax.jit(lambda xs, b: jax.value_and_grad(
        lambda flat: model.loss(view.unflatten(flat), b, config, rnd))(
            xs.astype(f32)))
    local = jax.jit(lambda xs, g, a: (xs.astype(f32) - a * g).astype(store),
                    donate_argnums=(0,))
    leaf_sq = jax.jit(view.leaf_sq)
    # DSGD keeps no gradient: after round 1, recon + res is the payload h
    # (both start at zero), so (theta0 - h) / alpha is the summed gradient
    # of the round's steps as the update applied it
    grad_sq = jax.jit(lambda th, rec, rs: view.leaf_sq(
        (th.astype(f32) - rec - rs) / alpha))
    change_sq = jax.jit(lambda xs, th: view.leaf_sq(
        xs.astype(f32) - th.astype(f32)))

    def _send(v, rec, rs):
        p = v - rec + rs
        dq = _quantize(p, chunk)
        return rec + dq, p - dq

    send = jax.jit(_send, donate_argnums=(1, 2))
    mix = jax.jit(lambda v, ws, nbrs, wn: (
        ws * v + sum(a * b for a, b in zip(wn, nbrs))).astype(store))

    theta0 = view.flatten(params).astype(store)
    x = [jax.device_put(theta0, d, may_alias=False) for d in dev]

    def zeros(dtype=f32):
        return [jax.device_put(jnp.zeros(view.total, dtype), d) for d in dev]

    recon, res = zeros(), zeros()
    if dsgt:
        t, gp, recon_t, res_t = zeros(store), zeros(store), zeros(), zeros()

    def site_batch(batch, step, i):
        b = {k: jax.device_put(v[step, i], dev[i]) for k, v in batch.items()}
        if fault == "half_batch":
            b = {k: v[: v.shape[0] // 2] for k, v in b.items()}
        return b

    def exchange(v, rec, rs):
        """One wire: send C(v_i), then mix W_ii v_i + sum_j W_ij recon_j.
        Consumes ``v``; returns the mixed values in the storage dtype."""
        for i in range(n):
            rec[i], rs[i] = send(v[i], rec[i], rs[i])
        out = []
        for i in range(n):
            nb = [j for j in range(n) if j != i and w[i, j] != 0.0]
            out.append(mix(v[i], f32(w[i, i]),
                           [jax.device_put(rec[j], dev[i]) for j in nb],
                           [f32(w[i, j]) for j in nb]))
            v[i] = None
        return out

    out = {"losses": [], "site_losses": []}
    for r, batch in enumerate(batches):
        for s in range(q - 1):
            a = f32(step_size(traffic, r * q + s + 1))
            for i in range(n):
                _, g = vg(x[i], site_batch(batch, s, i))
                x[i] = local(x[i], g, a)
        a = f32(step_size(traffic, r * q + q))
        losses, h, th = [], [], []
        for i in range(n):
            li, g = vg(x[i], site_batch(batch, q - 1, i))
            losses.append(li)
            if dsgt:
                ti = t[i].astype(f32) + g - gp[i].astype(f32)
                th.append(ti)
                gp[i] = g.astype(store)
                h.append(x[i].astype(f32) - a * ti)
            else:
                h.append(x[i].astype(f32) - a * g)
            x[i] = g = None
        x = exchange(h, recon, res)
        if dsgt:
            t = exchange(th, recon_t, res_t)
        site = [float(v) for v in losses]
        out["site_losses"].append(site)
        out["losses"].append(float(np.mean(site)))
        if r == 0:
            if dsgt:
                sq = [leaf_sq(v.astype(f32)) for v in gp]
            else:
                sq = [grad_sq(jax.device_put(theta0, dev[i]), recon[i], res[i])
                      for i in range(n)]
            out["grad_sites"] = [site_norms(view.names, v) for v in sq]
    out["change_sites"] = [
        site_norms(view.names, change_sq(x[i], jax.device_put(theta0, dev[i])))
        for i in range(n)]
    return out
