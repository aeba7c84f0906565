"""Mixture-of-Experts FFN: a top-k router, and two dispatches.

``moe_apply``: the capacity dispatch, for a layer that holds every expert
(dbrx, llama4), described below. ``moe_held_apply``: the dropless layer
that is told which experts it holds (expert parallelism's share of a
layer): it routes over all experts, drops no token, and computes only its
own experts' part of the result through a grouped product whose cost
follows the rows routed to them.

The capacity dispatch stays beside the dropless one for the configurations
that hold all their experts under GSPMD: its ``(E, C, d)`` einsums shard
over the ``model`` axis as they are, where the grouped product's
per-tile expert index would gather the sharded expert weights.

Expert-parallel layout: expert weights carry a leading E axis sharded over
the ``model`` mesh axis (E=16 experts over 16-way TP -> one expert per
device group); the dispatch scatter/gather becomes the all-to-all the MoE
literature expects, inserted by GSPMD around the sharded expert einsum.

Dispatch is the TPU-standard sort-free capacity scheme WITHOUT the O(N*E*C)
one-hot of GShard: assignments are ranked per expert via a stable sort of
expert ids, tokens beyond capacity C = ceil(N*k/E * capacity_factor) are
DROPPED (their combine weight contributes nothing -- the residual stream
carries them), and scatter/gather use a +1 padded row as the drop sink.

FLOP count therefore matches the paper-table expectation:
experts_per_token x N x (3 d d_ff) x capacity_factor, not n_experts x.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, normal_init, swiglu, swiglu_init

PyTree = Any

__all__ = ["moe_init", "moe_apply", "moe_capacity", "moe_held_apply",
           "route", "grouped_matmul", "zero_counters", "merge_counters",
           "TILE"]

#: rows of one tile of the grouped product
TILE = 256
#: how the held-expert layer's counters combine over layers
COUNTERS = {"moe_held_assignments": jnp.add, "moe_held_max_load": jnp.maximum}


def moe_capacity(n_tokens: int, n_experts: int, k: int, factor: float) -> int:
    cap = int(-(-(n_tokens * k * factor) // n_experts))  # ceil
    # round to a lane-friendly multiple of 8 and keep >= k
    return max(8, ((cap + 7) // 8) * 8)


def moe_init(
    key,
    d_model: int,
    d_ff: int,
    n_experts: int,
    dtype,
    shared_expert: bool = False,
    *,
    n_held: int = 0,
    shared_d_ff: int = 0,
    select_bias: bool = False,
) -> Dict:
    """Router over ``n_experts``; ``n_held`` experts' weights (0 => all);
    a shared SwiGLU of ``shared_d_ff`` (0 => d_ff); with ``select_bias``
    the router's per-expert selection bias (DeepSeek-V3's
    ``e_score_correction_bias``), drawn N(0, 0.1^2) so that it moves the
    selection."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    scale = d_model**-0.5
    held = n_held or n_experts
    p = {
        "router": {"w": normal_init(kr, (d_model, n_experts), scale, jnp.float32)},
        "gate": normal_init(kg, (held, d_model, d_ff), scale, dtype),
        "up": normal_init(ku, (held, d_model, d_ff), scale, dtype),
        "down": normal_init(kd, (held, d_ff, d_model), d_ff**-0.5, dtype),
    }
    if select_bias:
        p["router"]["bias"] = normal_init(jax.random.fold_in(kr, 1), (n_experts,), 0.1,
                                          jnp.float32)
    if shared_expert:
        p["shared"] = swiglu_init(ks, d_model, shared_d_ff or d_ff, dtype)
    return p


def moe_apply(
    p: Dict,
    x: jnp.ndarray,
    *,
    n_experts: int,
    k: int,
    capacity_factor: float = 1.25,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux_loss).

    aux_loss is the standard load-balance term E * sum_e f_e * p_e
    (Switch/GShard), which the trainer scales by ``router_aux_coef``.
    """
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    cap = moe_capacity(n, n_experts, k, capacity_factor)

    top_e, top_p, probs = route(p["router"], xf, k)  # (N, k), (N, k), (N, E)

    # load-balance auxiliary loss
    frac = jnp.mean(
        jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32), axis=0
    )
    mean_p = jnp.mean(probs, axis=0)
    aux = jnp.float32(n_experts) * jnp.sum(frac * mean_p)

    # --- rank assignments within each expert (stable sort by expert id) ---
    flat_e = top_e.reshape(-1)  # (N*k,)
    flat_w = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(sorted_e, length=n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(n * k) - starts[sorted_e]
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = rank < cap
    slot = jnp.where(keep, flat_e * cap + rank, n_experts * cap)  # drop sink row

    # --- dispatch: scatter tokens into the (E*C [+1 sink], d) buffer ---
    buf = jnp.zeros((n_experts * cap + 1, d), compute_dtype)
    buf = buf.at[slot].set(xf[flat_tok].astype(compute_dtype))
    buf = buf[: n_experts * cap].reshape(n_experts, cap, d)

    # --- expert computation (expert-parallel einsums) ---
    g = jnp.einsum("ecd,edf->ecf", buf, p["gate"].astype(compute_dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["up"].astype(compute_dtype))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, p["down"].astype(compute_dtype))

    # --- combine: gather back and weight ---
    y_flat = jnp.concatenate(
        [y.reshape(n_experts * cap, d), jnp.zeros((1, d), y.dtype)], axis=0
    )
    contrib = y_flat[slot] * flat_w[:, None].astype(y.dtype)  # dropped rows hit the zero sink
    out = jnp.zeros((n, d), jnp.float32).at[flat_tok].add(contrib.astype(jnp.float32))
    out = out.astype(compute_dtype)

    if "shared" in p:
        out = out + swiglu(p["shared"], xf, compute_dtype)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# the dropless held-expert layer
# ---------------------------------------------------------------------------


def route(router: Dict, xf: jnp.ndarray, k: int, *, score_func: str = "softmax",
          scaling: float = 1.0):
    """float32 routing of tokens (N, d): ``(expert ids (N, k), weights
    (N, k), scores (N, E))``.

    ``softmax``: the top-k probabilities. ``sigmoid``: the top-k of the
    sigmoid scores plus the selection bias (under ``stop_gradient``), each
    weighted by its score without the bias (DeepSeek-V3's ``noaux_tc`` with
    one group). The k weights are renormalised, then times ``scaling``."""
    logits = jnp.dot(xf.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        select = scores + jax.lax.stop_gradient(router["bias"].astype(jnp.float32))
        _, idx = jax.lax.top_k(select, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(scores, k)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scaling, scores


def _tile_dot(x, w, contract):
    return jax.lax.dot_general(x, w, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


def _gmm(lhs, rhs, tile_group, n_active, tile, contract):
    """Rows of tile t of ``lhs`` against ``rhs[tile_group[t]]``, for the
    first ``n_active`` tiles; the rows of the other tiles are zero."""
    n_out = rhs.shape[2] if contract == ((1,), (0,)) else rhs.shape[1]

    def body(t, out):
        x = jax.lax.dynamic_slice_in_dim(lhs, t * tile, tile, 0)
        w = jax.lax.dynamic_index_in_dim(rhs, tile_group[t], 0, keepdims=False)
        y = _tile_dot(x, w, contract).astype(out.dtype)
        return jax.lax.dynamic_update_slice_in_dim(out, y, t * tile, 0)

    out = jnp.zeros((lhs.shape[0], n_out), lhs.dtype)
    return jax.lax.fori_loop(0, n_active, body, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(lhs, rhs, tile_group, n_active, tile):
    """(R, a) rows sorted into tiles of ``tile`` rows, each tile one
    group's, against (G, a, c) group weights: (R, c). Only the first
    ``n_active`` tiles are computed, so the cost follows the rows routed,
    not the rows the buffer could hold. float32 accumulation."""
    return _gmm(lhs, rhs, tile_group, n_active, tile, ((1,), (0,)))


def _grouped_matmul_fwd(lhs, rhs, tile_group, n_active, tile):
    out = grouped_matmul(lhs, rhs, tile_group, n_active, tile)
    return out, (lhs, rhs, tile_group, n_active)


def _grouped_matmul_bwd(tile, res, dy):
    lhs, rhs, tile_group, n_active = res
    dlhs = _gmm(dy, rhs, tile_group, n_active, tile, ((1,), (1,)))

    def body(t, drhs):
        x = jax.lax.dynamic_slice_in_dim(lhs, t * tile, tile, 0)
        g = jax.lax.dynamic_slice_in_dim(dy, t * tile, tile, 0)
        e = tile_group[t]
        part = jax.lax.dynamic_index_in_dim(drhs, e, 0, keepdims=True)
        part = part + _tile_dot(x, g, ((0,), (0,)))[None]
        return jax.lax.dynamic_update_index_in_dim(drhs, part, e, 0)

    drhs = jax.lax.fori_loop(0, n_active, body, jnp.zeros(rhs.shape, jnp.float32))
    return dlhs, drhs.astype(rhs.dtype), None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


@jax.custom_vjp
def _take_rows(x, idx, inv):
    """Rows ``idx`` of x (N, d), where ``idx == N`` takes a zero row.
    ``inv`` (N, f) lists, for each row of x, the positions of ``idx`` that
    take it (``len(idx)`` where fewer than f do), so that the backward pass
    gathers and sums instead of scattering onto a few hot rows."""
    return jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])[idx]


def _take_rows_fwd(x, idx, inv):
    return _take_rows(x, idx, inv), inv


def _take_rows_bwd(inv, g):
    g = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)])
    return jnp.sum(g[inv].astype(jnp.float32), axis=1).astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def zero_counters() -> Dict[str, jnp.ndarray]:
    """The held-expert layer's counters before any layer."""
    return {k: jnp.float32(0.0) for k in COUNTERS}


def merge_counters(acc: Dict, new: Dict) -> Dict:
    """Combine two layers' counters (``COUNTERS`` says how)."""
    return {k: COUNTERS[k](acc[k], new[k]) for k in acc}


def moe_held_apply(
    p: Dict,
    x: jnp.ndarray,
    *,
    n_experts: int,
    k: int,
    first_held: int = 0,
    score_func: str = "softmax",
    scaling: float = 1.0,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (out, counters), in the ``moe`` named scope.

    Routes every token over all ``n_experts`` and computes the part of the
    result that the held experts ``first_held .. first_held + G - 1`` give
    (G = the leading axis of ``p["gate"]``), with no token dropped, plus
    the shared expert once. The held assignments are laid out by expert,
    each expert's rows padded to whole tiles; the grouped product runs
    the tiles that hold rows. Counters: ``moe_held_assignments``, the
    token-expert assignments to the held experts, and
    ``moe_held_max_load``, the busiest held expert's over their mean."""
    with jax.named_scope("moe"):
        b, s, d = x.shape
        n = b * s
        xf = x.reshape(n, d)
        idx, w, _ = route(p["router"], xf, k, score_func=score_func, scaling=scaling)
        g_held = p["gate"].shape[0]
        tile = min(TILE, -(-n // 8) * 8)
        rows = -(-(n * min(k, g_held) + g_held * (tile - 1)) // tile) * tile

        # each assignment's expert among the held (g_held: not held), its
        # rank among that expert's assignments, and its row in the buffer
        local = idx.reshape(-1) - first_held
        held = (local >= 0) & (local < g_held)
        group = jnp.where(held, local, g_held)
        onehot = jax.nn.one_hot(group, g_held + 1, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, group[:, None], 1)[:, 0]
        counts = jnp.sum(onehot, axis=0)[:g_held]
        tiles = (counts + tile - 1) // tile
        tile_end = jnp.cumsum(tiles)
        start = (tile_end - tiles) * tile
        row = jnp.where(held, start[jnp.minimum(group, g_held - 1)] + rank, rows)
        tile_group = jnp.minimum(
            jnp.searchsorted(tile_end, jnp.arange(rows // tile), side="right"),
            g_held - 1)
        n_active = tile_end[-1]

        # dispatch: each buffer row's assignment (n * k: none) and token
        # (n: the zero row); gather the rows
        assign = jnp.full((rows,), n * k, jnp.int32).at[row].set(
            jnp.arange(n * k, dtype=jnp.int32), mode="drop")
        xs = _take_rows(xf.astype(compute_dtype), jnp.minimum(assign // k, n),
                        row.reshape(n, k))

        def gmm(a, wts):
            return grouped_matmul(a, wts.astype(compute_dtype), tile_group, n_active, tile)

        hid = jax.nn.silu(gmm(xs, p["gate"])) * gmm(xs, p["up"])
        ys = gmm(hid, p["down"])

        # combine: each held assignment's row, weighted; the rest add zero
        ys = _take_rows(ys, row, assign[:, None])
        out = jnp.sum((ys.astype(jnp.float32) * w.reshape(-1, 1)).reshape(n, k, d), axis=1)
        if "shared" in p:
            out = out + swiglu(p["shared"], xf, compute_dtype).astype(jnp.float32)
        counts = counts.astype(jnp.float32)
        counters = {
            "moe_held_assignments": jnp.sum(counts),
            "moe_held_max_load": jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9),
        }
        return out.astype(compute_dtype).reshape(b, s, d), counters
