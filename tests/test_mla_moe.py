"""Latent attention, the dropless held-expert layer and the loss counters.

* MLA decode through the latent cache (normed c_kv and the roped shared
  key) agrees with the full forward's logits at every position.
* The grouped product's value and gradients are the dense products'.
* ``param_count`` counts what ``init_params`` makes.
* A loss that returns ``(loss, counters)`` puts the counters' mean over
  sites in the round's metrics; a scalar loss compiles the same round as
  before and reports the same keys.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import transformer as tfm
from repro.models.moe import grouped_matmul


def _f32_smoke():
    return dataclasses.replace(get_config("kanana-2-30b-a3b", smoke=True),
                               compute_dtype="float32")


@pytest.mark.parametrize("ring", [False, True])
def test_mla_decode_through_latent_cache_matches_full_forward(ring):
    cfg = _f32_smoke()
    bundle = build_model(cfg)
    params = bundle.init_fn(jax.random.key(0))
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab_size)
    emb = params["embed"]["table"][tokens]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.default_matmul_precision("highest"):
        h, _ = tfm.forward_hidden(params, cfg, emb, pos, remat=False)
        full = h @ params["head"]["table"].T  # (b, s, vocab)
        # a ring buffer as long as the prompt holds every position; a
        # float32 cache, so that only summation order parts the two paths
        caches = tfm.init_decode_state(cfg, b, s, sliding_override=ring,
                                       cache_dtype=jnp.float32)
        step = jax.jit(lambda p, t, c: bundle.decode_fn(p, t, c, sliding_override=ring))
        for i in range(s):  # the prompt stepped through the cache, then on
            logits, caches = step(params, tokens[:, i], caches)
            np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, i]),
                                       rtol=2e-4, atol=2e-4)


def test_latent_cache_holds_576_values_a_token_a_layer():
    cfg = get_config("kanana-2-30b-a3b")
    caches = jax.eval_shape(lambda: tfm.init_decode_state(cfg, 1, 8))
    per_layer = [c for c in caches["lead"]] + [
        jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                               caches["blocks"])]
    for c in per_layer:
        assert c["ckv"].shape[-1] + c["kpe"].shape[-1] == 576
    assert caches["blocks"]["ckv"].shape[0] == cfg.n_layers - 1


def test_grouped_matmul_matches_dense_products():
    rng = np.random.default_rng(0)
    tile, groups, a, c = 8, 3, 16, 12
    sizes = [5, 0, 11]  # rows a group; each group's rows padded to whole tiles
    tiles = [-(-n // tile) for n in sizes]
    rows = (sum(tiles) + 2) * tile  # room the loop leaves untouched
    lhs = np.zeros((rows, a), np.float32)
    group_of_row = np.full(rows, -1)  # a computed tile's rows, pads included
    start = 0
    for g, (n, t) in enumerate(zip(sizes, tiles)):
        lhs[start:start + n] = rng.normal(size=(n, a))
        group_of_row[start:start + t * tile] = g
        start += t * tile
    rhs = jnp.asarray(rng.normal(size=(groups, a, c)), jnp.float32)
    tile_group = jnp.asarray(np.repeat(np.arange(groups), tiles).tolist()
                             + [groups - 1] * 2, jnp.int32)
    n_active = jnp.int32(sum(tiles))
    cot = jnp.asarray(rng.normal(size=(rows, c)), jnp.float32)
    live = jnp.asarray(group_of_row >= 0)

    def dense(lhs, rhs):
        w = rhs[jnp.maximum(jnp.asarray(group_of_row), 0)]
        return jnp.where(live[:, None], jnp.einsum("ra,rac->rc", lhs, w), 0.0)

    def gmm(lhs, rhs):
        return grouped_matmul(lhs, rhs, tile_group, n_active, tile)

    with jax.default_matmul_precision("highest"):
        lhs = jnp.asarray(lhs)
        np.testing.assert_allclose(gmm(lhs, rhs), dense(lhs, rhs), rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda l, r: jnp.sum(gmm(l, r) * cot), argnums=(0, 1))(lhs, rhs)
        want = jax.grad(lambda l, r: jnp.sum(dense(l, r) * cot), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("smoke", [True, False])
def test_param_count_counts_what_init_makes(smoke):
    cfg = get_config("kanana-2-30b-a3b", smoke=smoke)
    shapes = build_model(cfg).param_shapes()
    made = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert made == cfg.param_count()
    if not smoke:  # the published model: 30.67B parameters
        assert 30.6e9 < made < 30.8e9


def test_expert_share_counts_its_own_experts():
    """The chip's share of the published layer: 8 of 128 experts held, the
    router still over all 128."""
    cfg = dataclasses.replace(get_config("kanana-2-30b-a3b"), n_layers=5,
                              vocab_size=16032, n_experts_held=8)
    moe = build_model(cfg).param_shapes()["blocks"]["moe"]
    assert moe["gate"].shape == (4, 8, 2048, 768)
    assert moe["router"]["w"].shape == (4, 2048, 128)
    assert cfg.param_count() == 425_354_240


# --- the counters path of make_fl_round -------------------------------------

#: what the round of every existing configuration reports
ROUND_KEYS = {"alpha", "comm_rounds", "consensus_err", "ef_residual_rms",
              "grad_norm_sq", "local_loss", "loss", "wire_bytes"}


def _round(loss_fn, params, n=2, q=2):
    from repro.core import FLConfig, FusedEngine, make_fl_round
    from repro.core.packing import pack
    from repro.core.schedules import constant

    stacked = jax.tree_util.tree_map(lambda a: jnp.stack([a] * n), params)
    buf, layout = pack(stacked, pad_to=128)
    cfg = FLConfig(algorithm="dsgd", q=q, n_nodes=n)
    w = np.full((n, n), 1.0 / n)
    engine = FusedEngine(w, layout, scale_chunk=128, impl="jnp")
    state = engine.init_state(cfg, buf)
    fn = jax.jit(make_fl_round(loss_fn, None, constant(0.1), cfg, engine=engine))
    return fn, state


def _normalized_text(fn, *args):
    """The compiled round without source locations and debug tables."""
    txt = fn.lower(*args).compile().as_text()
    txt = re.sub(r", metadata=\{[^}]*\}", "", txt)
    return "\n".join(line for line in txt.splitlines()
                     if not re.match(r"^[0-9]+ ", line))


@pytest.mark.parametrize("arch", ["smollm-360m", "ehr-mlp"])
def test_scalar_loss_round_is_unchanged_by_the_counters_path(arch):
    if arch == "ehr-mlp":
        from repro.models.mlp import make_mlp_loss, mlp_init

        params = mlp_init(jax.random.key(0), 42, 32, 2)
        loss_fn = make_mlp_loss(np.ones(2))
        batch = {"x": jnp.ones((2, 2, 4, 42)), "y": jnp.zeros((2, 2, 4), jnp.int32)}
    else:
        bundle = build_model(get_config(arch, smoke=True))
        params = bundle.init_fn(jax.random.key(0))
        loss_fn = bundle.loss_fn
        batch = {"tokens": jnp.zeros((2, 2, 2, 17), jnp.int32)}
    plain, state = _round(loss_fn, params)
    empty, _ = _round(lambda p, b: (loss_fn(p, b), {}), params)
    assert _normalized_text(plain, state, batch) == _normalized_text(empty, state, batch)
    _, metrics = plain(state, batch)
    assert set(metrics) == ROUND_KEYS


def test_counting_loss_reports_its_counters_averaged_over_sites():
    from repro.models.mlp import make_mlp_loss, mlp_init

    params = mlp_init(jax.random.key(0), 42, 32, 2)
    base = make_mlp_loss(np.ones(2))

    def counted(p, b):  # a counter that differs from site to site
        return base(p, b), {"rows_seen": jnp.sum(b["y"]).astype(jnp.float32)}

    fn, state = _round(counted, params)
    y = jnp.stack([jnp.stack([jnp.full((4,), 1), jnp.full((4,), 0)])] * 2)
    batch = {"x": jnp.ones((2, 2, 4, 42)), "y": y.astype(jnp.int32)}
    _, metrics = fn(state, batch)
    assert set(metrics) == ROUND_KEYS | {"rows_seen"}
    assert float(metrics["rows_seen"]) == pytest.approx(2.0)  # (4 + 0) / 2
