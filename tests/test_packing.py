"""Flat-buffer packing layer: lossless round-trips, layout invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.packing import (
    FlatLayout,
    flat_wire_bytes,
    pack,
    pack_layout,
    pack_like,
    unpack,
)


def _mixed_tree(n, seed):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n, 5, 3)), jnp.float32),
        "nested": {
            "b16": jnp.asarray(rng.normal(size=(n, 7)), jnp.bfloat16),
            "rank4": jnp.asarray(rng.normal(size=(n, 2, 3, 2)), jnp.float32),
        },
        "vec": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
        "f16": jnp.asarray(rng.normal(size=(n, 4)), jnp.float16),
    }


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("pad_to", [1, 8, 512])
def test_pack_unpack_roundtrip_mixed_dtypes_and_ranks(seed, pad_to):
    """fp32/bf16/fp16 leaves of rank 1-4 survive the round trip BITWISE
    (fp32 holds each losslessly)."""
    tree = _mixed_tree(6, seed)
    flat, layout = pack(tree, pad_to=pad_to)
    assert flat.shape == (6, layout.total)
    assert layout.total % pad_to == 0
    assert layout.used == sum(l.size for l in jax.tree_util.tree_leaves(tree)) // 6
    back = unpack(flat, layout)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert jnp.array_equal(a, b)


def test_layout_is_static_and_hashable():
    tree = _mixed_tree(4, 0)
    _, layout = pack(tree)
    assert isinstance(hash(layout), int)  # usable as a jit static argument
    # identical trees produce identical layouts
    _, layout2 = pack(_mixed_tree(4, 1))
    assert layout == layout2


def test_pack_layout_works_on_shape_structs():
    tree = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), _mixed_tree(4, 0)
    )
    layout = pack_layout(tree, pad_to=128)
    assert layout.n_nodes == 4 and layout.total == 128


def test_pack_padding_is_zero():
    tree = {"x": jnp.ones((3, 5), jnp.float32)}
    flat, layout = pack(tree, pad_to=8)
    assert layout.total == 8 and layout.used == 5
    assert np.asarray(flat[:, 5:]).max() == 0.0


def test_pack_like_follows_layout():
    tree = _mixed_tree(5, 3)
    flat, layout = pack(tree, pad_to=16)
    again = pack_like(tree, layout)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(again))
    # shape mismatch is rejected
    bad = dict(tree, vec=jnp.zeros((5, 2)))
    with pytest.raises(ValueError):
        pack_like(bad, layout)


def test_pack_rejects_inconsistent_node_axis():
    with pytest.raises(ValueError):
        pack({"a": jnp.zeros((4, 2)), "b": jnp.zeros((3, 2))})
    with pytest.raises(ValueError):
        pack({})


def test_unpack_rejects_wrong_buffer_shape():
    tree = {"x": jnp.ones((3, 5))}
    flat, layout = pack(tree)
    with pytest.raises(ValueError):
        unpack(flat[:, :-1], layout)


def test_roundtrip_under_jit_with_static_layout():
    tree = _mixed_tree(4, 7)
    flat, layout = pack(tree)

    @jax.jit
    def double_via_flat(t):
        f, lay = pack(t)
        return unpack(f * 2.0, lay)

    out = double_via_flat(tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32) * 2.0, np.asarray(b, np.float32),
            rtol=1e-2 if a.dtype == jnp.bfloat16 else 1e-6,
        )
    assert isinstance(layout, FlatLayout)


def test_flat_wire_bytes_accounting():
    tree = {"a": jnp.zeros((4, 1000)), "b": jnp.zeros((4, 100))}
    _, layout = pack(tree, pad_to=512)
    assert layout.total == 1536
    # int8 payload + one fp32 scale per 512-column chunk, per neighbor
    assert flat_wire_bytes(layout, degree=2, scale_chunk=512) == 2 * (1536 + 4 * 3)
    # scale_chunk=0: single per-node scale
    assert flat_wire_bytes(layout, degree=1) == 1536 + 4


# -- the row path: leaves converted through 128-lane rows --------------------


def _column_pack_like(tree, layout):
    """The column formulation every leaf took before the row path: each
    leaf as ``(n, size)`` columns, one concatenate. Kept as the oracle."""
    n = layout.n_nodes
    cols = [l.reshape(n, -1).astype(layout.storage_dtype)
            for l in jax.tree_util.tree_leaves(tree)]
    if layout.total > layout.used:
        cols.append(jnp.zeros((n, layout.total - layout.used),
                              layout.storage_dtype))
    return jnp.concatenate(cols, axis=1)


def _column_unpack(flat, layout):
    """Column slices of the buffer, the oracle of :func:`unpack`."""
    n = layout.n_nodes
    leaves = [jax.lax.slice_in_dim(flat, s.offset, s.offset + s.size, axis=1)
              .reshape((n,) + s.shape).astype(s.dtype) for s in layout.leaves]
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def _normal(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


def _layer_stacked_bf16(n, rng):
    # scanned layer weights (n, layers, ...), 128-aligned, and a final norm
    # whose 96 columns end the buffer unaligned before the tail padding
    return {"blocks": {"up": _normal(rng, (n, 2, 8, 48), jnp.bfloat16),
                       "ln": _normal(rng, (n, 2, 64), jnp.bfloat16),
                       "down": _normal(rng, (n, 2, 48, 8), jnp.bfloat16)},
            "final": _normal(rng, (n, 96), jnp.bfloat16)}


def _ehr_mlp_f32(n, rng):
    # the paper's MLP: 1,344 + 32 + 64 + 2 columns, none a multiple of 128
    return {"b1": _normal(rng, (n, 32), jnp.float32),
            "b2": _normal(rng, (n, 2), jnp.float32),
            "w1": _normal(rng, (n, 42, 32), jnp.float32),
            "w2": _normal(rng, (n, 32, 2), jnp.float32)}


def _mixed_dtypes(n, rng):
    # an unaligned run (100 + 28 columns) ends on a row boundary before an
    # aligned leaf; float32 leaves round to the bf16 storage
    return {"a": _normal(rng, (n, 100), jnp.float32),
            "b": _normal(rng, (n, 28), jnp.float16),
            "c": _normal(rng, (n, 2, 128), jnp.float32),
            "d": _normal(rng, (n, 5), jnp.bfloat16)}


def _aligned_tail_padded(n, rng):
    return {"w": _normal(rng, (n, 3, 128), jnp.float32),
            "v": _normal(rng, (n, 256), jnp.float32)}


# name -> (tree maker, pack_layout kwargs, columns on the row path)
ROW_TREES = {
    "layer_stacked_bf16": (_layer_stacked_bf16,
                           dict(pad_to=512, storage_dtype=jnp.bfloat16),
                           768 + 128 + 768),
    "ehr_mlp_f32": (_ehr_mlp_f32, dict(pad_to=512), 0),
    "mixed_dtypes_bf16_storage": (_mixed_dtypes,
                                  dict(pad_to=128, storage_dtype=jnp.bfloat16),
                                  256),
    "tail_padded": (_aligned_tail_padded, dict(pad_to=1000), 384 + 256),
    "unaligned_total": (_aligned_tail_padded, dict(pad_to=1, shards=1), 640),
    "shards2": (_layer_stacked_bf16,
                dict(pad_to=128, storage_dtype=jnp.bfloat16, shards=2),
                768 + 128 + 768),
}


def _bits(a):
    return np.asarray(a).view(f"u{a.dtype.itemsize}")


def _check_tree(name):
    make, kw, row_columns = ROW_TREES[name]
    n = 3
    tree = make(n, np.random.default_rng(0))
    layout = pack_layout(tree, **kw)
    if layout.total % 128:
        row_columns = 0  # a buffer of partial rows converts by columns
    assert layout.row_columns == row_columns
    assert layout.row_share == row_columns / layout.used
    want = _column_pack_like(tree, layout)
    for got in (pack_like(tree, layout),
                jax.jit(lambda t: pack_like(t, layout))(tree),
                pack(tree, kw["pad_to"], kw.get("storage_dtype", jnp.float32),
                     kw.get("shards", 1))[0]):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(_bits(got), _bits(want))
    # every column of a random buffer, padding included, is read as the
    # column slices read it
    flat = jnp.asarray(np.random.default_rng(1).normal(
        size=(n, layout.total)), layout.storage_dtype)
    want = jax.tree_util.tree_leaves(_column_unpack(flat, layout))
    for back in (unpack(flat, layout),
                 jax.jit(lambda f: unpack(f, layout))(flat)):
        for a, b in zip(jax.tree_util.tree_leaves(back), want):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(_bits(a), _bits(b))


def _check_fused_round():
    """A FusedEngine DSGD round leaves the state bit-identical whether its
    ``eval_grads`` converts through rows or through the column oracle."""
    from repro.core import FLConfig, FusedEngine, init_fl_state, make_fl_round
    from repro.core import engine as engine_mod
    from repro.core.schedules import constant

    n, q = 3, 2
    rng = np.random.default_rng(2)
    params = {"w1": _normal(rng, (n, 8, 32), jnp.float32),
              "w2": _normal(rng, (n, 32, 4), jnp.float32),
              "w_bias": _normal(rng, (n, 4), jnp.float32)}
    flat, layout = pack(params, pad_to=128, buffer_dtype=jnp.bfloat16)
    assert layout.row_columns == 256 + 128
    w = np.full((n, n), 1.0 / n)
    engine = FusedEngine(w, layout, scale_chunk=128)
    cfg = FLConfig(algorithm="dsgd", q=q, n_nodes=n)
    batches = {"x": _normal(rng, (q, n, 4, 8), jnp.float32),
               "y": _normal(rng, (q, n, 4, 4), jnp.float32)}

    def loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] + p["w_bias"] - b["y"]) ** 2)

    def two_rounds():
        rf = jax.jit(make_fl_round(loss, None, constant(0.1), cfg,
                                   engine=engine))
        state = init_fl_state(cfg, flat, engine=engine)
        for _ in range(2):
            state, _ = rf(state, batches)
        return jax.tree_util.tree_leaves(state)

    rows = two_rounds()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "pack_like", _column_pack_like)
        mp.setattr(engine_mod, "unpack", _column_unpack)
        cols = two_rounds()
    for a, b in zip(rows, cols):
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("case", [*ROW_TREES, "fused_round"])
def test_row_path_matches_column_oracle(case):
    """``pack_like``/``unpack`` through 128-lane rows equal the column
    formulation bit for bit, whichever leaves take the row path."""
    if case == "fused_round":
        _check_fused_round()
    else:
        _check_tree(case)


def test_row_columns_of_the_bench_models():
    """smollm-360m's layout takes every column but the final norm's 960 on
    the row path; the EHR MLP's leaves all bypass it."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.mlp import mlp_init

    def stacked(shapes, n):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), shapes)

    lm = build_model(get_config("smollm-360m")).param_shapes()
    layout = pack_layout(stacked(lm, 2), pad_to=512,
                         storage_dtype=jnp.bfloat16)
    assert (layout.row_columns, layout.used) == (361_820_160, 361_821_120)
    ehr = jax.eval_shape(lambda k: mlp_init(k, 42, 32, 2), jax.random.key(0))
    layout = pack_layout(stacked(ehr, 20), pad_to=512)
    assert (layout.row_columns, layout.used) == (0, 1_442)
