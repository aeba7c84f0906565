"""Compile the gossip round kernels for a described TPU v5e at real widths.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached. Interpret-mode tests cannot
see what Mosaic refuses (a block not aligned to the (8, 128) tile, too
much scoped VMEM); these compiles can. Widths are the main path's:

* ``fused_round`` / ``fused_round_gt`` -- the dense engine's round
  megakernel on the paper's cell, 20 hospitals x the EHR MLP's 1,442
  parameters padded to 1,536 columns;
* ``wire_stage`` / ``wire_stage_gt`` -- one site's row of the sharded
  engine at smollm-360m's published widths, the packed parameter count
  padded to the 512-column scale chunk;
* ``pack_like`` / ``unpack`` -- the conversion between two smollm-360m
  sites' parameter leaves and their packed bfloat16 ``(2, total)`` state,
  which must lower without a per-node relayout loop.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gossip import gossip as G
from test_packing import _column_pack_like, _column_unpack

CHUNK = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep the cache off
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _smollm_stacked(n, sharding=None):
    """smollm-360m's parameter leaves stacked for ``n`` sites."""
    from repro.configs import get_config
    from repro.models import build_model

    shapes = build_model(get_config("smollm-360m")).param_shapes()
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype,
                                       sharding=sharding), shapes)


@pytest.fixture(scope="module")
def smollm_width():
    """smollm-360m's packed row: every parameter, padded to the chunk."""
    from repro.core.packing import pack_layout

    return pack_layout(_smollm_stacked(1), pad_to=CHUNK).total


def _compile_has_kernel(fn, *args) -> bool:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("dsgt", [False, True], ids=["dsgd", "dsgt"])
def test_fused_round_compiles_for_v5e_hospital20(one_chip, dsgt):
    n, t = 20, 1536
    buf = _sds(one_chip, (n, t))
    w_off, w_self = _sds(one_chip, (n, n)), _sds(one_chip, (n,))
    alpha = _sds(one_chip, ())
    if dsgt:
        fn = functools.partial(G.fused_round_gt_pallas, scale_chunk=CHUNK)
        args = [buf] * 8 + [w_off, w_self, alpha]
    else:
        fn = functools.partial(G.fused_round_pallas, scale_chunk=CHUNK)
        args = [buf] * 4 + [w_off, w_self, alpha]
    assert _compile_has_kernel(fn, *args)


@pytest.mark.parametrize("dsgt", [False, True], ids=["dsgd", "dsgt"])
def test_wire_stage_compiles_for_v5e_smollm_row(one_chip, smollm_width, dsgt):
    assert 361_000_000 < smollm_width < 362_000_000
    buf = _sds(one_chip, (1, smollm_width))
    alpha = _sds(one_chip, ())
    if dsgt:
        fn = functools.partial(G.wire_stage_gt_pallas, scale_chunk=CHUNK)
        args = [buf] * 8 + [alpha]
    else:
        fn = functools.partial(G.wire_stage_pallas, scale_chunk=CHUNK)
        args = [buf] * 4 + [alpha]
    assert _compile_has_kernel(fn, *args)


def _has_relayout_loop(fn, arg) -> bool:
    """Whether the compiled text holds a per-node relayout loop (the
    compiler names its body ``wide.body``)."""
    return "wide.body" in jax.jit(fn).lower(arg).compile().as_text()


@pytest.mark.parametrize("direction", ["pack", "unpack"])
def test_flat_conversion_has_no_relayout_loop_for_v5e(one_chip, direction):
    """Two smollm-360m sites, bfloat16 state, 512-column chunks. The
    column oracle is compiled without the attention leaves, whose loops
    take over a minute to compile; the MLP, norm and embedding leaves at
    their published widths show the loop."""
    from repro.core.packing import pack_layout, pack_like, unpack

    full = _smollm_stacked(2, one_chip)
    part = dict(full, blocks={k: v for k, v in full["blocks"].items()
                              if k != "attn"})
    for tree in (part, full):
        layout = pack_layout(tree, pad_to=CHUNK, storage_dtype=jnp.bfloat16)
        if direction == "pack":
            fns, arg = (_column_pack_like, pack_like), tree
        else:
            fns = (_column_unpack, unpack)
            arg = _sds(one_chip, (2, layout.total), jnp.bfloat16)
        oracle, new = (functools.partial(f, layout=layout) for f in fns)
        if tree is part:
            assert _has_relayout_loop(oracle, arg)
        assert not _has_relayout_loop(new, arg)
