"""``kanana-2-30b-a3b`` against its plain reference on the CPU at the
``smoke`` sizes, on seeded random weights: the program's loss and every
leaf's gradient; the expert layer's share of a layer; a routing that sends
every token to one held expert loses none."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

WORKLOAD = "kanana30b-ring4-4chip-dsgd-q4"

#: float32 program against the float32 reference: the same equations, so
#: they differ by summation order alone (about 1e-7 relative on the loss,
#: 1e-6 on a leaf). Computing in bfloat16 misses both by ten times or more
#: (checked below).
LOSS_RTOL = 2e-6
GRAD_RTOL = 1e-4


@pytest.fixture(scope="module")
def cell():
    """The smoke cell, with each token selecting 2 of the 4 experts (the
    smoke file selects all 4, for the four-device test's readings), so
    that the top-k selection and its bias matter here."""
    cell = harness.resolve(WORKLOAD, smoke=True)
    cell.config["num_experts_per_tok"] = 2
    return cell


@pytest.fixture(scope="module")
def inputs(cell):
    params = cell.model.init_params(cell.config, jax.random.key(3))
    tokens = jax.random.randint(jax.random.key(4), (2, 33), 0,
                                cell.config["vocab_size"])
    return params, {"tokens": tokens}


@pytest.fixture(scope="module")
def reference(cell, inputs):
    params, batch = inputs
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: cell.model.loss(p, batch, cell.config))(params)


def _program(cell, inputs, compute_dtype):
    from repro.models import build_model

    cfg = dataclasses.replace(cell.system.model_config(cell.config),
                              compute_dtype=compute_dtype)
    params, batch = inputs
    (loss, counters), grads = jax.value_and_grad(
        build_model(cfg).counted_loss_fn, has_aux=True)(params, batch)
    return loss, counters, grads


def _gaps(got, want):
    loss_gap = abs(float(got[0]) - float(want[0])) / abs(float(want[0]))
    leaf = jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        if float(jnp.linalg.norm(b)) > 0 else float(jnp.linalg.norm(a)),
        got[-1], want[1])
    return loss_gap, leaf


def test_float32_program_matches_reference(cell, inputs, reference):
    loss, counters, grads = _program(cell, inputs, "float32")
    loss_gap, leaf = _gaps((loss, grads), reference)
    assert loss_gap < LOSS_RTOL
    worst = max(jax.tree_util.tree_leaves(leaf))
    assert worst < GRAD_RTOL, leaf
    # the bias moves the selection only: no gradient, as in the reference
    assert float(jnp.abs(grads["blocks"]["moe"]["router"]["bias"]).max()) == 0
    assert set(counters) == {"moe_held_assignments", "moe_held_max_load"}


def test_bfloat16_program_fails_the_float32_tolerances(cell, inputs,
                                                       reference):
    """The tolerances above have teeth: the program at its bfloat16
    compute dtype misses them."""
    loss, _, grads = _program(cell, inputs, "bfloat16")
    loss_gap, leaf = _gaps((loss, grads), reference)
    assert loss_gap > 10 * LOSS_RTOL
    assert max(jax.tree_util.tree_leaves(leaf)) > 10 * GRAD_RTOL


def _layer_config(cell, held, first):
    c = dict(cell.config)
    c["n_routed_experts"] = held
    c["deployment"] = dict(c["deployment"], first_expert_held=first,
                           expert_parallel=c["deployment"]["experts_published"] // held)
    return c


def _layer_params(cell, key, n_experts):
    """One expert layer's weights over all ``n_experts`` (the uncut
    layer), from the reference's own initialisation."""
    c = _layer_config(cell, n_experts, 0)
    c["num_hidden_layers"] = c["first_k_dense_replace"] + 1
    return jax.tree_util.tree_map(lambda a: a[0],
                                  cell.model.init_params(c, key)["blocks"]["moe"])


def _held_part(p, first, held):
    return dict(p, **{k: p[k][first:first + held] for k in ("gate", "up", "down")})


def _program_layer(cell, p, x, first, held):
    from repro.models.moe import moe_held_apply

    c = cell.config
    return moe_held_apply(
        p, x, n_experts=c["deployment"]["experts_published"],
        k=c["num_experts_per_tok"], first_held=first,
        score_func=c["scoring_func"], scaling=c["routed_scaling_factor"],
        compute_dtype=jnp.float32)


def test_expert_shares_sum_to_the_uncut_layer(cell):
    """Each share of the experts computes its own part of the routed result
    and the shared expert; over all shares, with the shared expert counted
    once, the parts are the uncut reference layer."""
    n_experts = cell.config["deployment"]["experts_published"]
    held = cell.config["n_routed_experts"]
    p = _layer_params(cell, jax.random.key(5), n_experts)
    x = jax.random.normal(jax.random.key(6), (2, 16, cell.config["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = cell.model.expert_layer(x.reshape(32, -1), p,
                                       _layer_config(cell, n_experts, 0))
        sh = p["shared"]
        shared = (jax.nn.silu(x @ sh["gate"]["w"]) * (x @ sh["up"]["w"])) @ sh["down"]["w"]
        parts = [_program_layer(cell, _held_part(p, f, held), x, f, held)
                 for f in range(0, n_experts, held)]
    total = sum(out for out, _ in parts) - (len(parts) - 1) * shared
    np.testing.assert_allclose(np.asarray(total).reshape(32, -1),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # every token-expert assignment lands in exactly one share
    assigned = sum(float(c["moe_held_assignments"]) for _, c in parts)
    assert assigned == 32 * cell.config["num_experts_per_tok"]


def test_routing_to_one_held_expert_drops_no_token(cell):
    """A selection bias that sends every token to one held expert: the
    program's layer keeps every assignment (no capacity) and agrees with
    the reference."""
    held = cell.config["n_routed_experts"]
    first = cell.config["deployment"]["first_expert_held"]
    n_experts = cell.config["deployment"]["experts_published"]
    p = _held_part(_layer_params(cell, jax.random.key(7), n_experts), first, held)
    p["router"] = dict(p["router"], bias=p["router"]["bias"].at[first].set(10.0))
    x = jax.random.normal(jax.random.key(8), (2, 24, cell.config["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, counters = _program_layer(cell, p, x, first, held)
        want = cell.model.expert_layer(x.reshape(48, -1), p, cell.config)
    np.testing.assert_allclose(np.asarray(got).reshape(48, -1), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # all 48 tokens reach the biased expert: its load is the whole batch
    assert float(counters["moe_held_assignments"]) >= 48
    assert float(counters["moe_held_max_load"]) * float(
        counters["moe_held_assignments"]) / held == pytest.approx(48)


def test_train_flops_count_the_expected_held_assignments(cell):
    """``train_flops`` counts the held experts at k x held / experts a
    token; the expert layers' part of it is ``expert_layer_flops``."""
    c, t = cell.config, cell.traffic
    s = int(t["seq_len"])
    k, held = c["num_experts_per_tok"], c["n_routed_experts"]
    e = c["deployment"]["experts_published"]
    moe_layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    expected = k * held / e * s * moe_layers
    part = cell.model.expert_layer_flops(c, s, expected)
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    assert part - cell.model.expert_layer_flops(c, s, 0) == pytest.approx(
        6 * expected * 3 * d * fe)
    assert 0 < part < cell.model.train_flops(c, t)


@pytest.mark.parametrize("program", ["counts", "parent"])
def test_expert_readers_on_a_traced_context(program):
    """The cell's readers on a traced run's context: the ``mla`` and
    ``moe`` scopes' ms a round and the expert layers' share of the peak;
    nothing (and no error) where the program has no such scope or counter,
    as a program without the expert layer."""
    cell = harness.resolve(WORKLOAD)
    scope_s = {"fl_local": 6.0, "mla": 4.0, "moe": 1.2}
    metrics = {"loss": 10.0, "moe_held_assignments": 6144.0}
    if program == "parent":
        scope_s, metrics = {"fl_local": 6.0}, {"loss": 10.0}
    chips = [{"busy_s": 9.5, "kernel_s": 0.6, "kernel_events": 10,
              "scope_s": scope_s} for _ in range(4)]
    out = {"correct": True, "attempted": 10, "failed": 0, "rounds": 10,
           "device": {}, "checks": {}, "flat_total": 425_354_240,
           "round_metrics": metrics,
           "trace": {"window_s": 10.0, "chips": chips, "device_ops": [],
                     "idle_gaps": []}}
    line = harness.result_line(cell, out, harness.peaks("TPU v5 lite"))
    new = {"mla_ms", "moe_ms", "moe_peak_share"}
    if program == "parent":
        assert not new & set(line["metrics"])
        return
    got = {k: line["metrics"][k]["value"] for k in new}
    assert got["mla_ms"] == pytest.approx(400.0)
    assert got["moe_ms"] == pytest.approx(120.0)
    flops = 4 * cell.model.expert_layer_flops(cell.config, 4096, 6144.0)
    assert got["moe_peak_share"] == pytest.approx(100 * flops / (0.12 * 197e12))
