"""The round's stages in the compiled program.

Every engine's round carries the stage names of ``repro.core.engine``
(``fl_local``, ``fl_wire``, ``fl_transport``, ``fl_metrics``) in the
``op_name`` metadata of its compiled instructions, which is how a device
trace attributes time to them. A small MLP round is compiled on the CPU
for the tree, flat and fused engines, and for the sharded engine on 8
forced host devices in a subprocess that runs this file (jax locks the
device count at init). ``fl_transport`` marks the collectives that move
the payload, so it appears only where the round has one: the sharded
engine's ppermute and all-gather wires, and its pipelined ingest.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FLConfig, FlatEngine, FusedEngine, TreeEngine,
                        init_fl_state, make_fl_round)
from repro.core.engine import STAGES
from repro.core.schedules import constant

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q = 3
LOCAL_CASES = [f"{engine}-{alg}"
               for engine in ("tree", "flat", "fused", "fused_pipelined")
               for alg in ("dsgd", "dsgt")]
SHARDED_CASES = [f"sharded_{wire}-{alg}"
                 for wire in ("ppermute", "allgather", "pipelined")
                 for alg in ("dsgd", "dsgt")]


def _loss(p, b):
    h = jnp.tanh(b["x"] @ p["w1"])
    return jnp.mean((h @ p["w2"] - b["y"]) ** 2)


def _problem(n):
    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(n, 6, 8)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(n, 8, 2)), jnp.float32),
    }
    batches = {"x": jnp.ones((Q, n, 4, 6)), "y": jnp.ones((Q, n, 4, 2))}
    return params, batches


def _summary(engine, state, batches, algorithm, n):
    """The stages in the compiled round's metadata, the stages of its
    collective-permutes, and whether it has any collective that can move
    a payload."""
    cfg = FLConfig(algorithm=algorithm, q=Q, n_nodes=n)
    rf = make_fl_round(_loss, None, constant(0.05), cfg, engine=engine)
    text = jax.jit(rf).lower(
        init_fl_state(cfg, state, engine=engine), batches
    ).compile().as_text()
    stages, permute_stages, collective = set(), set(), False
    for line in text.splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        m = re.search(r'op_name="([^"]*)"', rhs)
        here = [c for c in m.group(1).split("/") if c in STAGES] if m else []
        stages.update(here)
        if re.search(r"\b(collective-permute|all-gather)(-start)?\(", rhs):
            collective = True
        if re.search(r"\bcollective-permute(-start)?\(", rhs):
            permute_stages.add(here[-1] if here else "unscoped")
    return {"stages": sorted(stages), "permute_stages": sorted(permute_stages),
            "collective": collective}


def _local_summary(case):
    engine, alg = case.split("-")
    n = 4
    params, batches = _problem(n)
    w = np.full((n, n), 1.0 / n)
    if engine == "tree":
        eng, state = TreeEngine.simulated(w, params)
    elif engine == "flat":
        eng, state = FlatEngine.simulated(w, params)
    else:
        sched = "pipelined" if engine == "fused_pipelined" else "sequential"
        eng, state = FusedEngine.simulated(w, params, scale_chunk=16,
                                           round_schedule=sched)
    return _summary(eng, state, batches, alg, n)


def _sharded_summaries(cases):
    """Run in a process with 8 host devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ShardedFusedEngine, pack
    from repro.launch.mesh import make_test_mesh, n_fl_nodes, node_axes

    mesh = make_test_mesh((2, 2, 2))
    naxes = node_axes(mesh)
    n = n_fl_nodes(mesh)
    params, batches = _problem(n)
    flat, _ = pack(params, pad_to=16)
    out = {}
    for case in cases:
        kind, alg = case.split("-")
        w = np.full((n, n), 1.0 / n) if kind == "sharded_allgather" else None
        sched = "pipelined" if kind == "sharded_pipelined" else "sequential"
        eng = ShardedFusedEngine.from_mesh(
            mesh, naxes, params, scale_chunk=16, w=w, round_schedule=sched)
        with mesh:
            state = jax.device_put(flat, NamedSharding(mesh, P(naxes, None)))
            out[case] = _summary(eng, state, batches, alg, n)
    return out


@pytest.fixture(scope="module")
def sharded_summaries():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *SHARDED_CASES], env=env,
        capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", LOCAL_CASES + SHARDED_CASES)
def test_round_stages_in_compiled_hlo(case, request):
    sharded = case.startswith("sharded")
    if sharded:
        got = request.getfixturevalue("sharded_summaries")[case]
    else:
        got = _local_summary(case)
    want = {"fl_local", "fl_wire", "fl_metrics"} | (
        {"fl_transport"} if sharded else set())
    assert set(got["stages"]) == want, (case, got)
    # fl_transport only where the round has a collective; every ppermute
    # (it moves nothing but payload) is under it
    assert got["collective"] == sharded, (case, got)
    want_permute = [] if case.startswith(("sharded_allgather", "tree",
                                          "flat", "fused")) else ["fl_transport"]
    assert got["permute_stages"] == want_permute, (case, got)


@pytest.mark.parametrize("engine", ["flat", "fused"])
def test_flat_conversion_scopes_in_local_step(engine):
    """The flat engines' conversion between the packed state and the leaf
    view around ``eval_grads`` carries ``flat_unpack`` and ``flat_pack``
    in its compiled ``op_name``s, always inside ``fl_local``."""
    n = 4
    rng = np.random.default_rng(0)
    # 128-aligned leaves: the conversion takes the row path
    params = {"w1": jnp.asarray(rng.normal(size=(n, 6, 64)), jnp.float32),
              "w2": jnp.asarray(rng.normal(size=(n, 64, 2)), jnp.float32)}
    batches = {"x": jnp.ones((Q, n, 4, 6)), "y": jnp.ones((Q, n, 4, 2))}
    w = np.full((n, n), 1.0 / n)
    if engine == "flat":
        eng, state = FlatEngine.simulated(w, params)
    else:
        eng, state = FusedEngine.simulated(w, params, scale_chunk=128)
    assert eng.layout.row_columns == eng.layout.used
    cfg = FLConfig(algorithm="dsgd", q=Q, n_nodes=n)
    rf = make_fl_round(_loss, None, constant(0.05), cfg, engine=eng)
    text = jax.jit(rf).lower(
        init_fl_state(cfg, state, engine=eng), batches).compile().as_text()
    seen = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        parts = path.split("/")
        for scope in ("flat_pack", "flat_unpack"):
            if scope in parts:
                seen.add(scope)
                assert "fl_local" in parts[:parts.index(scope)], path
    assert seen == {"flat_pack", "flat_unpack"}


if __name__ == "__main__":
    print(json.dumps(_sharded_summaries(sys.argv[1:])))
