"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) pair.

Proves the distribution config is coherent without TPU hardware:
  * 512 placeholder host devices stand in for 2 pods x 256 chips;
  * every combination must .lower().compile() under its production
    sharding; failures (sharding mismatch, unsupported collective) are
    bugs in the system, not in the environment;
  * memory_analysis() / cost_analysis() + the collective ops parsed from
    the compiled HLO feed EXPERIMENTS.md (§Dry-run, §Roofline).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
      --shape train_4k --mesh single --q 4 --out experiments/dryrun
  (run_all: benchmarks/run_dryruns.py drives every pair with caching)
"""

# The VERY FIRST lines, before ANY other import: jax locks the device
# count at first initialization.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    SHAPES,
    decode_sliding_override,
    get_config,
    serve_input_specs,
    supports_shape,
    train_input_specs,
)
from repro.core.dynamics import program_names  # noqa: E402
from repro.core.engine import engine_names, get_engine, schedule_names  # noqa: E402
from repro.core.fl import FLConfig, FLState, make_fl_round  # noqa: E402
from repro.core.schedules import inv_sqrt  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    make_production_mesh,
    model_axis,
    n_fl_nodes,
    node_axes,
)
from repro.models import build_model  # noqa: E402
from repro.models.sharding import model_param_specs, node_stack_specs  # noqa: E402


def _sds_tree(tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree
    )


def _stack_nodes_sds(tree, n_nodes: int):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n_nodes,) + l.shape, l.dtype), tree
    )


def build_train_lowering(arch: str, shape_name: str, mesh, q: int, algorithm: str = "dsgt",
                         wire_dtype=None, pod_gossip_every: int = 1, impl: str = "ref",
                         pad_heads: int = 0, fl_engine: str = "tree",
                         scale_chunk: int = 512, topk=None,
                         fl_schedule: str = "sequential",
                         fl_topology_program: Optional[str] = None,
                         fl_node_program: Optional[str] = None,
                         fl_privacy: Optional[str] = None,
                         fl_scope: Optional[str] = None,
                         fl_shard_model: bool = False):
    """Lower one FL round (Q local steps + gossip) for the given mesh.

    ``fl_engine`` names a registered GossipEngine (the registry in
    ``repro.core.engine`` is the one source of truth; no string dispatch
    here), built against the mesh with its ``from_mesh`` constructor:

      * "tree"          -- node-stacked pytree state, per-leaf model
                           sharding, ppermute gossip inside shard_map;
      * "flat"          -- the state lives as ONE packed (nodes, total)
                           buffer end to end; local steps, metrics, and
                           gossip are all single-buffer ops;
      * "fused"         -- the round megakernel against the dense
                           equivalent of the mesh's circulant W. The
                           dry-run lowers the kernel's jnp oracle
                           (bit-identical math) because GSPMD can
                           partition it over the node axes;
      * "sharded_fused" -- the shard_map-native fused round: wire-stage
                           Pallas kernel per shard (interpret off-TPU) +
                           int8 ppermute wire; the one-kernel-per-round
                           property survives the mesh.

    ``topk`` masks the fused engines' payload to k columns per scale
    chunk; on the sharded engine it also turns on the COMPACT wire (the
    collective moves k int8 values + k positions + scales per chunk
    instead of the masked-dense buffer). ``fl_schedule`` selects the
    round's time layout through the RoundSchedule registry:
    "sequential" (produce -> collective -> mix) or "pipelined" (the
    collective for round r's payload is issued before round r+1's
    local-step scan and the mix consumes one-round-stale neighbor
    information; fused engines only). ``fl_topology_program`` selects the
    per-round graph dynamics through the TopologyProgram registry
    (``repro.core.dynamics``; e.g. "node_churn:p_down=0.2"): the round's
    mixing weights become traced operands of the one compiled round --
    churn adds zero recompiles and zero collectives (fused engines; the
    sharded engine gates its circulant ppermute wire).
    ``fl_node_program`` adds per-node heterogeneity the same way
    (``repro.core.heterogeneity``; e.g. "stragglers:frac=0.25"): compute
    and payload gates are traced operands, so slow/faulty nodes change
    nothing about the lowering. ``fl_schedule`` also accepts depth-k
    specs ("bounded_staleness:k=3"): the comm state grows a k-slot wire
    ring but the collective still moves ONE slot per round. ``fl_privacy``
    adds the wire's privacy epilogue the same way (``repro.core.privacy``;
    e.g. "secure_agg+dp:sigma=0.5,clip=1.0"): pads and noise are generated
    from comm-state counters inside the round, so the lowering keeps the
    identical collective count and operand bytes as the plaintext wire.
    """
    import dataclasses as _dc

    engine_cls = get_engine(fl_engine)  # raises with the registry listing
    cfg = get_config(arch)
    if pad_heads:
        cfg = _dc.replace(cfg, tp_head_pad=pad_heads)
    bundle = build_model(cfg, impl=impl, remat=True)
    shape = SHAPES[shape_name]
    nodes = n_fl_nodes(mesh)
    naxes = node_axes(mesh)

    params_sds = jax.eval_shape(bundle.init_fn, jax.random.key(0))
    stacked_sds = _stack_nodes_sds(params_sds, nodes)
    pspecs = node_stack_specs(model_param_specs(params_sds), naxes)

    fl_cfg = FLConfig(algorithm=algorithm, q=q, n_nodes=nodes)
    # Hierarchical gossip (pod_gossip_every > 1): the driver alternates two
    # jitted rounds; this lowering is the COMMON-CASE round whose gossip
    # mixes only the intra-pod ("data") axis. The every-k-th full round is
    # the pod_gossip_every == 1 lowering; amortized cost =
    # ((k-1) * data_only + full) / k (EXPERIMENTS.md §Perf).
    hier = pod_gossip_every > 1 and "pod" in naxes

    extra = {}
    if fl_shard_model:
        # the two-axis (gossip_node, model_shard) round: each node's flat
        # buffer tiles over the model axis; gossip stays node-axis-only
        if fl_engine != "sharded_fused":
            raise ValueError(
                "--fl-shard-model needs the sharded_fused engine (the "
                f"two-axis wire is its contract); got fl_engine={fl_engine!r}"
            )
        maxis = model_axis(mesh)
        if maxis is None:
            raise ValueError(
                "--fl-shard-model needs a mesh with a 'model' axis; "
                f"this mesh has {mesh.axis_names!r}"
            )
        extra["model_axis"] = maxis
    if fl_engine == "fused":
        # the dense engine runs under GSPMD here, which partitions the
        # jnp oracle but not a Pallas call
        extra["impl"] = "jnp"
    engine = engine_cls.from_mesh(
        mesh, naxes, stacked_sds, specs=pspecs, wire_dtype=wire_dtype,
        axes_subset=("data",) if hier else None, scale_chunk=scale_chunk,
        topk=topk, round_schedule=fl_schedule,
        topology_program=fl_topology_program,
        node_program=fl_node_program,
        privacy=fl_privacy, scope=fl_scope, **extra,
    )
    round_fn = make_fl_round(
        bundle.loss_fn, None, inv_sqrt(0.02), fl_cfg, engine=engine
    )

    int_sds = jax.ShapeDtypeStruct((), jnp.int32)
    if engine.layout is None:
        buf_sds, buf_specs = stacked_sds, pspecs
    else:
        buf_sds = jax.ShapeDtypeStruct(
            (nodes, engine.layout.total),
            jnp.dtype(engine.layout.storage_dtype),
        )
        # the engine owns its partition spec: the two-axis sharded engine
        # tiles the flat buffer's columns over the model axis
        buf_specs = (engine.params_spec() if hasattr(engine, "params_spec")
                     else P(tuple(naxes), None))
    # comm buffers from the engine's own contract (shapes/dtypes differ
    # per schedule and wire: in-flight int8 payloads, positions, scales).
    # Node-stacked (rank >= 2) buffers shard over the LEADING node axes
    # only -- depth-k rings are (n, k, width) and the dense-W neighbor
    # replica is (n, n, t), both sharded by receiver row; the topology
    # program's scalar counters (topo_round, topo_key) replicate. Engines
    # exposing comm_state_specs (the two-axis sharded engine) decide for
    # themselves which trailing axes tile over the model axis.
    comm_sds = engine.comm_state_sds(fl_cfg)
    if comm_sds is None:
        comm_specs = None
    elif hasattr(engine, "comm_state_specs"):
        comm_specs = engine.comm_state_specs(fl_cfg)
    else:
        comm_specs = {
            k: (P(tuple(naxes), *(None,) * (len(s.shape) - 1))
                if len(s.shape) >= 2 else P())
            for k, s in comm_sds.items()
        }
    if algorithm == "dsgt":
        state_sds = FLState(int_sds, buf_sds, buf_sds, buf_sds, comm_sds)
        state_specs = FLState(P(), buf_specs, buf_specs, buf_specs, comm_specs)
    else:
        state_sds = FLState(int_sds, buf_sds, None, None, comm_sds)
        state_specs = FLState(P(), buf_specs, None, None, comm_specs)

    batch_sds = train_input_specs(cfg, shape, nodes, q)
    batch_specs = jax.tree_util.tree_map(
        lambda l: P(None, naxes, *(None,) * (l.ndim - 2)), batch_sds
    )

    def shardings(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    jitted = jax.jit(
        round_fn, in_shardings=(shardings(state_specs), shardings(batch_specs))
    )
    aux = {"engine": engine, "round_fn": round_fn, "fl_cfg": fl_cfg,
           "mesh": mesh}
    return jitted, (state_sds, batch_sds), cfg, aux


def _serve_param_shardings(mesh, params_sds):
    specs = model_param_specs(params_sds)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def build_prefill_lowering(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    bundle = build_model(cfg, impl="ref", remat=False)
    shape = SHAPES[shape_name]
    naxes = node_axes(mesh)
    params_sds = jax.eval_shape(bundle.init_fn, jax.random.key(0))
    batch_sds = serve_input_specs(cfg, shape)
    nodes = n_fl_nodes(mesh)
    bdim = naxes if shape.global_batch % nodes == 0 else (
        ("data",) if shape.global_batch % mesh.shape["data"] == 0 else None
    )
    batch_specs = jax.tree_util.tree_map(
        lambda l: P(bdim, *(None,) * (l.ndim - 1)), batch_sds
    )
    jitted = jax.jit(
        bundle.prefill_fn,
        in_shardings=(
            _serve_param_shardings(mesh, params_sds),
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), batch_specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        ),
    )
    return jitted, (params_sds, batch_sds), cfg


def _cache_specs(cache_sds, batch: int, naxes, divisible: bool):
    """Shard the batch dim of every decode-cache leaf over the node axes."""

    def f(l):
        spec = [None] * l.ndim
        if divisible:
            for i, d in enumerate(l.shape):
                if d == batch and i <= 1:
                    spec[i] = naxes
                    break
        return P(*spec)

    return jax.tree_util.tree_map(f, cache_sds)


def build_decode_lowering(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    bundle = build_model(cfg, impl="ref", remat=False)
    shape = SHAPES[shape_name]
    naxes = node_axes(mesh)
    nodes = n_fl_nodes(mesh)
    sliding = decode_sliding_override(cfg, shape)
    b = shape.global_batch
    params_sds = jax.eval_shape(bundle.init_fn, jax.random.key(0))
    cache_sds = jax.eval_shape(
        lambda: bundle.init_decode_state_fn(b, shape.seq_len, sliding_override=sliding)
    )
    divisible = b % nodes == 0
    cache_specs = _cache_specs(cache_sds, b, naxes, divisible)
    tok_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
    tok_spec = P(naxes) if divisible else P()

    def step(params, tokens, caches):
        return bundle.decode_fn(params, tokens, caches, sliding_override=sliding)

    jitted = jax.jit(
        step,
        in_shardings=(
            _serve_param_shardings(mesh, params_sds),
            NamedSharding(mesh, tok_spec),
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), cache_specs,
                is_leaf=lambda x: isinstance(x, P),
            ),
        ),
    )
    return jitted, (params_sds, tok_sds, cache_sds), cfg


def _walk_jaxpr(jaxpr, name, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for v in eqn.params.values():
            subs = v if isinstance(v, (list, tuple)) else [v]
            for sub in subs:
                if hasattr(sub, "jaxpr"):
                    _walk_jaxpr(sub.jaxpr, name, found)
                elif hasattr(sub, "eqns"):
                    _walk_jaxpr(sub, name, found)
    return found


def two_axis_record(engine, round_fn, state_sds, batch_sds, fl_cfg) -> Dict[str, Any]:
    """Jaxpr proof obligations for the two-axis (node, shard) round:

      * ONE pallas_call per (node, shard) wire-stage tile -- the shard_map
        body traces once with per-device-tile (local) shapes, so one
        pallas_call eqn IS one kernel launch per tile;
      * every gossip collective (ppermute / all_gather) binds node axes
        ONLY -- nothing moves over the model axis;
      * the collective operands of one wire direction are EXACTLY the
        per-shard compact encoding: flat_wire_bytes_per_shard bytes.

    Returns the record fields; raises AssertionError when the lowering
    breaks the contract (a bug, not an environment problem)."""
    from repro.core.packing import flat_wire_bytes_per_shard

    jx = jax.make_jaxpr(round_fn)(state_sds, batch_sds)
    pallas = _walk_jaxpr(jx.jaxpr, "pallas_call", [])
    assert len(pallas) == 1, (
        f"two-axis round must stay ONE wire-stage kernel per (node, shard) "
        f"tile; found {len(pallas)} pallas_call eqns"
    )
    node_axes_set = set(engine.node_axes)
    coll = (_walk_jaxpr(jx.jaxpr, "ppermute", [])
            + _walk_jaxpr(jx.jaxpr, "all_gather", []))
    axes_seen = set()
    for eqn in coll:
        ax = eqn.params.get("axis_name")
        for a in (ax if isinstance(ax, (list, tuple)) else (ax,)):
            axes_seen.add(a)
    assert axes_seen and axes_seen <= node_axes_set, (
        f"gossip collectives must bind node axes only; saw {axes_seen!r} "
        f"vs node axes {node_axes_set!r}"
    )
    # one wire direction = one group of per-buffer ppermutes (compact
    # bitmap wire: values + bitmap + scales = 3; dense int8 wire: q +
    # scales = 2). Inside shard_map the jaxpr's shapes are LOCAL
    # per-device tiles: one node row x one model shard.
    pp = _walk_jaxpr(jx.jaxpr, "ppermute", [])
    n_buffers = 3 if engine.compact_wire else 2
    per_shard = None
    if pp:
        one_dir = pp[:n_buffers]
        moved = sum(int(np.prod(e.invars[0].aval.shape))
                    * e.invars[0].aval.dtype.itemsize for e in one_dir)
        # the wire moves the SCOPED layout: under a partial federation
        # scope the collectives carry only the shared slice's columns
        per_shard = flat_wire_bytes_per_shard(
            getattr(engine, "wire_layout", engine.layout), 1,
            engine.scale_chunk,
            engine.topk if engine.compact_wire else None)
        assert moved == per_shard, (
            f"per-shard collective operand bytes {moved} != "
            f"flat_wire_bytes_per_shard {per_shard}"
        )
    return {
        "model_axis": engine.model_axis,
        "model_shards": int(engine.model_shards),
        "shard_width": int(engine.layout.shard_width),
        "pallas_calls": len(pallas),
        "collective_axes": sorted(axes_seen),
        "wire_bytes_per_shard_one_edge": per_shard,
        "wire_bytes_per_shard_per_round": float(
            engine.wire_bytes_per_shard(fl_cfg)),
    }


def run_pair(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    q: int = 4,
    algorithm: str = "dsgt",
    wire_dtype: Optional[str] = None,
    pod_gossip_every: int = 1,
    remat: bool = True,
    impl: str = "ref",
    pad_heads: int = 0,
    fl_engine: str = "tree",
    topk=None,
    fl_schedule: str = "sequential",
    fl_topology_program: Optional[str] = None,
    fl_node_program: Optional[str] = None,
    fl_privacy: Optional[str] = None,
    fl_scope: Optional[str] = None,
    fl_shard_model: bool = False,
) -> Dict[str, Any]:
    """Lower + compile one pair; return the dry-run record."""
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    if not supports_shape(cfg, shape):
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "skipped", "reason": "whisper x long_500k: enc-dec full-attention decoder (DESIGN.md §4)",
        }
    wd = jnp.dtype(wire_dtype) if wire_dtype else None
    t0 = time.time()
    aux = None
    with mesh:
        if shape.kind == "train":
            jitted, args, cfg, aux = build_train_lowering(
                arch, shape_name, mesh, q, algorithm, wd, pod_gossip_every, impl,
                pad_heads, fl_engine, topk=topk, fl_schedule=fl_schedule,
                fl_topology_program=fl_topology_program,
                fl_node_program=fl_node_program,
                fl_privacy=fl_privacy, fl_scope=fl_scope,
                fl_shard_model=fl_shard_model,
            )
            lowered = jitted.lower(*args)
        elif shape.kind == "prefill":
            jitted, args, cfg = build_prefill_lowering(arch, shape_name, mesh)
            lowered = jitted.lower(*args)
        else:
            jitted, args, cfg = build_decode_lowering(arch, shape_name, mesh)
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    # while-aware accounting (cost_analysis counts scan bodies once)
    hlo = analyze_hlo(compiled.as_text())
    n_chips = int(np.prod(list(mesh.shape.values())))
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "kind": shape.kind,
        "status": "ok",
        "q": q if shape.kind == "train" else None,
        "algorithm": algorithm if shape.kind == "train" else None,
        "impl": impl,
        "fl_engine": fl_engine if shape.kind == "train" else None,
        "fl_schedule": fl_schedule if shape.kind == "train" else None,
        "fl_topology_program": (
            fl_topology_program if shape.kind == "train" else None
        ),
        "fl_node_program": (
            fl_node_program if shape.kind == "train" else None
        ),
        "fl_privacy": fl_privacy if shape.kind == "train" else None,
        "fl_scope": fl_scope if shape.kind == "train" else None,
        "topk": topk if shape.kind == "train" else None,
        "wire_dtype": wire_dtype,
        "pod_gossip_every": pod_gossip_every,
        "n_chips": n_chips,
        "n_nodes": n_fl_nodes(mesh),
        "flops": float(hlo.flops),
        "traffic_bytes": float(hlo.traffic_bytes),
        "raw_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": {
            "per_kind": hlo.collectives,
            "total_bytes": float(hlo.collective_bytes),
            "cross_node_bytes": float(hlo.cross_node_bytes),
            "cross_pod_bytes": float(hlo.cross_pod_bytes),
        },
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        },
        "model_params": cfg.param_count() if cfg.family != "mlp" else None,
        "active_params": cfg.active_param_count() if cfg.family != "mlp" else None,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if fl_shard_model and aux is not None:
        with mesh:
            record["two_axis"] = two_axis_record(
                aux["engine"], aux["round_fn"], args[0], args[1],
                aux["fl_cfg"])
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="single", choices=("single", "multi"))
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--algorithm", default="dsgt", choices=("dsgd", "dsgt"))
    ap.add_argument("--wire-dtype", default=None)
    ap.add_argument("--pod-gossip-every", type=int, default=1)
    ap.add_argument("--impl", default="ref", choices=("ref", "blocked"))
    ap.add_argument("--fl-engine", default="tree", choices=engine_names(),
                    help="round engine, resolved through the GossipEngine "
                         "registry (repro.core.engine; see "
                         "docs/ARCHITECTURE.md)")
    ap.add_argument("--topk", type=int, default=None,
                    help="fused engines: ship only the k largest payload "
                         "columns per scale chunk (compact sparse wire on "
                         "the sharded engine)")
    ap.add_argument("--fl-schedule", default="sequential",
                    help="round time layout, resolved through the "
                         f"RoundSchedule registry ({', '.join(schedule_names())}): "
                         "pipelined overlaps the collective with the next "
                         "round's local steps; spec syntax "
                         "'bounded_staleness:k=3' keeps k payloads in "
                         "flight (fused engines only)")
    ap.add_argument("--fl-topology-program", default=None,
                    help="per-round graph dynamics, resolved through the "
                         "TopologyProgram registry "
                         f"({', '.join(program_names())}); spec syntax "
                         "name:k=v,... e.g. "
                         "'node_churn:p_down=0.2,mean_downtime=5' -- "
                         "fused engines take any W, the sharded engine "
                         "gates its circulant ppermute wire")
    ap.add_argument("--fl-node-program", default=None,
                    help="per-node heterogeneity, resolved through the "
                         "NodeProgram registry (repro.core.heterogeneity); "
                         "spec syntax name:k=v,... e.g. "
                         "'stragglers:frac=0.25,rate=0.5' -- compute and "
                         "payload gates are traced operands of the one "
                         "compiled round")
    ap.add_argument("--fl-privacy", default=None,
                    help="wire privacy epilogue (repro.core.privacy); "
                         "'+'-separated spec e.g. "
                         "'secure_agg+dp:sigma=0.5,clip=1.0' -- pads and "
                         "noise ride comm-state counters, so the lowering "
                         "keeps the plaintext wire's collective count and "
                         "operand bytes")
    ap.add_argument("--fl-scope", default=None,
                    help="federation scope (repro.core.scope): which "
                         "flat-buffer columns gossip touches -- 'full', "
                         "'backbone[:private=PAT]', 'ranges:a-b,...', "
                         "'layerwise:freq=R' (fused only); partial scopes "
                         "shrink every collective operand to the shared "
                         "slice (asserted on the jaxpr)")
    ap.add_argument("--fl-shard-model", action="store_true",
                    help="two-axis (gossip_node, model_shard) round: each "
                         "node's flat parameter buffer tiles over the mesh's "
                         "'model' axis; the wire stage runs one Pallas pass "
                         "per (node, shard) tile and the gossip collective "
                         "stays node-axis-only (sharded_fused engine only; "
                         "jaxpr-asserted, recorded under 'two_axis')")
    ap.add_argument("--pad-heads", type=int, default=0,
                    help="pad q heads to a multiple of this (16 = TP degree)")
    ap.add_argument("--out", default=None, help="directory for the JSON record")
    args = ap.parse_args()

    rec = run_pair(
        args.arch, args.shape, args.mesh, q=args.q, algorithm=args.algorithm,
        wire_dtype=args.wire_dtype, pod_gossip_every=args.pod_gossip_every,
        impl=args.impl, pad_heads=args.pad_heads, fl_engine=args.fl_engine,
        topk=args.topk, fl_schedule=args.fl_schedule,
        fl_topology_program=args.fl_topology_program,
        fl_node_program=args.fl_node_program,
        fl_privacy=args.fl_privacy,
        fl_scope=args.fl_scope,
        fl_shard_model=args.fl_shard_model,
    )
    print(json.dumps(rec, indent=2))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        suffix = ""
        if args.impl != "ref":
            suffix += f"_{args.impl}"
        if args.fl_engine != "tree":
            suffix += f"_{args.fl_engine}"
        if args.topk:
            suffix += f"_topk{args.topk}"
        if args.fl_shard_model:
            suffix += "_shardmodel"
        if args.fl_schedule != "sequential":
            suffix += "_" + args.fl_schedule.replace(":", "-").replace("=", "")
        if args.fl_topology_program:
            suffix += "_" + args.fl_topology_program.split(":")[0]
        if args.fl_node_program:
            suffix += "_" + args.fl_node_program.split(":")[0]
        if args.fl_privacy:
            suffix += "_" + args.fl_privacy.split(":")[0].replace("+", "-")
        if args.fl_scope:
            suffix += "_scope-" + args.fl_scope.split(":")[0]
        if args.pad_heads:
            suffix += f"_hpad{args.pad_heads}"
        if args.wire_dtype:
            suffix += f"_wire-{args.wire_dtype}"
        if args.pod_gossip_every > 1:
            suffix += f"_podq{args.pod_gossip_every}"
        if args.q != 4 and args.shape in ("train_4k",):
            suffix += f"_q{args.q}"
        if args.algorithm != "dsgt":
            suffix += f"_{args.algorithm}"
        fname = f"{args.arch}_{args.shape}_{args.mesh}{suffix}.json"
        with open(os.path.join(args.out, fname), "w") as f:
            json.dump(rec, f, indent=2)


if __name__ == "__main__":
    main()
