"""End-to-end driver: train a decoder with FD-DSGT for a few hundred
steps (deliverable (b): the end-to-end training example).

Two modes:

  * default -- a 100M-class llama-family config (d=512, 8 layers, 32k
    vocab) across 4 FL nodes on a ring with Q=5 local steps per round,
    through the simulated tree engine (single device, dense-W gossip);

  * decentralized -- ``--fl-engine sharded_fused`` builds the round on a
    ``(gossip_node, model_shard)`` mesh of forced host CPU devices (a
    rehearsal of the chip path; ``chip_smoke.py --chips 4`` runs it on
    TPUs): each node's parameters live as one flat buffer whose
    columns tile over the model axis, the wire stage runs one fused pass
    per (node, shard) tile, and the int8 gossip collective stays on the
    node axis only. ``--arch smollm-360m`` swaps in the SmolLM-360M
    config (``--smoke`` shrinks it to a 2-layer smoke variant that runs
    in seconds on CPU). The other round axes ride along:
    ``--fl-schedule/--fl-topology-program/--fl-node-program/--fl-privacy``.

  PYTHONPATH=src python examples/train_100m.py --rounds 60
  PYTHONPATH=src python examples/train_100m.py --arch smollm-360m --smoke \
      --fl-engine sharded_fused --model-shards 2 --topk 8 --rounds 6
"""

# XLA locks the device count at first jax initialization, so the mesh
# size must be decided from argv BEFORE importing jax.
import os
import sys


def _argv_value(flag, default):
    if flag in sys.argv:
        i = sys.argv.index(flag)
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return default


if _argv_value("--fl-engine", "tree") == "sharded_fused":
    _n = int(_argv_value("--nodes", "4"))
    _s = int(_argv_value("--model-shards", "1"))
    # forced host devices are a CPU rehearsal: stay off any accelerator
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n * _s} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse  # noqa: E402
import csv  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402

from repro.configs import FLRunConfig, get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.data.tokens import make_fl_token_batches  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.training.checkpoint import save_fl_state  # noqa: E402
from repro.training.trainer import (  # noqa: E402
    stack_for_nodes,
    train_decentralized,
)


def model_100m() -> ModelConfig:
    return ModelConfig(
        name="llama-100m",
        family="dense",
        n_layers=8,
        d_model=512,
        n_heads=8,
        n_kv_heads=4,
        d_ff=1536,
        vocab_size=32000,
        head_dim=64,
        source="100M-class llama-family config (this repo)",
    )


def build_sharded_engine(args, stacked):
    """The two-axis (gossip_node, model_shard) engine on forced host
    devices: node ring over 'data', flat-buffer columns over 'model'."""
    from repro.core import ShardedFusedEngine

    shards = args.model_shards
    mesh = jax.make_mesh((args.nodes, shards), ("data", "model"))
    engine = ShardedFusedEngine.from_mesh(
        mesh, ("data",), stacked, scale_chunk=args.scale_chunk,
        topk=args.topk, impl="pallas",
        model_axis="model" if shards > 1 else None,
        round_schedule=args.fl_schedule,
        topology_program=args.fl_topology_program,
        node_program=args.fl_node_program,
        privacy=args.fl_privacy,
    )
    return engine, mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--q", type=int, default=5)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch-per-node", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--alpha0", type=float, default=0.4)
    ap.add_argument("--ckpt", default="experiments/ckpt_100m")
    ap.add_argument("--arch", default="llama-100m",
                    help="'llama-100m' (built in) or a registry arch like "
                         "'smollm-360m'")
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's smoke variant (registry archs only)")
    ap.add_argument("--fl-engine", default="tree",
                    choices=("tree", "flat", "fused", "sharded_fused"),
                    help="'sharded_fused' trains on a real (gossip_node, "
                         "model_shard) mesh of forced host devices")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="size of the mesh's model axis (sharded_fused "
                         "only): each node's flat buffer tiles over it")
    ap.add_argument("--scale-chunk", type=int, default=256)
    ap.add_argument("--topk", type=int, default=None,
                    help="fused engines: ship only the k largest payload "
                         "columns per scale chunk")
    ap.add_argument("--fl-schedule", default=None,
                    help="round time layout, e.g. 'pipelined' or "
                         "'bounded_staleness:k=2'")
    ap.add_argument("--fl-topology-program", default=None,
                    help="per-round graph dynamics, e.g. "
                         "'node_churn:p_down=0.2,mean_downtime=5'")
    ap.add_argument("--fl-node-program", default=None,
                    help="per-node heterogeneity, e.g. "
                         "'slow_uplink:frac=0.25,k_scale=0.25'")
    ap.add_argument("--fl-privacy", default=None,
                    help="wire privacy epilogue, e.g. "
                         "'secure_agg+dp:sigma=0.5,clip=1.0'")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "llama-100m":
        if args.smoke:
            ap.error("--smoke needs a registry arch (e.g. --arch smollm-360m)")
        cfg = model_100m()
    else:
        cfg = get_config(args.arch, smoke=args.smoke)
    bundle = build_model(cfg)
    n_params = cfg.param_count()
    print(f"model: {cfg.name} ({n_params/1e6:.1f}M params), "
          f"{args.nodes} nodes x Q={args.q}, {args.rounds} rounds "
          f"= {args.rounds*args.q} training steps, "
          f"engine={args.fl_engine}"
          + (f" x {args.model_shards} model shards"
             if args.fl_engine == "sharded_fused" else ""))

    run = FLRunConfig(algorithm="dsgt", q=args.q, topology="ring",
                      n_nodes=args.nodes, batch_per_node=args.batch_per_node,
                      alpha0=args.alpha0, schedule="constant")
    stream = make_fl_token_batches(cfg.vocab_size, args.nodes,
                                   args.batch_per_node, args.seq_len, q=1, seed=0)
    step_batches = ({k: v[0] for k, v in b.items()} for b in stream)

    params0 = bundle.init_fn(jax.random.key(0))
    engine_arg = args.fl_engine
    mesh = None
    if args.fl_engine == "sharded_fused":
        stacked = stack_for_nodes(params0, args.nodes)
        engine_arg, mesh = build_sharded_engine(args, stacked)
        params0 = stacked
        knobs = dict(engine=engine_arg)
    else:
        knobs = dict(engine=engine_arg, topk=args.topk,
                     round_schedule=args.fl_schedule,
                     topology_program=args.fl_topology_program,
                     node_program=args.fl_node_program,
                     privacy=args.fl_privacy)
        if args.fl_engine in ("flat", "fused"):
            knobs["scale_chunk"] = args.scale_chunk

    t0 = time.time()
    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        result = train_decentralized(
            bundle.loss_fn, params0, run,
            step_batches, rounds=args.rounds, log_every=2, **knobs,
        )
    dt = time.time() - t0
    rows = result.history.rows()
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/train_100m_metrics.csv", "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=sorted(rows[0]))
        w.writeheader()
        w.writerows(rows)
    eng = engine_arg if isinstance(engine_arg, str) else engine_arg.name
    save_fl_state(args.ckpt, result.state, extra={"arch": cfg.name},
                  engine=None if isinstance(engine_arg, str) else engine_arg)
    print(f"\nloss {rows[0]['loss']:.3f} -> {rows[-1]['loss']:.3f} "
          f"({int(rows[-1]['iteration'])} steps, {dt/60:.1f} min, "
          f"{dt/max(1,int(rows[-1]['iteration'])):.1f}s/step, engine={eng})")
    print(f"metrics -> experiments/train_100m_metrics.csv; ckpt -> {args.ckpt}")


def _nullcontext():
    import contextlib

    return contextlib.nullcontext()


if __name__ == "__main__":
    main()
