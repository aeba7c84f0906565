"""Training launcher: decentralized FL training of any registered arch.

Every site is a row of the node-stacked state on the host's default
device (``train_decentralized`` with the selected round engine), at the
arch's published widths unless ``--smoke`` picks the reduced config of
the same family. ``--storage-dtype bfloat16`` halves the stored
parameter buffer of the fused engine when a published-width state would
not fit otherwise. Multi-device meshes (one site per chip, ppermute
gossip) are built by ``examples/train_100m.py --fl-engine sharded_fused``
and ``chip_smoke.py --chips 4``; ``launch/dryrun.py`` lowers the same
round for a production mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --rounds 20 --q 4 --algorithm dsgt --nodes 8
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --nodes 2 --algorithm dsgd --q 2 --rounds 3 --fl-engine fused \
      --storage-dtype bfloat16
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import jax

from repro.configs import FLRunConfig, get_config
from repro.configs.base import ModelConfig
from repro.core.dynamics import program_names
from repro.core.engine import engine_names, schedule_names
from repro.core.heterogeneity import node_program_names
from repro.data.tokens import make_fl_token_batches
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.training.checkpoint import save_fl_state
from repro.training.trainer import TrainResult, train_decentralized


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="train the arch's reduced config (default: the "
                         "published widths)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--algorithm", default="dsgt", choices=("dsgd", "dsgt"))
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--batch-per-node", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--alpha0", type=float, default=0.5)
    ap.add_argument("--fl-engine", default="tree", choices=engine_names(),
                    help="round engine, resolved through the GossipEngine "
                         "registry (sharded_fused needs a mesh -- use "
                         "launch/dryrun.py for that path)")
    ap.add_argument("--scale-chunk", type=int, default=512,
                    help="fused engines: int8 scale block width")
    ap.add_argument("--topk", type=int, default=None,
                    help="fused engines: k largest payload columns per "
                         "scale chunk on the wire")
    ap.add_argument("--fl-schedule", default="sequential",
                    help="round time layout (RoundSchedule registry: "
                         f"{', '.join(schedule_names())}): pipelined "
                         "overlaps the collective with the next round's "
                         "local steps, mixing one-round stale; spec "
                         "syntax name:k=v e.g. 'bounded_staleness:k=3' "
                         "keeps k payloads in flight (fused engines only)")
    ap.add_argument("--fl-staleness-depth", type=int, default=None,
                    help="sugar for --fl-schedule bounded_staleness:k=K "
                         "(0 = sequential); mutually exclusive with "
                         "--fl-schedule")
    ap.add_argument("--storage-dtype", default=None,
                    help="flat engine: buffer storage dtype (e.g. "
                         "bfloat16); fp32 stays in the mix accumulator")
    ap.add_argument("--fl-topology-program", default=None,
                    help="per-round graph dynamics (TopologyProgram "
                         f"registry: {', '.join(program_names())}); spec "
                         "syntax name:k=v,... e.g. "
                         "'edge_failure:p=0.2,seed=0' -- flat/fused "
                         "engines; metrics gain edge_fraction")
    ap.add_argument("--fl-node-program", default=None,
                    help="per-node heterogeneity (NodeProgram registry: "
                         f"{', '.join(node_program_names())}); spec syntax "
                         "name:k=v,... e.g. "
                         "'stragglers:frac=0.25,rate=0.5' gates local-step "
                         "budgets and payload delivery per round; metrics "
                         "gain payload_fraction / compute_fraction")
    ap.add_argument("--fl-privacy", default=None,
                    help="wire privacy epilogue (PrivacySpec): "
                         "'+'-separated tokens, e.g. 'secure_agg' "
                         "(pairwise antisymmetric masks -- no single "
                         "neighbor payload readable, cancels exactly "
                         "under the symmetric mix), "
                         "'dp:sigma=0.5,clip=1.0' (per-node clip + "
                         "Gaussian noise riding the EF residual; metrics "
                         "gain dp_epsilon), or both joined with '+' -- "
                         "fused engines; tree rejects")
    ap.add_argument("--fl-scope", default=None,
                    help="federation scope (FederationScope registry: "
                         "which flat-buffer columns gossip touches): "
                         "'full' (default), 'backbone' (share all but "
                         "the classifier head -- per-node personalized "
                         "heads stay bit-untouched, wire shrinks to the "
                         "shared slice), 'backbone:private=PAT', "
                         "'ranges:a-b,c-d', or 'layerwise:freq=R' (head "
                         "joins the mix every R rounds; fused engine "
                         "only) -- fused/sharded_fused; tree/flat "
                         "reject")
    ap.add_argument("--fl-robust-alpha", action="store_true",
                    help="shrink the step-size schedule by the "
                         "staleness/churn controller "
                         "(robust_alpha_scale(uptime, k))")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=5)
    return ap


def resolve_config(args: argparse.Namespace) -> ModelConfig:
    """The arch's published config, or its reduced one under --smoke."""
    return get_config(args.arch, smoke=args.smoke)


def train(args: argparse.Namespace) -> Tuple[Dict, TrainResult]:
    """Run the decentralized training the arguments describe. Returns the
    JSON summary and the :class:`TrainResult` (final state, engine, the
    jitted round)."""
    cfg = resolve_config(args)
    bundle = build_model(cfg)
    run = FLRunConfig(
        algorithm=args.algorithm,
        q=args.q,
        topology=args.topology,
        n_nodes=args.nodes,
        batch_per_node=args.batch_per_node,
        alpha0=args.alpha0,
        seed=args.seed,
    )

    extras: Dict[str, tuple] = {}
    if cfg.family == "vlm":
        extras["prefix_embeds"] = (cfg.frontend_seq, cfg.d_model)
    if cfg.family == "audio":
        extras["frames"] = (cfg.encoder.seq_len, cfg.encoder.d_model)

    fl_rounds = make_fl_token_batches(
        cfg.vocab_size, args.nodes, args.batch_per_node, args.seq_len,
        q=1, seed=args.seed, extras=extras or None,
    )

    def step_batches():
        while True:
            b = next(fl_rounds)
            yield {k: v[0] for k, v in b.items()}  # (nodes, pnb, ...)

    t0 = time.time()
    fl_schedule = args.fl_schedule
    if args.fl_staleness_depth is not None:
        if fl_schedule != "sequential":
            raise SystemExit(
                "--fl-staleness-depth is sugar for --fl-schedule "
                "bounded_staleness:k=K; pass one or the other"
            )
        fl_schedule = None  # trainer derives it from staleness_depth
    # the initial params are handed over, not kept: the trainer frees the
    # tree once the state holds them
    result = train_decentralized(
        bundle.loss_fn, bundle.init_fn(jax.random.key(args.seed)), run,
        step_batches(), rounds=args.rounds,
        log_every=args.log_every, engine=args.fl_engine,
        scale_chunk=args.scale_chunk, topk=args.topk,
        round_schedule=fl_schedule, storage_dtype=args.storage_dtype,
        topology_program=args.fl_topology_program,
        node_program=args.fl_node_program,
        staleness_depth=args.fl_staleness_depth,
        robust_alpha=args.fl_robust_alpha,
        privacy=args.fl_privacy,
        scope=args.fl_scope,
    )
    hist = result.history
    first, last = hist.rows()[0], hist.last()
    summary = {
        "arch": cfg.name,
        "fl_engine": args.fl_engine,
        "fl_schedule": result.engine.round_schedule.spec(),
        "fl_topology_program": args.fl_topology_program,
        "fl_node_program": args.fl_node_program,
        "fl_privacy": result.engine.privacy.spec(),
        "fl_scope": result.engine.scope.spec(),
        "algorithm": args.algorithm,
        "q": args.q,
        "rounds": args.rounds,
        "iterations": int(last["iteration"]),
        "loss_first": first["loss"],
        "loss_last": last["loss"],
        "consensus_err_last": last["consensus_err"],
        "dp_epsilon": last.get("dp_epsilon"),
        "wall_s": round(time.time() - t0, 1),
    }
    if args.checkpoint:
        save_fl_state(args.checkpoint, result.state, extra={"arch": cfg.name},
                      engine=result.engine)
        summary["checkpoint"] = args.checkpoint
    return summary, result


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    summary, _ = train(args)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
