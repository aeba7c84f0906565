"""Serve the consensus WHILE it trains: end-to-end snapshot pipeline.

Two threads over one snapshot directory:

* **trainer** -- decentralized FL (smollm-360m smoke by default, 4-node
  ring, fused flat-buffer engine) advancing the round frontier; every
  ``--publish-every`` rounds it publishes the consensus (one mean over
  the node axis of the flat ``(nodes, total)`` state buffer) as an
  mmap-able snapshot (``repro.training.snapshot.write_snapshot``);

* **server** (main thread) -- waits for the first snapshot, mmap-loads
  it zero-copy into a :class:`~repro.serving.engine.ServeEngine`, then
  replays a deterministic request stream
  (``benchmarks.serve_load.replay``). Between requests it polls
  ``LATEST`` and hot-swaps fresher consensus weights in at decode step
  boundaries -- in-flight batches are never drained, and each request
  reports how many rounds its weights lag the live training frontier
  (the staleness series).

  PYTHONPATH=src python examples/serve_consensus.py
  PYTHONPATH=src python examples/serve_consensus.py --rounds 12 \
      --publish-every 2 --requests 8
"""

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from benchmarks.serve_load import make_requests, replay  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (  # noqa: E402
    FLConfig,
    get_engine,
    init_fl_state,
    make_fl_round,
    mixing_matrix,
)
from repro.core.schedules import inv_sqrt  # noqa: E402
from repro.data.tokens import make_fl_token_batches  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving.engine import ServeEngine  # noqa: E402
from repro.training.snapshot import (  # noqa: E402
    latest_round,
    load_snapshot,
    write_snapshot,
)
from repro.training.trainer import stack_for_nodes  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch-per-node", type=int, default=1)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--alpha0", type=float, default=0.02)
    ap.add_argument("--scale-chunk", type=int, default=512)
    ap.add_argument("--publish-every", type=int, default=2,
                    help="rounds between snapshot publishes")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--serve-batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--snap-dir", default=None,
                    help="snapshot directory (default: a temp dir)")
    ap.add_argument("--out", default="experiments/serve_consensus_metrics.json")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=True)
    bundle = build_model(cfg)
    params0 = bundle.init_fn(jax.random.key(0))
    n = args.nodes
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.2f}M params), "
          f"{n}-node ring x Q={args.q}, {args.rounds} rounds, "
          f"publish every {args.publish_every}")

    # ---- build the decentralized round (fused flat-buffer engine)
    w = mixing_matrix("ring", n)
    stacked = stack_for_nodes(params0, n)
    engine, state0 = get_engine("fused").simulated(
        w, stacked, scale_chunk=args.scale_chunk, impl="jnp")
    fl_cfg = FLConfig(algorithm="dsgt", q=args.q, n_nodes=n)
    round_fn = jax.jit(
        make_fl_round(bundle.loss_fn, None, inv_sqrt(args.alpha0), fl_cfg,
                      engine=engine))
    state = init_fl_state(fl_cfg, state0, engine=engine)
    stream = make_fl_token_batches(cfg.vocab_size, n, args.batch_per_node,
                                   args.seq_len, q=args.q, seed=0)

    snap_dir = args.snap_dir or tempfile.mkdtemp(prefix="serve_consensus_")
    frontier = {"round": 0}
    trainer_err = []

    def trainer():
        nonlocal state
        try:
            for rnd in range(1, args.rounds + 1):
                state, m = round_fn(state, next(stream))
                jax.block_until_ready(state.params)
                frontier["round"] = rnd
                if rnd % args.publish_every == 0 or rnd == args.rounds:
                    # state.params IS the flat (nodes, total) buffer;
                    # write_snapshot takes the node-mean = the consensus
                    write_snapshot(snap_dir, state.params, engine.layout,
                                   round_frontier=rnd, engine=engine,
                                   step=int(state.step))
                    print(f"  [trainer] round {rnd}: loss="
                          f"{float(m['loss']):.3f}, published snapshot")
        except Exception as e:  # surface into the main thread
            trainer_err.append(e)
            raise

    th = threading.Thread(target=trainer, daemon=True)
    th.start()

    # ---- serving side: wait for the first publish, then replay
    while latest_round(snap_dir) is None:
        if trainer_err:
            raise trainer_err[0]
        time.sleep(0.05)
    tmpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params0)
    snap = load_snapshot(snap_dir, template=tmpl)
    eng = ServeEngine.from_snapshot(
        bundle, snap, max_seq=args.prompt_len + args.new_tokens + 8,
        batch=args.serve_batch)
    print(f"  [server] serving from snapshot round {eng.snapshot_round} "
          f"(mmap {snap.header['blob_bytes']/1e6:.1f} MB zero-copy)")

    def refresh():
        newest = latest_round(snap_dir)
        if newest is not None and newest != eng.snapshot_round:
            eng.publish_snapshot(load_snapshot(snap_dir, newest,
                                               template=tmpl))

    requests = make_requests(args.requests, args.serve_batch,
                             args.prompt_len, cfg.vocab_size, seed=1)
    eng.generate(requests[0], max_new_tokens=2, temperature=0.0)  # warm jit
    row = replay(eng, requests, args.new_tokens,
                 frontier_fn=lambda: frontier["round"], refresh_fn=refresh)
    th.join()
    if trainer_err:
        raise trainer_err[0]

    row.update({"name": f"serve_consensus__{cfg.name}",
                "total_params": int(cfg.param_count()), "n_nodes": n,
                "q": args.q, "rounds": args.rounds,
                "publish_every": args.publish_every,
                "final_round_served": int(eng.snapshot_round)})
    print(f"\nserved {row['gen_tokens']} tokens at "
          f"{row['tokens_per_s']:.1f} tok/s; p50="
          f"{row['us_p50_request']/1e3:.1f}ms p99="
          f"{row['us_p99_request']/1e3:.1f}ms; {row['n_swaps']} hot swaps "
          f"(mean pause {row['us_swap_pause_mean']:.1f}us); staleness "
          f"mean={row.get('staleness_mean', 0):.1f} "
          f"max={row.get('staleness_max', 0)} rounds behind frontier "
          f"{frontier['round']}")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(row, f, indent=2)
    print(f"metrics -> {args.out}; snapshots -> {snap_dir}")


if __name__ == "__main__":
    main()
