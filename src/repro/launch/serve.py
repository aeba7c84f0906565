"""Serving launcher: batched generation from an arch's model or a
published consensus snapshot.

The model is built at the arch's published widths unless ``--smoke``
picks its reduced config. Weights come from ``--snapshot DIR`` (the
newest consensus snapshot there, ``training/snapshot.write_snapshot``)
or are drawn from ``--seed``.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \\
      --smoke --batch 4 --prompt-len 16 --max-new 32 --temperature 0.7
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import GenerationResult, ServeEngine


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced config (default: the "
                         "published widths)")
    ap.add_argument("--snapshot", default=None,
                    help="directory of published consensus snapshots; "
                         "serves the newest (default: seeded weights)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def serve(args: argparse.Namespace) -> Tuple[Dict, GenerationResult]:
    """Answer one batch of seeded prompts. Returns the JSON summary and
    the :class:`GenerationResult`."""
    cfg = get_config(args.arch, smoke=args.smoke)
    bundle = build_model(cfg)
    if args.snapshot:
        from repro.training.snapshot import load_snapshot

        snap = load_snapshot(args.snapshot, template=bundle.param_shapes())
        engine = ServeEngine.from_snapshot(bundle, snap, max_seq=args.max_seq,
                                           batch=args.batch)
    else:
        params = bundle.init_fn(jax.random.key(args.seed))
        engine = ServeEngine(bundle, params, max_seq=args.max_seq,
                             batch=args.batch)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)).astype(np.int32)
    frames = None
    if cfg.family == "audio":
        frames = rng.normal(size=(args.batch, cfg.encoder.seq_len, cfg.encoder.d_model)).astype(np.float32)

    t0 = time.time()
    out = engine.generate(
        prompts, max_new_tokens=args.max_new, temperature=args.temperature,
        seed=args.seed, frames=frames,
    )
    dt = time.time() - t0
    summary = {
        "arch": cfg.name,
        "snapshot_round": engine.snapshot_round,
        "batch": args.batch,
        "steps": out.steps,
        "tokens_generated": int(args.batch * args.max_new),
        "wall_s": round(dt, 2),
        "tok_per_s": round(args.batch * args.max_new / dt, 1),
        "sample_continuation": out.tokens[0, args.prompt_len:args.prompt_len + 16].tolist(),
    }
    return summary, out


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    summary, _ = serve(args)
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
