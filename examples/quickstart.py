"""Quickstart: decentralized federated training in ~40 lines.

Trains a reduced llama-family model across 8 simulated FL nodes on a ring
graph with FD-DSGT (the paper's Algorithm 1), then serves the consensus
model. Runs on CPU in ~2 minutes.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import numpy as np

from repro.configs import FLRunConfig, get_config
from repro.data.tokens import make_fl_token_batches
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import ServeEngine
from repro.training.trainer import train_decentralized

enable_compile_cache()

# 1. pick an architecture (any of the 10 assigned ids works)
cfg = get_config("tinyllama-1.1b", smoke=True)
bundle = build_model(cfg)

# 2. decentralized FL run config: 8 hospitals on a ring, Q=4 local steps
run = FLRunConfig(algorithm="dsgt", q=4, topology="ring", n_nodes=8,
                  batch_per_node=2, alpha0=0.5, schedule="constant")

# 3. per-node non-IID token streams
stream = make_fl_token_batches(cfg.vocab_size, run.n_nodes, run.batch_per_node,
                               seq_len=64, q=1, seed=0)
step_batches = ({k: v[0] for k, v in b.items()} for b in stream)

# 4. train: Q local steps per node, then one ring-gossip round
result = train_decentralized(
    bundle.loss_fn, bundle.init_fn(jax.random.key(0)), run,
    step_batches, rounds=25, log_every=5,
)
h = result.history
print(f"\nloss {h.rows()[0]['loss']:.3f} -> {h.last()['loss']:.3f} "
      f"in {int(h.last()['comm_rounds'])} comm rounds "
      f"({int(h.last()['iteration'])} iterations)")
print(f"consensus error: {h.last()['consensus_err']:.2e}")

# 5. serve the consensus model
engine = ServeEngine(bundle, result.consensus, max_seq=96, batch=2)
prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
out = engine.generate(prompts, max_new_tokens=8, temperature=0.0)
print("generated:", out.tokens[:, 8:].tolist())
