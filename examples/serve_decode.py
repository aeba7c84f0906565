"""Batched serving example: prefill + step-decode across architectures,
including the SSM (RWKV-6) whose decode state is O(1) in context length and
the sliding-window mode used for long_500k decoding.

  PYTHONPATH=src python examples/serve_decode.py
"""

import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import ServeEngine


def demo(arch: str, sliding: bool = False, batch: int = 2, max_new: int = 12) -> None:
    cfg = get_config(arch, smoke=True)
    bundle = build_model(cfg)
    params = bundle.init_fn(jax.random.key(0))
    engine = ServeEngine(bundle, params, max_seq=64, batch=batch,
                         sliding_override=sliding)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (batch, 8)).astype(np.int32)
    frames = None
    if cfg.family == "audio":
        frames = rng.normal(size=(batch, cfg.encoder.seq_len, cfg.encoder.d_model)).astype(np.float32)
    t0 = time.time()
    out = engine.generate(prompts, max_new_tokens=max_new, temperature=0.8,
                          seed=1, frames=frames)
    dt = time.time() - t0
    mode = " (sliding-window cache)" if sliding else ""
    print(f"{arch:24s}{mode}: {batch}x{max_new} tokens in {dt:5.1f}s "
          f"-> {out.tokens[0, 8:14].tolist()}...")


if __name__ == "__main__":
    enable_compile_cache()
    print("batched decode across model families (reduced configs, CPU):")
    demo("tinyllama-1.1b")                 # dense GQA, contiguous KV cache
    demo("qwen2.5-32b", sliding=True)      # dense, ring-buffer window cache
    demo("rwkv6-7b")                       # SSM: O(1) decode state
    demo("recurrentgemma-2b")              # hybrid RG-LRU + local attention
    demo("dbrx-132b")                      # MoE routing per decoded token
    demo("whisper-medium")                 # enc-dec with cross-attention
