"""Two-axis (gossip_node, model_shard) round micro-benchmark helper.

The sharded engine's round runs under shard_map on a real device mesh,
and XLA locks the host device count at first jax initialization -- so a
process that already imported jax (gossip_bench, thm1_speedup) cannot
re-mesh itself. Each (nodes, shards) cell therefore runs in a CHILD
process: ``python -m benchmarks.two_axis --nodes N --shards S ...``
forces ``N * S`` host devices before importing jax, times the full
fused round (jnp oracle; the Pallas kernel is a TPU story) on the
``(data, model)`` mesh, and prints one JSON record. The parent-side
helpers compose those records into BENCH_gossip.json rows:

  * ``wire_bytes_per_shard_*`` -- deterministic per-shard collective
    operand bytes (``packing.flat_wire_bytes_per_shard``); the guarded
    columns. Per-shard bytes x shards == the single-axis wire bytes:
    sharding tiles the payload, it never grows it.
  * ``us_n{N}_s{S}`` -- measured step time vs node-count x shard-count
    (unguarded absolutes; the interleaving protection of the in-process
    rows does not apply across processes).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (nodes, shards) cells: same device budget (8 host devices), the
# shard axis traded against the node axis. The s=1 cell is the
# single-axis reference the equivalence tests pin to 1e-5.
CELLS: Tuple[Tuple[int, int], ...] = ((8, 1), (4, 2), (2, 4))


def run_cell(nodes: int, shards: int, *, total: int = 8192,
             chunk: int = 256, topk: int = 32, algorithm: str = "dsgt",
             q: int = 2, rounds: int = 20, trials: int = 5,
             timeout: int = 1200) -> Dict:
    """Run one (nodes, shards) cell in a child process; return its record."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    # forced host devices are a CPU rehearsal: the child stays off any
    # accelerator (the parent may already hold it)
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "benchmarks.two_axis",
           "--nodes", str(nodes), "--shards", str(shards),
           "--total", str(total), "--chunk", str(chunk),
           "--topk", str(topk), "--algorithm", algorithm,
           "--q", str(q), "--rounds", str(rounds), "--trials", str(trials)]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"two_axis cell n={nodes} s={shards} failed:\n"
            + proc.stderr[-4000:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def two_axis_row(smoke: bool = False) -> Dict:
    """The BENCH_gossip.json row: one record spanning all cells."""
    if smoke:
        kw = dict(total=1024, chunk=64, topk=8, rounds=5, trials=3)
    else:
        kw = dict(total=8192, chunk=256, topk=32, rounds=20, trials=5)
    row: Dict = {
        "name": "two_axis_round_dsgt",
        "total_params": kw["total"],
        "scale_chunk": kw["chunk"],
        "topk": kw["topk"],
        "q": 2,
        "model_shards": max(s for _, s in CELLS),
        "note": "full sharded_fused DSGT rounds on a (data, model) host-"
                "device mesh, one subprocess per (nodes, shards) cell; "
                "wire_bytes_per_shard_* are the deterministic per-shard "
                "collective operand bytes (guarded) -- per-shard bytes x "
                "shards == the single-axis wire bytes, so sharding tiles "
                "the payload without growing it. us_* absolutes are "
                "cross-process and unguarded.",
    }
    for nodes, shards in CELLS:
        rec = run_cell(nodes, shards, algorithm="dsgt", **kw)
        tag = f"n{nodes}_s{shards}"
        row[f"us_{tag}"] = rec["us_per_round"]
        row[f"wire_bytes_per_shard_{tag}"] = rec["wire_bytes_per_shard"]
        row[f"wire_bytes_per_round_{tag}"] = rec["wire_bytes_per_round"]
        assert abs(rec["wire_bytes_per_shard"] * shards
                   - rec["wire_bytes_per_round"]) < 1e-6, rec
    return row


def _child_main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, required=True)
    ap.add_argument("--shards", type=int, required=True)
    ap.add_argument("--total", type=int, default=8192)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--topk", type=int, default=32)
    ap.add_argument("--algorithm", default="dsgt")
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.nodes * args.shards} "
        + os.environ.get("XLA_FLAGS", "")
    )
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.core import (
        FLConfig,
        ShardedFusedEngine,
        init_fl_state,
        make_fl_round,
        pack,
    )
    from repro.core.schedules import constant

    n, s = args.nodes, args.shards
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(n, args.total)), jnp.float32)}
    batches = {"t": jnp.asarray(rng.normal(size=(args.q, n)), jnp.float32)}

    def loss(p, batch):
        return jnp.sum((p["w"] - batch["t"]) ** 2) / args.total

    mesh = jax.make_mesh((n, s), ("data", "model"))
    engine = ShardedFusedEngine.from_mesh(
        mesh, ("data",), params, scale_chunk=args.chunk, topk=args.topk,
        impl="pallas", model_axis="model" if s > 1 else None)
    cfg = FLConfig(algorithm=args.algorithm, q=args.q, n_nodes=n)
    flat, _ = pack(params, pad_to=args.chunk * s)
    with mesh:
        rf = jax.jit(make_fl_round(loss, None, constant(0.01), cfg,
                                   engine=engine))
        st = init_fl_state(cfg, jax.device_put(
            flat, NamedSharding(mesh, engine.params_spec())), engine=engine)
        st, _ = rf(st, batches)  # compile + warm
        jax.block_until_ready(st.params)
        samples = []
        for _ in range(args.trials):
            t0 = time.perf_counter()
            for _ in range(args.rounds):
                st, _ = rf(st, batches)
            jax.block_until_ready(st.params)
            samples.append((time.perf_counter() - t0) / args.rounds * 1e6)

    print(json.dumps({
        "nodes": n,
        "shards": int(engine.model_shards),
        "total_params": int(engine.layout.total),
        "shard_width": int(engine.layout.shard_width),
        "us_per_round": float(np.median(samples)),
        "wire_bytes_per_shard": float(engine.wire_bytes_per_shard(cfg)),
        "wire_bytes_per_round": float(engine.wire_bytes(cfg)),
    }))


if __name__ == "__main__":
    _child_main()
