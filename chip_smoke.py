"""Chip smoke check: the decentralized round and the serving of its
consensus, run once on a TPU through the repo's own entry points.

  python chip_smoke.py            # one chip: phases A, B, C
  python chip_smoke.py --chips 4  # four chips: phase D only

A. The paper's cell: the EHR MLP on the 20-hospital graph, FD-DSGT with
   Q=10, every site on one chip (``FusedEngine.simulated``, the round
   megakernel). The compiled round must hold a Pallas TPU kernel, its
   consensus must match the jnp oracle engine run on the same batches,
   and the consensus model must beat chance on balanced accuracy.
B. SmolLM-360M at its published widths through ``launch/train.py``: two
   sites on a ring, DSGD, Q=2, bf16 parameter storage (an f32 state for
   two sites does not fit one chip's HBM next to the round's temporaries).
   Losses and consensus errors must be finite, and the round's program
   must hold the wire-stage kernel.
C. Serving: phase B's consensus is published as a snapshot
   (``training/snapshot.py``), the training state is freed, and
   ``launch/serve.py`` loads the snapshot and answers 4 requests
   (128-token prompts, 16 new tokens) at the same widths.
D. (``--chips 4``) Four sites on a ring, one site per chip, on
   ``ShardedFusedEngine`` (the Pallas wire stage, payloads by ppermute):
   (i) the EHR MLP compared with ``FusedEngine`` on the dense-equivalent
   W over the same batches; (ii) SmolLM-360M, DSGD, 2 rounds.

Every check prints a line. Compile and step times are wall times of this
smoke run, not benchmark metrics. Any failed check exits non-zero; only a
run whose checks all pass ends with the JSON line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script refuses to run unless JAX's first device is a TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

#: FD-DSGT step size of the EHR cells: constant, as in the repo's EHR
#: training tests (a 20-round smoke run is too short for the paper's
#: 0.02/sqrt(r) decay to leave the majority-class plateau)
EHR_ALPHA = 0.05
#: |consensus(pallas) - consensus(reference)| bound of the EHR cells, as
#: a fraction of max |consensus|. Both runs share every local step; they
#: differ only where the kernel's f32 elementwise arithmetic rounds
#: differently from XLA's, which can move one int8 level of one payload
#: entry (1/127 of its chunk's max) before error feedback returns it.
#: A wrong W row, scale or residual shows up at the size of the
#: parameters themselves.
EHR_REL_TOL = 2e-3


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def wall(label: str, seconds: float) -> None:
    print(f"  smoke wall time, {label}: {seconds:.2f} s", flush=True)


def peak_bytes(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"  {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
              flush=True)


def _tree_max_abs_diff(a, b):
    import jax
    import numpy as np

    diffs = jax.tree_util.tree_map(
        lambda x, y: float(np.max(np.abs(np.asarray(x, np.float64)
                                         - np.asarray(y, np.float64)))),
        a, b)
    return max(jax.tree_util.tree_leaves(diffs))


def _tree_max_abs(a):
    import jax
    import numpy as np

    return max(float(np.max(np.abs(np.asarray(x))))
               for x in jax.tree_util.tree_leaves(a))


def _consensus(engine, params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda p: jnp.mean(p, axis=0),
                                  engine.params_view(params))


def _run_compiled(name, fn, state, batches):
    """AOT-compile ``fn`` on the first batch, then run every batch.
    Returns (final state, last metrics, compiled HLO text)."""
    import jax

    t0 = time.perf_counter()
    compiled = fn.lower(state, batches[0]).compile()
    wall(f"{name} compile", time.perf_counter() - t0)
    m = None
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        state, m = compiled(state, b)
        jax.block_until_ready(state)
        if i in (0, len(batches) - 1):
            wall(f"{name} round {i + 1}", time.perf_counter() - t0)
    return state, m, compiled.as_text()


def _ehr_batches(n_sites: int, q: int, rounds: int, seed: int):
    import jax
    import numpy as np

    from repro.data.ehr import generate_ehr_cohort, make_node_batcher

    data = generate_ehr_cohort(seed=seed, n_hospitals=n_sites)
    batcher = make_node_batcher(data, m=20, seed=seed + 1)
    batches = []
    for _ in range(rounds):
        steps = [next(batcher) for _ in range(q)]
        batches.append(jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                              *steps))
    return data, batches


def _check_consensus_close(name, got, ref):
    err = _tree_max_abs_diff(got, ref)
    scale = _tree_max_abs(ref)
    check(err <= EHR_REL_TOL * scale,
          f"{name}: max |consensus - reference| = {err:.3e} <= "
          f"{EHR_REL_TOL:g} x max |consensus| ({scale:.3e})")


def phase_a(seed: int = 0, rounds: int = 20) -> None:
    """The paper's cell on one chip, Pallas megakernel vs jnp oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.ehr_mlp import CLASS_WEIGHT, class_weights
    from repro.core import (FLConfig, FusedEngine, init_fl_state,
                            make_fl_round, mixing_matrix)
    from repro.core.schedules import constant
    from repro.models.mlp import make_mlp_loss, mlp_balanced_accuracy, mlp_init
    from repro.training.trainer import stack_for_nodes

    print("phase A: EHR MLP, hospital20, FD-DSGT Q=10, FusedEngine "
          "(20 sites on one chip)", flush=True)
    n, q = 20, 10
    data, batches = _ehr_batches(n, q, rounds, seed)
    params = stack_for_nodes(mlp_init(jax.random.key(seed)), n)
    cfg = FLConfig(algorithm="dsgt", q=q, n_nodes=n)
    loss_fn = make_mlp_loss(class_weights(CLASS_WEIGHT))
    w = mixing_matrix("hospital20", n)
    consensus = {}
    for impl in ("pallas", "jnp"):
        engine, flat = FusedEngine.simulated(w, params, scale_chunk=512,
                                             impl=impl)
        state = init_fl_state(cfg, flat, engine=engine)
        fn = jax.jit(make_fl_round(loss_fn, None, constant(EHR_ALPHA), cfg,
                                   engine=engine))
        state, m, hlo = _run_compiled(f"A {impl}", fn, state, batches)
        has_kernel = "tpu_custom_call" in hlo
        if impl == "pallas":
            check(has_kernel, "compiled round holds a Pallas TPU kernel "
                  "(tpu_custom_call)")
        else:
            check(not has_kernel, "jnp reference round holds no Pallas "
                  "kernel")
        check(bool(np.isfinite(float(m["loss"]))),
              f"{impl}: final loss {float(m['loss']):.4f} is finite")
        consensus[impl] = _consensus(engine, state.params)
    _check_consensus_close("A", consensus["pallas"], consensus["jnp"])
    xall = jnp.asarray(np.concatenate(data.features))
    yall = jnp.asarray(np.concatenate(data.labels))
    bal = float(mlp_balanced_accuracy(consensus["pallas"], xall, yall))
    check(bal > 0.5, f"consensus balanced accuracy {bal:.4f} > 0.5")
    peak_bytes(jax.devices()[:1])


def phase_b_c(seed: int = 0) -> None:
    """SmolLM-360M through launch/train.py, then its consensus served
    through launch/serve.py from a published snapshot."""
    import jax
    import numpy as np

    from repro.launch import serve as serve_launcher
    from repro.launch import train as train_launcher
    from repro.training.snapshot import write_snapshot

    print("phase B: smollm-360m (published widths) via launch/train.py, "
          "2 sites, ring, DSGD Q=2, bf16 storage", flush=True)
    rounds, q, nodes, batch, seq = 3, 2, 2, 2, 128
    targs = train_launcher.build_parser().parse_args([
        "--arch", "smollm-360m", "--nodes", str(nodes), "--topology", "ring",
        "--algorithm", "dsgd", "--q", str(q), "--rounds", str(rounds),
        "--fl-engine", "fused", "--storage-dtype", "bfloat16",
        "--batch-per-node", str(batch), "--seq-len", str(seq),
        "--seed", str(seed), "--log-every", "1",
    ])
    cfg = train_launcher.resolve_config(targs)
    check(cfg.name == "smollm-360m" and cfg.n_layers == 32
          and cfg.d_model == 960, f"config is the published {cfg.name} "
          f"({cfg.n_layers} layers, d_model {cfg.d_model})")
    summary, result = train_launcher.train(targs)
    rows = result.history.rows()
    wall("B round 1 (compile included)", rows[0]["wall_s"])
    wall(f"B rounds 2-{rounds} each",
         (rows[-1]["wall_s"] - rows[0]["wall_s"]) / (rounds - 1))
    losses = [r["loss"] for r in rows]
    cons = [r["consensus_err"] for r in rows]
    check(bool(np.isfinite(losses).all()), f"losses finite: {losses}")
    check(bool(np.isfinite(cons).all()), f"consensus errors finite: {cons}")
    state, engine = result.state, result.engine
    check(state.params.dtype == np.dtype("bfloat16")
          and state.params.shape == (nodes, engine.layout.total),
          f"state is the bf16 ({nodes}, {engine.layout.total}) flat buffer")
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    batch_sds = {"tokens": jax.ShapeDtypeStruct((q, nodes, batch, seq + 1),
                                                np.int32)}
    hlo = result.round_fn.lower(sds, batch_sds).as_text()
    check("tpu_custom_call" in hlo, "round program holds the round "
          "megakernel (tpu_custom_call)")
    peak_bytes(jax.devices()[:1])

    print("phase C: publish the consensus, free the training state, serve "
          "4 requests from the snapshot via launch/serve.py", flush=True)
    with tempfile.TemporaryDirectory() as snap_dir:
        t0 = time.perf_counter()
        write_snapshot(snap_dir, state.params, engine.layout,
                       round_frontier=rounds, engine=engine,
                       step=int(state.step))
        wall("C snapshot publish", time.perf_counter() - t0)
        del state, result, sds
        gc.collect()
        prompt, new = 128, 16
        sargs = serve_launcher.build_parser().parse_args([
            "--arch", "smollm-360m", "--snapshot", snap_dir,
            "--batch", "4", "--prompt-len", str(prompt), "--max-new",
            str(new), "--max-seq", "256", "--seed", str(seed),
        ])
        t0 = time.perf_counter()
        ssum, out = serve_launcher.serve(sargs)
        wall("C serve (compile included)", time.perf_counter() - t0)
    check(ssum["snapshot_round"] == rounds,
          f"served the round-{rounds} snapshot")
    check(out.tokens.shape == (4, prompt + new),
          f"4 requests answered, {new} new tokens each")
    gen = out.tokens[:, prompt:]
    check(bool(((gen >= 0) & (gen < cfg.vocab_size)).all()),
          f"every generated token is in the vocabulary [0, {cfg.vocab_size})")
    print(f"  sample continuation: {gen[0].tolist()}", flush=True)
    peak_bytes(jax.devices()[:1])


def _sharded_state(cfg, engine, mesh, params_buf):
    """Initial FL state built directly in its per-site shardings (the
    zero comm buffers never exist whole on one chip)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import FLState, init_fl_state

    def ns(spec):
        return NamedSharding(mesh, spec)

    row = ns(engine.params_spec())
    dsgt = cfg.algorithm == "dsgt"
    out = FLState(ns(P()), row, row if dsgt else None, row if dsgt else None,
                  {k: ns(s) for k, s in engine.comm_state_specs(cfg).items()})
    return jax.jit(lambda p: init_fl_state(cfg, p, engine=engine),
                   out_shardings=out)(params_buf)


def _check_one_row_per_chip(name, buf, devices) -> None:
    shards = buf.addressable_shards
    rows = sorted(s.index[0].start for s in shards)
    check(sorted(s.device.id for s in shards) == sorted(d.id for d in devices)
          and rows == list(range(len(devices)))
          and all(s.data.shape[0] == 1 for s in shards),
          f"{name}: each chip holds its own site row")


def phase_d_ehr(devices, seed: int = 0, rounds: int = 20) -> None:
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from repro.configs.ehr_mlp import CLASS_WEIGHT, class_weights
    from repro.core import (FLConfig, FusedEngine, ShardedFusedEngine,
                            init_fl_state, make_fl_round, pack)
    from repro.core.schedules import constant
    from repro.models.mlp import make_mlp_loss, mlp_init
    from repro.training.trainer import stack_for_nodes

    n, q = len(devices), 10
    print(f"phase D(i): EHR MLP over {n} sites, one per chip, FD-DSGT "
          f"Q={q}, ShardedFusedEngine vs FusedEngine", flush=True)
    mesh = Mesh(np.array(devices), ("data",))
    _, batches = _ehr_batches(n, q, rounds, seed)
    params = stack_for_nodes(mlp_init(jax.random.key(seed)), n)
    cfg = FLConfig(algorithm="dsgt", q=q, n_nodes=n)
    loss_fn = make_mlp_loss(class_weights(CLASS_WEIGHT))
    sched = constant(EHR_ALPHA)
    sh = ShardedFusedEngine.from_mesh(mesh, ("data",), params,
                                      scale_chunk=512, impl="pallas")
    flat, layout = pack(params, pad_to=512)
    fe = FusedEngine(sh.dense_equivalent(), layout, scale_chunk=512,
                     impl="pallas")
    st_f = init_fl_state(cfg, flat, engine=fe)
    fn_f = jax.jit(make_fl_round(loss_fn, None, sched, cfg, engine=fe))
    st_f, _, _ = _run_compiled("D(i) fused", fn_f, st_f, batches)
    with mesh:
        st_s = init_fl_state(
            cfg, jax.device_put(flat, NamedSharding(mesh, P("data", None))),
            engine=sh)
        _check_one_row_per_chip("D(i) initial params", st_s.params, devices)
        fn_s = jax.jit(make_fl_round(loss_fn, None, sched, cfg, engine=sh))
        st_s, m, hlo = _run_compiled("D(i) sharded", fn_s, st_s, batches)
    _check_one_row_per_chip("D(i) final params", st_s.params, devices)
    check("tpu_custom_call" in hlo and "collective-permute" in hlo,
          "sharded round holds the wire-stage kernel and collective-permute")
    check(bool(np.isfinite(float(m["loss"]))),
          f"final loss {float(m['loss']):.4f} is finite")
    _check_consensus_close("D(i)", _consensus(sh, st_s.params),
                           _consensus(fe, st_f.params))


def phase_d_lm(devices, seed: int = 0, rounds: int = 2) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from repro.configs import get_config
    from repro.core import FLConfig, ShardedFusedEngine, make_fl_round, pack
    from repro.core.schedules import constant
    from repro.data.tokens import make_fl_token_batches
    from repro.models import build_model

    n, q, batch, seq = len(devices), 2, 2, 128
    print(f"phase D(ii): smollm-360m (published widths), {n} sites on a "
          f"ring, one per chip, DSGD Q={q}, bf16 storage, "
          "ShardedFusedEngine", flush=True)
    cfg_m = get_config("smollm-360m")
    bundle = build_model(cfg_m)
    mesh = Mesh(np.array(devices), ("data",))
    stacked_sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype),
        bundle.param_shapes())
    engine = ShardedFusedEngine.from_mesh(
        mesh, ("data",), stacked_sds, scale_chunk=512, impl="pallas",
        storage_dtype=jnp.bfloat16)
    # every site starts from the same seeded init: pack one row, then
    # place a copy on each chip
    params = bundle.init_fn(jax.random.key(seed))
    row, layout = pack(jax.tree_util.tree_map(lambda l: l[None], params),
                       pad_to=512, buffer_dtype=jnp.bfloat16)
    del params
    check(layout.total == engine.layout.total,
          f"one site row is {layout.total} columns "
          f"({layout.used} parameters)")
    buf = jax.make_array_from_single_device_arrays(
        (n, layout.total), NamedSharding(mesh, engine.params_spec()),
        [jax.device_put(row, d) for d in devices])
    del row
    cfg = FLConfig(algorithm="dsgd", q=q, n_nodes=n)
    state = _sharded_state(cfg, engine, mesh, buf)
    del buf
    _check_one_row_per_chip("D(ii) initial params", state.params, devices)
    stream = make_fl_token_batches(cfg_m.vocab_size, n, batch, seq, q=q,
                                   seed=seed)
    tok_sharding = NamedSharding(mesh, P(None, "data"))
    batches = [{"tokens": jax.device_put(next(stream)["tokens"],
                                         tok_sharding)}
               for _ in range(rounds)]
    with mesh:
        fn = jax.jit(make_fl_round(bundle.loss_fn, None, constant(0.5), cfg,
                                   engine=engine), donate_argnums=(0,))
        state, m, hlo = _run_compiled("D(ii)", fn, state, batches)
    check("tpu_custom_call" in hlo, "round holds the wire-stage kernel "
          "(tpu_custom_call)")
    check("collective-permute" in hlo, "payloads move by collective-permute")
    _check_one_row_per_chip("D(ii) final params", state.params, devices)
    loss, cons = float(m["loss"]), float(m["consensus_err"])
    check(bool(np.isfinite(loss)) and bool(np.isfinite(cons)),
          f"loss {loss:.4f} and consensus error {cons:.3e} finite")
    peak_bytes(devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: phase D only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    kind = devices[0].device_kind
    print(f"device_kind={kind} platform={platform} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_d_ehr(devices[:4], seed=args.seed)
            phase_d_lm(devices[:4], seed=args.seed)
        else:
            phase_a(seed=args.seed)
            phase_b_c(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    wall("whole run", time.perf_counter() - t0)
    print(f"compile cache: {cache_events['hits']} hits, "
          f"{cache_events['misses']} misses", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
