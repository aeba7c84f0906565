"""The entry points chip_smoke.py drives: the training and serving
launchers' functions, the compile-cache helper, and the interpret-mode
helper that keeps Pallas kernels compiled on a TPU."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as serve_launcher
from repro.launch import train as train_launcher
from repro.launch.cache import CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_then_serve_the_published_consensus(tmp_path):
    """launch/train.py's function runs a smoke arch for 2 rounds on the
    fused engine; its consensus, published as a snapshot, is served by
    launch/serve.py's function (phases B and C of chip_smoke.py at a
    smoke size)."""
    from repro.training.snapshot import write_snapshot

    args = train_launcher.build_parser().parse_args([
        "--arch", "smollm-360m", "--smoke", "--nodes", "2", "--rounds", "2",
        "--q", "2", "--algorithm", "dsgd", "--fl-engine", "fused",
        "--storage-dtype", "bfloat16", "--seq-len", "16", "--log-every", "0",
    ])
    summary, result = train_launcher.train(args)
    assert summary["arch"] == "smollm-360m-smoke"
    assert summary["iterations"] == 4
    assert np.isfinite([summary["loss_first"], summary["loss_last"],
                        summary["consensus_err_last"]]).all()
    assert result.state.params.dtype == np.dtype("bfloat16")

    write_snapshot(str(tmp_path), result.state.params, result.engine.layout,
                   round_frontier=2, engine=result.engine)
    sargs = serve_launcher.build_parser().parse_args([
        "--arch", "smollm-360m", "--smoke", "--snapshot", str(tmp_path),
        "--batch", "2", "--prompt-len", "8", "--max-new", "4",
        "--max-seq", "32",
    ])
    ssum, out = serve_launcher.serve(sargs)
    assert ssum["snapshot_round"] == 2
    assert out.tokens.shape == (2, 12)
    vocab = get_config("smollm-360m", smoke=True).vocab_size
    assert ((out.tokens >= 0) & (out.tokens < vocab)).all()


@pytest.mark.parametrize("smoke", [False, True])
def test_launchers_default_to_published_widths(smoke):
    argv = ["--arch", "smollm-360m"] + (["--smoke"] if smoke else [])
    targs = train_launcher.build_parser().parse_args(argv)
    sargs = serve_launcher.build_parser().parse_args(argv)
    assert targs.smoke is smoke and sargs.smoke is smoke
    cfg = train_launcher.resolve_config(targs)
    assert cfg == get_config("smollm-360m", smoke=smoke)
    if not smoke:
        assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (32, 960, 49152)


def test_train_does_not_consume_callers_stacked_params():
    """The round donates its state; params the caller passes already
    node-stacked stay usable after training."""
    from repro.configs import FLRunConfig
    from repro.training.trainer import train_decentralized

    n = 4
    params = {"w": jax.numpy.ones((n, 3), jax.numpy.float32)}

    def loss(p, batch):
        return jax.numpy.sum((p["w"] - batch["t"]) ** 2)

    def batches():
        while True:
            yield {"t": np.zeros((n, 3), np.float32)}

    run = FLRunConfig(algorithm="dsgd", q=1, topology="ring", n_nodes=n,
                      alpha0=0.1, schedule="constant")
    train_decentralized(loss, params, run, batches(), rounds=2)
    np.testing.assert_array_equal(np.asarray(params["w"]), 1.0)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        assert first == enable_compile_cache() == CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CACHE_DIR == os.path.join(REPO, ".jax_cache")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", "from repro.launch.cache import "
         "enable_compile_cache; print(enable_compile_cache())"],
        env=env, capture_output=True, text=True, timeout=300, check=True,
    ).stdout.strip().splitlines()[-1]
    assert out == CACHE_DIR


@pytest.mark.parametrize("env,backend,expect", [
    (None, "cpu", True),
    ("0", "cpu", False),
    ("1", "cpu", True),
    (None, "tpu", False),
    ("0", "tpu", False),
    ("1", "tpu", RuntimeError),
])
def test_pallas_interpret_never_on_tpu(monkeypatch, env, backend, expect):
    from repro.kernels import pallas_interpret

    if env is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", env)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="TPU backend"):
            pallas_interpret()
    else:
        assert pallas_interpret() is expect


def test_topk_wire_refused_where_kernels_compile(monkeypatch):
    """The Pallas top-k wire has no Mosaic lowering: on a TPU backend the
    engine refuses it instead of substituting another wire."""
    from repro.core import FusedEngine, pack

    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _, layout = pack({"w": np.zeros((2, 16), np.float32)}, pad_to=8)
    w = np.full((2, 2), 0.5)
    with pytest.raises(NotImplementedError, match="topk=4"):
        FusedEngine(w, layout, scale_chunk=8, topk=4, impl="pallas")
    FusedEngine(w, layout, scale_chunk=8, topk=None, impl="pallas")
    FusedEngine(w, layout, scale_chunk=8, topk=4, impl="jnp")
