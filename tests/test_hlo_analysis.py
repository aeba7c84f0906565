"""The while-aware HLO analyzer vs ground truth (unrolled lowerings)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_match_unrolled():
    def f_scan(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=9)
        return h.sum()

    def f_unroll(x, w):
        h = x
        for _ in range(9):
            h = jnp.tanh(h @ w)
        return h.sum()

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    a_scan = analyze_hlo(_compile(f_scan, xs, ws).as_text())
    c_unroll = _compile(f_unroll, xs, ws)
    truth = c_unroll.cost_analysis()["flops"]
    dot_flops = 9 * 2 * 64 * 128 * 128
    assert abs(a_scan.flops - truth) / truth < 0.02
    assert a_scan.flops >= dot_flops


def test_nested_scan_multiplication():
    def f(x, w):
        def outer(h, _):
            def inner(g, _):
                return g @ w, None
            g, _ = jax.lax.scan(inner, h, None, length=4)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h.sum()

    xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    a = analyze_hlo(_compile(f, xs, ws).as_text())
    expect = 3 * 4 * 2 * 32 * 64 * 64
    assert abs(a.flops - expect) / expect < 0.05


def test_grad_of_scan_counts_forward_and_backward():
    def loss(x, w):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=6)
        return jnp.sum(h * h)

    xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    a_fwd = analyze_hlo(_compile(loss, xs, ws).as_text())
    a_grad = analyze_hlo(_compile(jax.grad(loss, argnums=(0, 1)), xs, ws).as_text())
    # backward ~ 2x forward matmul cost (dx and dw) on top of the forward
    assert a_grad.flops > 2.4 * a_fwd.flops


def test_collectives_exact_count_and_bytes():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_hlo
        mesh = jax.make_mesh((8,), ("model",))
        def g(x, w):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=5)
            return h.sum()
        xs = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        ws = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        with mesh:
            c = jax.jit(g, in_shardings=(
                NamedSharding(mesh, P(None, "model")),
                NamedSharding(mesh, P("model", None)))).lower(xs, ws).compile()
        a = analyze_hlo(c.as_text())
        ar = a.collectives["all-reduce"]
        # 5 in-loop activation all-reduces (128x256 fp32) + 1 scalar
        assert ar["count"] == 6, ar
        assert abs(ar["bytes"] - (5 * 128 * 256 * 4 + 4)) < 8, ar
        print("COLL-OK")
        """
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "COLL-OK" in proc.stdout


def test_q4_round_hand_count_and_trip_inference():
    """A Q=4 DSGD round vs an exact hand count of its matmul flops --
    and the same HLO with every ``known_trip_count`` annotation stripped
    must analyze IDENTICALLY (trip count recovered from the loop
    condition's ``counter < N`` bound). Before that fallback existed,
    an un-annotated scanned body silently counted once."""
    import re

    from repro.core.fl import FLConfig, init_fl_state, make_fl_round
    from repro.core.mixing import make_dense_gossip
    from repro.core.topology import metropolis_weights, ring_graph

    n, din, dh, q, batch = 4, 32, 64, 4, 8
    key = jax.random.key(0)
    params = {
        "w1": jax.random.normal(key, (n, din, dh), jnp.float32),
        "w2": jax.random.normal(key, (n, dh, 2), jnp.float32),
    }

    def loss_fn(p, b):
        x, y = b
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    gossip = make_dense_gossip(metropolis_weights(ring_graph(n)))
    cfg = FLConfig(algorithm="dsgd", q=q, n_nodes=n)
    state = init_fl_state(cfg, params)
    round_fn = make_fl_round(
        loss_fn, gossip, schedule=lambda s: jnp.float32(0.01), cfg=cfg)
    batches = (jnp.zeros((q, n, batch, din)), jnp.zeros((q, n, batch, 2)))
    text = _compile(round_fn, state, batches).as_text()

    # hand count, per local step, all n nodes:
    #   forward   x@w1 (2*n*B*dh*din) + h@w2 (2*n*B*2*dh)
    #   backward  dlogits@w2^T (2*n*B*dh*2)   [dx of layer 2]
    #             x^T@dh (2*n*din*dh*B)       [dw1]
    #             h^T@dlogits (2*n*dh*2*B)    [dw2]
    #   (no dx for layer 1: x is data, grads are wrt params only)
    per_step = (2 * n * batch * dh * din + 2 * n * batch * 2 * dh
                + 2 * n * batch * dh * 2 + 2 * n * din * dh * batch
                + 2 * n * dh * 2 * batch)
    # gossip mix: W (n,n) @ params (n, total); XLA concatenates the two
    # leaves into one (n, din*dh + dh*2) operand
    total = din * dh + dh * 2
    hand_dots = q * per_step + 2 * n * n * total

    a = analyze_hlo(text)
    # analyzer = exact dot flops + a 1-flop/elem fusion estimate on top
    assert a.flops >= hand_dots
    assert a.flops <= hand_dots * 1.25

    stripped = re.sub(r'"?known_trip_count"?\s*:\s*\{[^}]*\},?', "", text)
    assert "known_trip_count" not in stripped
    a_inferred = analyze_hlo(stripped)
    assert a_inferred.flops == a.flops
    assert a_inferred.traffic_bytes == a.traffic_bytes


def test_traffic_includes_loop_body():
    def f_scan(x):
        def body(h, _):
            return jnp.sin(h) * 2.0, None
        h, _ = jax.lax.scan(body, x, None, length=50)
        return h

    xs = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    a = analyze_hlo(_compile(f_scan, xs).as_text())
    one_buffer = 1024 * 1024 * 4
    # >= 50 reads + 50 writes of the carried buffer
    assert a.traffic_bytes >= 90 * one_buffer
