"""``ShardedFusedEngine``: one site a chip on a 1-D mesh of the cell's
chips, the circulant W of the mesh's ring. Each round the wire stage (local
update, int8 quantize, error feedback) runs as one Pallas call on the
chip's own row, and the int8 payload and its scales reach the two ring
neighbours by ``ppermute``."""

import jax
import jax.numpy as jnp
import numpy as np

AXIS = "sites"


def _state(cfg, engine, mesh, params_buf):
    """The initial FL state built directly in its per-site shardings: the
    zero comm buffers never exist whole on one chip."""
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


def build(loss_fn, fl_cfg, schedule, traffic, params, w, devices, fault=None):
    """Returns (round body, initial state, params_view, theta0 row). The
    body is ``make_fl_round``'s, unjitted. ``w`` must be the ring's
    circulant W that the mesh realizes; ``fault="no_exchange"`` sets the
    self weight to 1 (W = I) on the same ppermute path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import ShardedFusedEngine, make_fl_round
    from repro.core.packing import pack

    n, chunk = fl_cfg.n_nodes, int(traffic["scale_chunk"])
    if len(devices) != n:
        raise ValueError(f"one site a chip: {n} sites, {len(devices)} chips")
    store = jnp.dtype(traffic["storage_dtype"])
    mesh = Mesh(np.array(devices), (AXIS,))
    sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), params)
    engine = ShardedFusedEngine.from_mesh(
        mesh, (AXIS,), sds, scale_chunk=chunk, impl="pallas",
        storage_dtype=store,
        self_weight=1.0 if fault == "no_exchange" else None)
    want = np.eye(n) if fault == "no_exchange" else w
    if not np.allclose(engine.dense_equivalent(), want):
        raise ValueError("the traffic's W is not the mesh ring's circulant "
                         f"W: {want} != {engine.dense_equivalent()}")
    row, _ = pack(jax.tree_util.tree_map(lambda l: l[None], params),
                  pad_to=chunk, buffer_dtype=store)
    buf = jax.make_array_from_single_device_arrays(
        (n, engine.layout.total), NamedSharding(mesh, engine.params_spec()),
        [jax.device_put(row, d) for d in devices])
    state = _state(fl_cfg, engine, mesh, buf)
    del buf
    theta0 = jax.device_put(row[0], NamedSharding(mesh, P()))
    body = make_fl_round(loss_fn, None, schedule, fl_cfg, engine=engine)
    return body, state, engine.params_view, theta0


def wire_bytes(traffic, total, sites_per_chip):
    """Bytes the wire-stage kernel must move per round on one chip: each
    buffer of its contract once, at the dtype it is stored in when the
    kernel runs (the operands and results of the compiled call). DSGD
    reads the float32 parameters (the engine widens the stored ones before
    the call), gradient, recon and residual; it writes the float32 payload
    h, recon and residual, the int8 values and one float32 scale a
    ``scale_chunk`` of columns."""
    if traffic["algorithm"] != "dsgd":
        raise ValueError("the sharded cells run DSGD")
    chunks = total // int(traffic["scale_chunk"])
    return sites_per_chip * (total * (4 * 4 + 3 * 4 + 1) + chunks * 4)
