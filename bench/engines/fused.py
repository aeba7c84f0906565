"""``FusedEngine``: every site of the federation on one chip, the round
megakernel (local update, int8 quantize, W mix, error feedback) as one
Pallas call per round."""

import jax
import jax.numpy as jnp
import numpy as np


def build(loss_fn, fl_cfg, schedule, traffic, params, w, devices, fault=None):
    """Returns (round body, initial state, params_view, theta0 row). The
    body is ``make_fl_round``'s, unjitted."""
    from repro.core import FusedEngine, init_fl_state, make_fl_round
    from repro.core.packing import pack, pack_layout

    n, chunk = fl_cfg.n_nodes, int(traffic["scale_chunk"])
    store = jnp.dtype(traffic["storage_dtype"])
    sds = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype), params)
    layout = pack_layout(sds, pad_to=chunk, storage_dtype=store)
    if fault == "no_exchange":
        w = np.eye(n)
    engine = FusedEngine(w, layout, scale_chunk=chunk, impl="pallas")
    with jax.default_device(devices[0]):
        row, _ = pack(jax.tree_util.tree_map(lambda l: l[None], params),
                      pad_to=chunk, buffer_dtype=store)
        flat = jax.jit(lambda r: jnp.broadcast_to(r, (n, layout.total)))(row)
        state = init_fl_state(fl_cfg, flat, engine=engine)
    body = make_fl_round(loss_fn, None, schedule, fl_cfg, engine=engine)
    return body, state, engine.params_view, row[0]


def wire_bytes(traffic, total, sites_per_chip):
    """Bytes the round megakernel must move per round on one chip: each
    state buffer of its contract once, at its stored dtype. Reads the
    parameters (and DSGT's tracker and previous gradient) in the storage
    dtype and the float32 gradient, recon and residual of each wire; writes
    the mixed parameters (and tracker) and the new recon and residual."""
    s = jnp.dtype(traffic["storage_dtype"]).itemsize
    if traffic["algorithm"] == "dsgt":
        per_col = (3 * s + 4 + 4 * 4) + (2 * s + 4 * 4)
    else:
        per_col = (s + 4 + 2 * 4) + (s + 2 * 4)
    return sites_per_chip * total * per_col
