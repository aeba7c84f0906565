"""Fully decentralized federated learning: DSGD / DSGT with Q local steps.

Implements the paper's Algorithm 1 and both base optimizers as pure JAX
step builders operating on **node-stacked** state (every parameter leaf
carries a leading ``nodes`` axis). All state representation, mixing, and
wire concerns live behind the :class:`repro.core.engine.GossipEngine`
protocol -- ``make_fl_round`` builds ONE round function for whichever
engine it is handed:

* ``tree``          -- nodes as a vmap axis over the parameter pytree,
  mixing via any tree-level gossip backend (dense-W simulated, ppermute
  mesh, all-gather); the EHR experiments and all CPU tests;
* ``flat``          -- the state packed into a single ``(nodes,
  total_params)`` buffer (``core.packing``): optimizer update, metrics,
  and mixing are single-buffer ops instead of per-leaf traversals;
* ``fused``         -- the flat state with the round megakernel: the
  whole communication step (local update + int8 quantize + W mix + EF
  residual, optionally top-k sparsified, for DSGD and DSGT alike) is ONE
  Pallas call (``repro.kernels.gossip``), with the compression state in
  ``FLState.comm``;
* ``sharded_fused`` -- the shard_map-native fused round for real meshes:
  one wire-stage kernel per round per shard, int8 payload moved by
  ppermute (circulant W) or all-gather (dense W).

Update equations (r is the global iteration counter, 1-indexed):

  local (Eq. 4):  theta_i <- theta_i - alpha^r * grad g_i(theta_i)

  DSGD comm (Eq. 2):
      theta_i <- sum_j W_ij theta_j - alpha^r * grad g_i(theta_i)

  DSGT comm (Eq. 3, GNSD ordering of [14]):
      g_new   = grad g_i(theta_i^r)
      vtheta  <- sum_j W_ij vtheta_j + (g_new - g_prev)
      theta_i <- sum_j W_ij theta_j - alpha^r * vtheta_i
      g_prev  <- g_new

  where for the federated variant (Q > 1) ``g_prev`` is the gradient from
  the *previous communication round* (local rounds use Eq. 4 only, exactly
  as Algorithm 1 prescribes). The gradient-tracking invariant

      mean_i vtheta_i^k == mean_i g_i^k        (at every comm round k)

  is preserved by any doubly-stochastic W and is property-tested.

  The FUSED engines use the adapt-then-combine ordering (update first,
  then mix the half-updated state) so the megakernel quantizes exactly
  what goes on the wire:

      DSGD:  theta_i <- sum_j W_ij Q[theta_j - alpha^r g_j]
      DSGT:  vtheta_half = vtheta + (g_new - g_prev)
             vtheta <- sum_j W_ij Q[vtheta_half_j]
             theta  <- sum_j W_ij Q[theta_j - alpha^r vtheta_half_j]

  with Q[.] the difference-coded int8 quantizer with error feedback
  (CHOCO-style; exact in the consensus limit; ``topk`` ships only the k
  largest payload columns per scale chunk, EF absorbing the truncation).
  Both orderings satisfy the same Theorem 1 style guarantees; the fused
  one is what a bandwidth-bound deployment runs.

Baselines expressed in the same machinery:
  * centralized SGD ("fusion center"):  W = (1/N) 1 1^T, Q = 1
  * FedAvg (star network, McMahan et al.): W = (1/N) 1 1^T, Q > 1
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mixing import GossipFn
from repro.core.schedules import Schedule

PyTree = Any
# (params_one_node, batch_one_node) -> scalar, or -> (scalar, counters)
LossFn = Callable[[PyTree, Any], Any]

__all__ = [
    "FLState",
    "FLConfig",
    "init_fl_state",
    "make_fl_round",
    "consensus_params",
]

_MIGRATION_HINT = (
    "was replaced by the GossipEngine protocol (repro.core.engine). "
    "Build an engine -- TreeEngine(gossip_fn), FlatEngine(mix_fn, layout), "
    "FusedEngine(w, layout, topk=...), or ShardedFusedEngine(mesh, "
    "node_axes, layout, ...) -- and pass it as engine=...; CLI surfaces "
    "resolve names through repro.core.engine.get_engine()."
)


class FLState(NamedTuple):
    """Node-stacked optimizer state. ``tracker``/``prev_grad`` are None for
    DSGD (keeps DSGD memory at 1x params, DSGT at 3x -- inherent to GT).
    ``comm`` is None except in the fused engines, where it holds the int8
    wire state (``engine.comm_keys``): ``{"recon", "residual"}`` (n, total)
    fp32 buffers for the parameter wire, ``{"recon_t", "residual_t"}`` for
    DSGT's tracker wire, and the sharded engine's running neighbor-mix
    accumulators ``{"mix_recon", "mix_recon_t"}`` (per-direction
    ``nbr_recon_{d}`` twins under a dynamic topology program). A dynamic
    :class:`~repro.core.dynamics.TopologyProgram` additionally carries its
    round counter and base RNG key here (``topo_round``, ``topo_key``), so
    checkpointed restores replay the identical graph sequence. An active
    :class:`~repro.core.privacy.PrivacySpec` rides the same counter
    discipline: ``priv_key`` (the spec's base key) plus ``topo_round``
    (reused as the pad/noise round counter even under a static topology),
    so restored runs regenerate the identical mask and noise streams."""

    step: jnp.ndarray  # () int32, global iteration r (counts local steps too)
    params: PyTree  # each leaf (nodes, ...)
    tracker: Optional[PyTree]  # DSGT vtheta, same layout
    prev_grad: Optional[PyTree]  # DSGT g at the last comm round
    #: fused-engine wire state (engine.comm_keys / comm_state_sds). Under
    #: the PIPELINED round schedule the sharded engine also double-buffers
    #: the in-flight wire payload here: ``wire_q`` (int8), ``wire_pos``
    #: (compact wire positions), ``wire_scales`` (+ ``_t`` twins for DSGT)
    comm: Optional[Dict[str, jnp.ndarray]] = None


@dataclasses.dataclass(frozen=True)
class FLConfig:
    algorithm: str = "dsgt"  # "dsgd" | "dsgt"
    q: int = 1  # local steps per communication round (Q in Alg. 1)
    n_nodes: int = 1

    def __post_init__(self) -> None:
        if self.algorithm not in ("dsgd", "dsgt"):
            raise ValueError(f"unknown algorithm {self.algorithm!r}")
        if self.q < 1:
            raise ValueError("q must be >= 1")
        if self.n_nodes < 1:
            raise ValueError("n_nodes must be >= 1")


def _tm(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def init_fl_state(
    cfg: FLConfig, stacked_params: PyTree, engine=None, **legacy
) -> FLState:
    """Initial state. DSGT's tracker is initialized to zeros; the first
    comm round's ``g_new - g_prev`` then loads the first gradient into the
    tracker (the standard GNSD cold start with g^0 := 0).

    ``engine``: the :class:`~repro.core.engine.GossipEngine` the state
    will be trained with. Engines validate their representation (the
    fused engines require the packed ``(nodes, total)`` flat buffer from
    ``core.packing.pack``) and contribute zero-initialized wire-state
    buffers to ``FLState.comm``. ``engine=None`` builds plain tree-state
    (no comm buffers) -- valid for the tree and flat exact-wire engines.
    """
    if legacy:
        raise TypeError(
            f"init_fl_state() got {sorted(legacy)}: the fused= flag "
            + _MIGRATION_HINT
        )
    if engine is not None and not hasattr(engine, "init_comm_state"):
        # e.g. the historical positional fused: bool landing on engine=
        raise TypeError(
            f"init_fl_state() engine must be a GossipEngine, got "
            f"{engine!r}: the fused= flag " + _MIGRATION_HINT
        )
    comm = None
    if engine is not None:
        engine.check_params(cfg, stacked_params)
        comm = engine.init_comm_state(cfg, stacked_params)
    else:
        leaves = jax.tree_util.tree_leaves(stacked_params)
        if not leaves:
            raise ValueError("empty parameter pytree")
        for leaf in leaves:
            if leaf.shape[:1] != (cfg.n_nodes,):
                raise ValueError(
                    f"param leaf {leaf.shape} is not node-stacked for "
                    f"n={cfg.n_nodes}"
                )
    zeros = _tm(jnp.zeros_like, stacked_params)
    if cfg.algorithm == "dsgt":
        return FLState(
            jnp.int32(0), stacked_params, zeros, _tm(jnp.zeros_like, zeros), comm
        )
    return FLState(jnp.int32(0), stacked_params, None, None, comm)


def consensus_params(state: FLState) -> PyTree:
    """theta_bar = (1/N) sum_i theta_i -- the model you deploy/serve."""
    return _tm(lambda p: jnp.mean(p, axis=0), state.params)


def make_fl_round(
    loss_fn: LossFn,
    gossip_fn: Optional[GossipFn] = None,
    schedule: Schedule = None,
    cfg: FLConfig = None,
    engine=None,
    **legacy,
) -> Callable[[FLState, PyTree], Tuple[FLState, Dict[str, jnp.ndarray]]]:
    """Build one *communication round*: (Q-1) local steps + 1 comm step.

    Args:
      loss_fn: per-node loss ``(params, batch) -> scalar`` (unstacked), or
        ``-> (scalar, counters)`` with a dict of scalar counters: the comm
        step's counters, averaged over sites, join the round's metrics.
      gossip_fn: convenience shorthand -- a tree-level mixing backend
        (theta <- W theta); wrapped in a
        :class:`~repro.core.engine.TreeEngine`. Mutually exclusive with
        ``engine``.
      schedule: alpha^r.
      cfg: algorithm + Q + N.
      engine: a :class:`~repro.core.engine.GossipEngine` -- THE dispatch
        path. The engine owns the state representation (tree pytree vs
        packed flat buffer), the wire (exact fp32/bf16 vs difference-coded
        int8 vs top-k sparsified int8), and the mixing implementation
        (dense matmul, ppermute, all-gather, round megakernel, sharded
        megakernel) -- and, via its ``round_schedule`` attribute, the
        round's TIME layout: ``sequential`` (the paper's blocking round)
        or ``pipelined`` (the collective for round r's payload in flight
        across round r+1's local steps, one-round-stale mixing; see
        ``repro.core.engine.RoundSchedule``). Build the matching state
        with ``init_fl_state(cfg, params, engine=engine)``. The
        historical ``layout=`` / ``fused=`` kwargs raise with a
        migration hint.

    Hierarchical (multi-pod) gossip is built by ALTERNATING two round
    functions at the driver level -- one whose engine mixes only the cheap
    intra-pod axis (``axes_subset=("data",)``), one that also crosses pods
    -- rather than branching inside the jitted program (a data-dependent
    `where` would execute both collectives every round; verified in the
    dry-run HLO).

    Returns ``round_fn(state, batches) -> (state, metrics)`` where each
    ``batches`` leaf is shaped (Q, nodes, ...) -- one microbatch per local
    iteration per node. Metrics: mean loss, ||mean_i grad_i||^2 (the
    stationarity term of Theorem 1), consensus error
    (1/N) sum_i ||theta_i - theta_bar||^2, comm_rounds (=1), alpha, and --
    for engines that account their wire -- ``wire_bytes`` (summed
    per-round egress of all nodes).
    """
    if legacy:
        raise TypeError(
            f"make_fl_round() got {sorted(legacy)}: the layout=/fused= "
            "kwarg maze " + _MIGRATION_HINT
        )
    if schedule is None or cfg is None:
        raise TypeError(
            "make_fl_round requires schedule and cfg (they default to None "
            "only so engine= can be passed by keyword)"
        )
    if engine is not None and not hasattr(engine, "make_comm_step"):
        # e.g. a historical positional layout= landing on engine=
        raise TypeError(
            f"make_fl_round() engine must be a GossipEngine, got "
            f"{engine!r}: the layout=/fused= kwarg maze " + _MIGRATION_HINT
        )
    if engine is None:
        if gossip_fn is None:
            raise ValueError(
                "make_fl_round needs either a tree-level gossip_fn or an "
                "engine=GossipEngine"
            )
        from repro.core.engine import TreeEngine

        engine = TreeEngine(gossip_fn)
    elif gossip_fn is not None:
        raise ValueError(
            "pass the mixing backend inside the engine, not as gossip_fn"
        )

    from repro.core.engine import STAGE_LOCAL, resolve_schedule

    def counted_loss(params, batch):
        out = loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    grad_fn = jax.vmap(jax.value_and_grad(counted_loss, has_aux=True))
    engine_eval_grads = engine.make_eval_grads(grad_fn)

    def eval_grads(params: PyTree, batch: PyTree):
        # every step's forward and backward, the comm step's included,
        # runs under the local-step stage. The per-site losses come back
        # as they are, or as (losses, counters) where the loss counts.
        with jax.named_scope(STAGE_LOCAL):
            (losses, counters), grads = engine_eval_grads(params, batch)
        return ((losses, counters) if counters else losses), grads

    def local_step(state: FLState, batch: PyTree,
                   mask=None) -> Tuple[FLState, jnp.ndarray]:
        # ``mask``: the node program's (n,) per-iteration compute gate
        # (straggling nodes sit masked iterations out -- traced, so the
        # ONE compiled scan serves every heterogeneity pattern).
        step = state.step + 1
        alpha = schedule(step)
        losses, grads = eval_grads(state.params, batch)
        params = engine.local_step(state.params, grads, alpha, mask=mask)
        if isinstance(losses, tuple):
            losses = losses[0]
        return state._replace(step=step, params=params), jnp.mean(losses)

    # The engine's RoundSchedule owns the round's TIME layout: sequential
    # (Q-1 local steps, then produce -> collective -> mix) or pipelined
    # (ingest the in-flight collective BEFORE the scan, mix one-round
    # stale). The schedule is fixed at engine construction because it is
    # part of the comm-state contract (repro.core.engine.RoundSchedule).
    round_schedule = resolve_schedule(getattr(engine, "round_schedule", None))
    return round_schedule.build_round(engine, eval_grads, schedule, cfg,
                                      local_step)


def _mean_grad_norm_sq(stacked_grads: PyTree) -> jnp.ndarray:
    """|| (1/N) sum_i grad_i ||^2 -- the first term of Theorem 1's LHS."""
    sq = 0.0
    for g in jax.tree_util.tree_leaves(stacked_grads):
        mean_g = jnp.mean(g.astype(jnp.float32), axis=0)
        sq = sq + jnp.sum(mean_g * mean_g)
    return sq


def _consensus_error(stacked_params: PyTree) -> jnp.ndarray:
    """(1/N) sum_i ||theta_i - theta_bar||^2 -- Theorem 1's second term."""
    err = 0.0
    for p in jax.tree_util.tree_leaves(stacked_params):
        pf = p.astype(jnp.float32)
        dev = pf - jnp.mean(pf, axis=0, keepdims=True)
        err = err + jnp.sum(dev * dev) / pf.shape[0]
    return err
