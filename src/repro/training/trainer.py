"""End-to-end decentralized-FL training driver (simulated node axis).

Runs the paper's Algorithm 1 on a single host: nodes live on the leading
array axis (vmap), mixing through whichever GossipEngine is selected
(``engine=`` accepts a registry name -- tree / flat / fused -- or a
prebuilt engine; the default tree engine gossips through the dense-W
backend). This is the driver behind the EHR reproduction and the
CPU-scale LM examples; the sharded multi-pod variant reuses the same
``make_fl_round`` with a mesh-built engine (see launch/train.py and
launch/dryrun.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLRunConfig
from repro.core.engine import GossipEngine, get_engine
from repro.core.fl import FLConfig, FLState, init_fl_state, make_fl_round
from repro.core.schedules import constant, inv_sqrt, theorem1_schedule
from repro.core.topology import check_assumption1, mixing_matrix
from repro.training.metrics import MetricHistory, comm_bytes_per_gossip

PyTree = Any

__all__ = ["AdaptiveTopK", "TrainResult", "train_decentralized",
           "make_schedule", "stack_for_nodes"]


class AdaptiveTopK:
    """Error-triggered wire densification: the ONE owner of the adaptive-k
    round-to-round logic (used by ``train_decentralized`` and the EHR
    example -- do not hand-roll the switch).

    Spec ``(k_sparse, k_dense, densify_high[, resparsify_low])``: rounds
    run the sparse wire until the ``ef_residual_rms`` metric (the mass
    the wire is deferring) crosses ``densify_high``; then the densified
    twin runs (``dense_topk`` collapses to None -- plain dense int8 --
    when k_dense covers the whole scale chunk) until the residual drains
    BELOW ``resparsify_low`` (default ``densify_high / 2``).

    The two thresholds are a HYSTERESIS band: a single threshold
    duty-cycles -- densifying drains the residual just under the line,
    re-sparsifying pushes it back over, so k flaps every round or two
    around regime changes (observed on the EHR cohort trace;
    regression-tested in tests/test_schedule.py). With the band, the
    wire stays dense until the residual is genuinely drained and stays
    sparse until it genuinely builds back up.

    Build BOTH engines/round functions up front (identical comm-state
    contract, so they advance the same state; k is a compile-time kernel
    constant, so adapting is a function switch, never a recompile), then
    per round:

        fn = ctl.pick(sparse_fn, dense_fn)
        state, m = fn(state, batches)        # ctl.current_k ran this round
        ctl.update(float(m["ef_residual_rms"]))
    """

    def __init__(self, spec, scale_chunk: int):
        if len(spec) == 3:
            k_sparse, k_dense, high = spec
            low = float(high) / 2.0
        else:
            k_sparse, k_dense, high, low = spec
        self.k_sparse = int(k_sparse)
        self.k_dense = int(k_dense)
        self.threshold = float(high)  #: densify when rms exceeds this
        self.low = float(low)  #: re-sparsify only when rms drains below
        if not (0.0 < self.low <= self.threshold):
            raise ValueError(
                f"hysteresis band needs 0 < low <= high, got "
                f"low={self.low}, high={self.threshold}"
            )
        #: topk= for the densified twin engine (None = dense int8)
        self.dense_topk = None if self.k_dense >= scale_chunk else self.k_dense
        self._use_dense = False
        self.rounds = 0
        self.dense_rounds = 0
        self.switches = 0

    @property
    def current_k(self) -> int:
        """The k THIS round ships (valid until :meth:`update` is called)."""
        return self.k_dense if self._use_dense else self.k_sparse

    def pick(self, sparse_fn, dense_fn):
        return dense_fn if self._use_dense else sparse_fn

    def update(self, ef_residual_rms: float) -> None:
        """Account the round just run and arm the next one: densify-high
        / re-sparsify-low, holding the current wire inside the band."""
        self.rounds += 1
        self.dense_rounds += int(self._use_dense)
        if self._use_dense:
            use_dense = ef_residual_rms >= self.low
        else:
            use_dense = ef_residual_rms > self.threshold
        self.switches += int(use_dense != self._use_dense)
        self._use_dense = use_dense


@dataclasses.dataclass
class TrainResult:
    state: FLState
    history: MetricHistory
    consensus: PyTree
    w: np.ndarray
    engine: GossipEngine = None  # the engine the run trained with
    round_fn: Callable = None  # the jitted round (lower it to read its HLO)


def make_schedule(run: FLRunConfig):
    if run.schedule == "inv_sqrt":
        return inv_sqrt(run.alpha0)
    if run.schedule == "constant":
        return constant(run.alpha0)
    if run.schedule == "theorem1":
        return theorem1_schedule(run.n_nodes, run.alpha0)
    raise ValueError(f"unknown schedule {run.schedule!r}")


def stack_for_nodes(params: PyTree, n_nodes: int, perturb: float = 0.0, key=None) -> PyTree:
    """Replicate one node's params across the node axis (identical init;
    optional per-node perturbation for consensus-dynamics experiments)."""

    def f(p):
        stacked = jnp.broadcast_to(p[None], (n_nodes,) + p.shape)
        return jnp.array(stacked)

    stacked = jax.tree_util.tree_map(f, params)
    if perturb > 0.0:
        if key is None:
            key = jax.random.key(0)
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        keys = jax.random.split(key, len(leaves))
        leaves = [
            l + perturb * jax.random.normal(k, l.shape, jnp.float32).astype(l.dtype)
            for l, k in zip(leaves, keys)
        ]
        stacked = jax.tree_util.tree_unflatten(treedef, leaves)
    return stacked


def train_decentralized(
    loss_fn: Callable[[PyTree, Dict], jnp.ndarray],
    params_single: PyTree,
    run: FLRunConfig,
    step_batches: Iterator[Dict[str, np.ndarray]],
    rounds: int,
    eval_fn: Optional[Callable[[PyTree], Dict[str, float]]] = None,
    eval_every: int = 50,
    log_every: int = 0,
    wire_dtype=None,
    engine="tree",
    scale_chunk: Optional[int] = None,
    topk: Optional[int] = None,
    round_schedule: Optional[str] = None,
    storage_dtype=None,
    topk_schedule: Optional[Tuple[int, ...]] = None,
    topology_program: Optional[str] = None,
    node_program: Optional[str] = None,
    staleness_depth: Optional[int] = None,
    robust_alpha: bool = False,
    privacy: Optional[str] = None,
    scope: Optional[str] = None,
) -> TrainResult:
    """Train for ``rounds`` communication rounds.

    ``step_batches`` yields PER-STEP node-stacked batches (nodes, ...);
    the driver groups Q of them per round (paper: Q local updates, then
    one communication).

    ``engine`` selects the round engine: a registry name (resolved via
    ``repro.core.engine.get_engine`` and built with its ``simulated``
    constructor against the run topology's W) or a prebuilt
    :class:`GossipEngine`. Flat/fused engines pack the state; the tree
    view is restored at the eval/consensus boundary via
    ``engine.params_view``. ``scale_chunk`` / ``topk`` configure the
    fused engines' int8 / top-k wire; ``round_schedule``
    ("sequential" | "pipelined") selects the round's time layout
    (pipelined overlaps the collective with the next round's local
    steps, mixing one-round stale); ``storage_dtype`` keeps the flat
    engine's packed buffer in bf16 (fp32 stays only in the mix
    accumulator).

    ``topk_schedule = (k_sparse, k_dense, densify_high[, resparsify_low])``
    is the adaptive-k hook: rounds run with the sparse wire until the
    EF-residual RMS (the ``ef_residual_rms`` metric) crosses
    ``densify_high``, then densify to ``k_dense`` (>= the scale chunk
    disables masking entirely) until the residual drains below
    ``resparsify_low`` (default ``densify_high / 2`` -- the hysteresis
    band that keeps k from duty-cycling; see
    :class:`AdaptiveTopK`). Both variants are built once and jitted once
    -- k is a compile-time kernel constant, so adapting means switching
    between two round functions over the SAME state, not recompiling.

    ``topology_program`` selects the per-round graph dynamics (the THIRD
    round axis, ``repro.core.dynamics``): a registry spec string like
    ``"node_churn:p_down=0.2,mean_downtime=5"`` -- the run's base W is
    gated per round with dropped-edge weight folded into the self-loops,
    inside the ONE compiled round function (metrics gain
    ``edge_fraction``). None (or ``"static"``) keeps the compile-time
    constant W.

    ``node_program`` selects per-NODE heterogeneity (the FOURTH round
    axis, ``repro.core.heterogeneity``): a spec string like
    ``"stragglers:frac=0.25,rate=0.5"`` gating each node's local-step
    budget and payload delivery per round -- still traced operands of
    the one compiled round (metrics gain ``payload_fraction`` /
    ``compute_fraction``). ``staleness_depth=k`` is sugar for
    ``round_schedule="bounded_staleness:k=k"`` (k-round-stale mixing
    with k payloads in flight; 0 = sequential). ``robust_alpha=True``
    shrinks the step-size schedule by
    ``robust_alpha_scale(expected_uptime, k)`` -- the staleness/churn
    controller keeping the effective alpha/spectral-gap ratio of the
    fault-free tuning.

    ``privacy`` selects the wire's privacy epilogue (the FIFTH round
    axis, ``repro.core.privacy``): a spec string like
    ``"secure_agg+dp:sigma=0.5,clip=1.0"`` -- pairwise antisymmetric
    masks that cancel under the symmetric mix (no single neighbor
    payload is readable) and/or per-node clip + Gaussian noise riding
    the EF residual, with the ``dp_epsilon`` moments bound as a metric.

    ``scope`` selects the federation scope (the SIXTH round axis,
    ``repro.core.scope``): which columns of the flat buffer gossip
    touches at all. A spec string like ``"backbone"`` (share everything
    but the classifier head -- each hospital keeps a personalized head
    trained purely on local gradients, bit-untouched by the wire) /
    ``"ranges:0-1376"`` / ``"layerwise:freq=4"`` (head columns join the
    mix only every 4th round). Partial scopes shrink the wire
    proportionally: every collective, top-k, EF residual and
    quantization scale operates on the shared slice only.
    """
    w = mixing_matrix(run.topology, run.n_nodes)
    check_assumption1(w)
    if staleness_depth is not None:
        if round_schedule is not None:
            raise ValueError(
                "pass either round_schedule or staleness_depth, not both "
                "(staleness_depth=k is sugar for "
                "round_schedule='bounded_staleness:k=k')"
            )
        k = int(staleness_depth)
        round_schedule = "sequential" if k == 0 else f"bounded_staleness:k={k}"
    cfg = FLConfig(algorithm=run.algorithm, q=run.q, n_nodes=run.n_nodes)
    stacked = (
        params_single
        if _is_stacked(params_single, run.n_nodes)
        else stack_for_nodes(params_single, run.n_nodes)
    )
    if isinstance(engine, GossipEngine):
        knobs = {"wire_dtype": wire_dtype, "scale_chunk": scale_chunk,
                 "topk": topk, "round_schedule": round_schedule,
                 "storage_dtype": storage_dtype,
                 "topk_schedule": topk_schedule,
                 "topology_program": topology_program,
                 "node_program": node_program,
                 "privacy": privacy,
                 "scope": scope}
        set_knobs = sorted(k for k, v in knobs.items() if v is not None)
        if set_knobs:
            raise ValueError(
                f"{set_knobs} configure an engine BUILD; the prebuilt "
                f"{engine.name!r} engine already fixed its wire -- pass a "
                "registry name instead, or bake the knobs into the engine"
            )
        params0 = stacked if engine.layout is None else engine_pack(engine, stacked)
    else:
        if topk_schedule is not None:
            if topk is not None:
                raise ValueError("pass either topk or topk_schedule, not both")
            topk = int(topk_schedule[0])  # start on the sparse wire
        build = get_engine(engine).simulated
        kw = dict(
            wire_dtype=wire_dtype,
            scale_chunk=512 if scale_chunk is None else scale_chunk,
            round_schedule=round_schedule, storage_dtype=storage_dtype,
            topology_program=topology_program, node_program=node_program,
            privacy=privacy, scope=scope,
        )
        engine, params0 = build(w, stacked, topk=topk, **kw)
    caller_ids = {id(l) for l in jax.tree_util.tree_leaves(params_single)}
    if any(id(l) in caller_ids for l in jax.tree_util.tree_leaves(params0)):
        # the round donates its state: never hand it the caller's arrays
        params0 = jax.tree_util.tree_map(jnp.copy, params0)
    schedule = make_schedule(run)
    if robust_alpha:
        from repro.core.schedules import robust_alpha_scale, scaled

        uptime = (engine.topology_program.expected_uptime()
                  * engine.node_program.expected_uptime())
        schedule = scaled(
            schedule,
            robust_alpha_scale(uptime, engine.round_schedule.depth),
        )
    # the round consumes its input state: donating it keeps one state, not
    # two, on the device (at published widths the state is most of HBM)
    round_fn = jax.jit(
        make_fl_round(loss_fn, None, schedule, cfg, engine=engine),
        donate_argnums=(0,),
    )
    adaptive, dense_fn = None, None
    if topk_schedule is not None:
        adaptive = AdaptiveTopK(topk_schedule, engine.scale_chunk)
        # the densified twin: same comm-state contract (comm_keys do not
        # depend on k), so both round functions advance the SAME state
        dense_engine, _ = build(w, stacked, topk=adaptive.dense_topk, **kw)
        dense_fn = jax.jit(
            make_fl_round(loss_fn, None, schedule, cfg, engine=dense_engine),
            donate_argnums=(0,),
        )
    fallback_bytes = engine.wire_bytes(cfg)
    if fallback_bytes is None:
        fallback_bytes = comm_bytes_per_gossip(
            params_single, run.topology, run.n_nodes,
            wire_dtype=str(np.dtype(wire_dtype)) if wire_dtype else None,
        )
    # drop the tree views: from here the state alone holds the parameters
    del params_single, stacked
    state = init_fl_state(cfg, params0, engine=engine)
    del params0
    history = MetricHistory()
    t0 = time.time()
    cum_bytes = 0.0
    for rnd in range(1, rounds + 1):
        qs = [next(step_batches) for _ in range(run.q)]
        batches = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *qs)
        fn = adaptive.pick(round_fn, dense_fn) if adaptive else round_fn
        state, m = fn(state, batches)
        cum_bytes += float(m.get("wire_bytes", fallback_bytes))
        row = {
            "round": rnd,
            "iteration": int(state.step),
            "comm_rounds": rnd,
            "comm_bytes": cum_bytes,
            "loss": float(m["loss"]),
            "local_loss": float(m["local_loss"]),
            "grad_norm_sq": float(m["grad_norm_sq"]),
            "consensus_err": float(m["consensus_err"]),
            "alpha": float(m["alpha"]),
            "wall_s": time.time() - t0,
        }
        for k in ("edge_fraction", "payload_fraction", "compute_fraction",
                  "dp_epsilon"):
            if k in m:
                row[k] = float(m[k])
        if adaptive is not None:
            row["topk"] = float(adaptive.current_k)
            row["ef_residual_rms"] = float(m["ef_residual_rms"])
            adaptive.update(float(m["ef_residual_rms"]))
        if eval_fn is not None and (rnd % eval_every == 0 or rnd == rounds):
            row.update({f"eval_{k}": v for k, v in eval_fn(_consensus(engine, state)).items()})
        history.append(**row)
        if log_every and rnd % log_every == 0:
            print(
                f"[round {rnd:5d}] it={row['iteration']:6d} loss={row['loss']:.4f} "
                f"cons={row['consensus_err']:.3e} gnorm2={row['grad_norm_sq']:.3e}"
            )
    return TrainResult(state=state, history=history,
                       consensus=_consensus(engine, state), w=w, engine=engine,
                       round_fn=round_fn)


def _consensus(engine: GossipEngine, state: FLState) -> PyTree:
    """theta_bar on the TREE view, whatever the engine's representation."""
    return jax.tree_util.tree_map(
        lambda p: jnp.mean(p, axis=0), engine.params_view(state.params)
    )


def engine_pack(engine: GossipEngine, stacked: PyTree):
    """Pack tree params into a prebuilt flat engine's layout."""
    from repro.core.packing import pack_like

    return pack_like(stacked, engine.layout)


def _is_stacked(params: PyTree, n_nodes: int) -> bool:
    leaves = jax.tree_util.tree_leaves(params)
    return bool(leaves) and all(l.ndim >= 1 and l.shape[0] == n_nodes for l in leaves)
