"""GossipEngine protocol: ONE pluggable layer behind ``make_fl_round``.

Historically the round machinery grew three divergent call paths -- the
node-stacked pytree path, the flat ``(nodes, total)`` buffer path
(``layout=``), and the fused round megakernel (``fused=``) -- selected by
a kwarg maze in ``core.fl`` and string-dispatched if-chains in the
launchers. This module replaces all of that with a small protocol:

    init_comm_state(cfg, params)  extra wire state carried in FLState.comm
    local_step(params, grads, a)  the SGD update in the engine's own
                                  state representation
    mix(buf)                      exact-wire W application (tree/flat
                                  engines; fused engines mix inside their
                                  comm step instead)
    wire_bytes(cfg)               per-round egress accounting (all nodes)

plus two build hooks ``make_eval_grads`` (representation adapter around
the vmapped grad fn) and ``make_comm_step`` (the whole communication
step; the base class provides the paper's exact-wire mix-then-adapt
Eqs. 2/3, fused engines override it with adapt-then-combine kernels).

Shipped engines (the registry keys are what ``--fl-engine`` accepts
everywhere -- launch/dryrun.py, launch/train.py, examples -- so names
cannot drift):

    tree           node-stacked pytree state + any tree-level gossip
                   backend (dense-W simulated, mesh ppermute, all-gather)
    flat           the state IS one packed (nodes, total) fp32 buffer;
                   mixing is one matmul / ppermute / all-gather on it
    fused          the round megakernel: local update + int8 quantize +
                   W mix + error feedback in ONE Pallas call
                   (``kernels.gossip``), CHOCO difference-coded wire
    sharded_fused  the shard_map-native fused round: every device owns
                   its node's W row and its rows of the flat buffer, the
                   wire stage (update + top-k + int8 quantize + EF) is
                   ONE Pallas call per round, and the int8 payload moves
                   via ppermute (circulant torus/ring W) or all-gather
                   (arbitrary dense W)

``topk=`` on the fused engines masks the payload to the k largest-|.|
columns per scale chunk inside the kernel; the EF residual absorbs the
truncation, and wire bytes drop below the dense-int8 floor
(``packing.flat_wire_bytes``). On the SHARDED engine, ``topk`` also
turns on the COMPACT wire by default: the wire-stage kernel's
compact-gather epilogue emits exactly (k int8 values, k int16/int32
in-chunk positions, fp32 scales) per chunk, those buffers -- and nothing
masked-dense -- are the collective's operands, and the receive side
scatter-accumulates them into the running ``mix_recon`` term, so
``flat_wire_bytes`` accounts the bytes that actually cross.

Orthogonally to WHAT moves, a :class:`RoundSchedule` fixes WHEN: the
``sequential`` schedule is the paper's produce -> collective -> mix
round; the ``pipelined`` schedule double-buffers the wire payload in
``FLState.comm`` (``wire_*`` keys), issues the collective for round r's
payload BEFORE round r+1's local-step scan (no data dependency -- the
overlap window an async-collective backend exploits), and mixes with
one-round-STALE neighbor information -- exactly
sequential-with-one-round-delay, proven against a hand-written delayed
oracle in tests/test_schedule.py. Engines carry their schedule
(``round_schedule=`` at build time) because it is part of the comm-state
contract; ``--fl-schedule`` resolves through the schedule registry the
same way ``--fl-engine`` resolves through the engine registry.

How the sharded engine stays O(params/node) per device: a CHOCO node
needs ``sum_j W_ij recon_j`` over its neighbors' reconstructions, but
``recon_j`` only ever advances by the dequantized wire payload
``dq_j``, so each node carries a running accumulator

    mix_recon_i  <-  mix_recon_i + sum_j W_ij dq_j        (one buffer)
    mixed_i       =  w_ii * h_i + mix_recon_i'

which equals the dense megakernel's ``W_off @ recon' + w_self * h`` row
exactly (up to summation order) without ever materializing neighbor
state. ``mix_recon`` rides in ``FLState.comm`` next to recon/residual.
"""

from __future__ import annotations

import abc
from typing import Any, Callable, ClassVar, Dict, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.dynamics import (
    STATIC,
    TopologyProgram,
    resolve_program,
)
from repro.core.heterogeneity import (
    HOMOGENEOUS,
    NodeProgram,
    compose_node_gate,
    resolve_node_program,
)
from repro.core.fl import (
    FLConfig,
    FLState,
    _consensus_error,
    _mean_grad_norm_sq,
)
from repro.core.mixing import (
    GossipFn,
    _allgather_row,
    _mesh_dirs,
    _shard_map,
    _split_w,
    make_dense_flat_mix,
    make_dense_gossip,
    make_mesh_flat_mix,
    make_mesh_gossip,
    mesh_gossip_dense_equivalent,
)
from repro.core.packing import (
    FlatLayout,
    bitmap_bytes_per_chunk,
    compact_index_bytes,
    compact_pos_dtype,
    flat_wire_bytes,
    flat_wire_bytes_per_shard,
    pack,
    pack_layout,
    pack_like,
    scoped_layout,
    unpack,
)
from repro.core.privacy import (
    NONE as PRIVACY_NONE,
    PAD_STREAM,
    TRACKER_STREAM_OFFSET,
    PrivacySpec,
    dp_noise,
    epsilon_traced,
    mask_wire,
    pair_index,
    resolve_privacy,
)
from repro.core.scope import (
    FULL as SCOPE_FULL,
    FederationScope,
    LayerwiseScope,
    resolve_scope,
)

PyTree = Any

__all__ = [
    "GossipEngine",
    "TreeEngine",
    "FlatEngine",
    "FusedEngine",
    "ShardedFusedEngine",
    "register_engine",
    "get_engine",
    "engine_names",
    "RoundSchedule",
    "SequentialSchedule",
    "PipelinedSchedule",
    "BoundedStalenessSchedule",
    "register_schedule",
    "get_schedule",
    "schedule_names",
    "resolve_schedule",
    "STAGES",
]


def _tm(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


# ---------------------------------------------------------------------------
# Round stages
# ---------------------------------------------------------------------------
#
# The round's device work carries one of four stage names, each opened as a
# ``jax.named_scope``. The name lands in the ``op_name`` metadata of every
# compiled instruction traced under it (fusions, ``while`` loops and their
# bodies included), which is how a device trace attributes time to the
# stages. A scope adds metadata only: the compiled program is otherwise the
# same. Where stages nest -- a collective inside the sharded engine's wire
# stage -- the innermost names the instruction.

#: the local steps' forward and backward: the Q-1 step scan and the comm
#: step's gradient (``make_fl_round`` wraps ``eval_grads``)
STAGE_LOCAL = "fl_local"
#: the wire stage: input casts and column gathers, the round or stage
#: kernel (or the exact-wire mix), the scope epilogue, the storage casts
STAGE_WIRE = "fl_wire"
#: every collective that moves the payload, and the pipelined ingest
STAGE_TRANSPORT = "fl_transport"
#: the round's metrics dict
STAGE_METRICS = "fl_metrics"
STAGES = (STAGE_LOCAL, STAGE_WIRE, STAGE_TRANSPORT, STAGE_METRICS)


# ---------------------------------------------------------------------------
# Round schedules: how a communication round is laid out in TIME
# ---------------------------------------------------------------------------


class RoundSchedule(abc.ABC):
    """How one communication round is laid out in time.

    The :class:`GossipEngine` owns WHAT moves (state representation, wire
    encoding, mixing math); the RoundSchedule owns WHEN: whether the
    collective for a round's payload blocks that round's mix
    (:class:`SequentialSchedule`) or is issued while the NEXT round's
    local steps compute, the mix consuming one-round-stale neighbor
    information (:class:`PipelinedSchedule`). An engine carries its
    schedule as ``engine.round_schedule`` (fixed at construction -- the
    schedule is part of the engine's comm-state contract, so
    ``init_fl_state`` / checkpoints see one consistent answer), and
    ``make_fl_round`` delegates the round layout here.

    Schedules register by name exactly like engines -- the registry is
    what ``--fl-schedule`` accepts everywhere.
    """

    name: ClassVar[str] = "abstract"
    #: staleness depth of the mixed neighbor information: 0 for the
    #: blocking sequential round, 1 for the double-buffered pipelined
    #: round, k for :class:`BoundedStalenessSchedule` (k in-flight
    #: payloads, mix against the k-round-stale one)
    depth: int = 0

    @abc.abstractmethod
    def build_round(self, engine: "GossipEngine", eval_grads, schedule,
                    cfg: FLConfig, local_step):
        """Assemble ``round_fn(state, batches) -> (state, metrics)`` from
        the engine's comm machinery and the per-iteration ``local_step``."""

    def spec(self) -> str:
        """The round-trippable string form (``resolve_schedule(spec)``
        reconstructs an equivalent schedule) -- what checkpoint manifests
        record and ``--fl-schedule`` accepts."""
        return self.name


_SCHEDULES: Dict[str, "RoundSchedule"] = {}


def register_schedule(cls: Type[RoundSchedule]) -> Type[RoundSchedule]:
    """Class decorator: make the schedule resolvable by name. Schedules
    are stateless, so the registry holds singleton instances -- the ONE
    list every ``--fl-schedule`` CLI and checkpoint manifest consults."""
    if cls.name in _SCHEDULES:
        raise ValueError(f"duplicate schedule name {cls.name!r}")
    _SCHEDULES[cls.name] = cls()
    return cls


def get_schedule(name: str) -> RoundSchedule:
    try:
        return _SCHEDULES[name]
    except KeyError:
        raise ValueError(
            f"unknown round schedule {name!r}; registered: {schedule_names()}"
        ) from None


def schedule_names() -> Tuple[str, ...]:
    return tuple(sorted(_SCHEDULES))


def resolve_schedule(rs) -> RoundSchedule:
    """Accept a registry name, a parameterized spec string
    (``"bounded_staleness:k=4"``), a RoundSchedule instance, or None
    (the sequential default)."""
    if rs is None:
        return _SCHEDULES["sequential"]
    if isinstance(rs, RoundSchedule):
        return rs
    name, _, argstr = str(rs).partition(":")
    base = get_schedule(name)
    if not argstr:
        return base
    kwargs: Dict[str, int] = {}
    for item in argstr.split(","):
        k, sep, v = item.partition("=")
        if not sep:
            raise ValueError(
                f"bad schedule spec {rs!r}: expected name:key=value[,...]"
            )
        try:
            kwargs[k.strip()] = int(v)
        except ValueError:
            raise ValueError(
                f"bad schedule spec {rs!r}: {v!r} is not an integer"
            ) from None
    try:
        return type(base)(**kwargs)
    except TypeError:
        raise ValueError(
            f"schedule {name!r} takes no parameters {tuple(kwargs)!r}"
        ) from None


def _require_sequential(round_schedule, name: str) -> RoundSchedule:
    rs = resolve_schedule(round_schedule)
    if rs.name != "sequential":
        raise ValueError(
            f"round schedule {rs.name!r} needs the split produce/collective "
            f"comm step of the fused engines; the {name!r} engine is "
            "sequential-only -- use 'fused' or 'sharded_fused'"
        )
    return rs


def _assemble_round(cfg, local_step, comm_call, pre_scan=None,
                    step_mask=None):
    """The shared round body: optional pre-scan hook (the pipelined
    ingest -- traced FIRST so its collective precedes the scan in the
    jaxpr), (Q-1) local steps under ONE lax.scan, then the comm call.
    ``comm_call(state, batch, aux)`` receives whatever ``pre_scan``
    returned (None without one). ``step_mask(state) -> (q-1, n)`` is the
    heterogeneous-compute hook (:meth:`GossipEngine.make_step_mask`): a
    traced per-node mask over the local-step scan -- straggling nodes
    run fewer EFFECTIVE iterations as masked updates of the ONE compiled
    scan, never as a recompile."""

    def round_fn(state: FLState, batches: PyTree):
        aux = None
        if pre_scan is not None:
            with jax.named_scope(STAGE_TRANSPORT):
                aux = pre_scan(state)
        q = cfg.q
        with jax.named_scope(STAGE_LOCAL):
            mask = step_mask(state) if step_mask is not None else None
            if q > 1:
                local_batches = _tm(lambda b: b[: q - 1], batches)
                if mask is None:
                    state, local_losses = jax.lax.scan(
                        local_step, state, local_batches
                    )
                else:
                    state, local_losses = jax.lax.scan(
                        lambda c, xs: local_step(c, xs[0], mask=xs[1]),
                        state, (local_batches, mask),
                    )
            else:
                local_losses = jnp.zeros((0,), jnp.float32)
            comm_batch = _tm(lambda b: b[q - 1], batches)
        state, metrics = comm_call(state, comm_batch, aux)
        with jax.named_scope(STAGE_METRICS):
            metrics["local_loss"] = jnp.where(
                q > 1,
                jnp.sum(local_losses) / jnp.maximum(1, q - 1),
                metrics["loss"],
            )
            if mask is not None:
                # realized local-step work: masked scan iterations + the
                # comm step's own update, as a fraction of the homogeneous
                # q * n
                metrics["compute_fraction"] = (
                    jnp.sum(mask.astype(jnp.float32)) + cfg.n_nodes
                ) / jnp.float32(q * cfg.n_nodes)
        return state, metrics

    return round_fn


@register_schedule
class SequentialSchedule(RoundSchedule):
    """The paper's round layout: (Q-1) local steps, then ONE comm step in
    which the payload is produced, crosses the wire, and is mixed before
    the round returns -- every engine supports it."""

    name = "sequential"
    depth = 0

    def build_round(self, engine, eval_grads, schedule, cfg, local_step):
        comm_step = engine.make_comm_step(eval_grads, schedule, cfg)
        return _assemble_round(
            cfg, local_step,
            lambda state, batch, aux: comm_step(state, batch),
            step_mask=engine.make_step_mask(cfg),
        )


@register_schedule
class PipelinedSchedule(RoundSchedule):
    """Overlap the collective with the local steps: round r's payload is
    double-buffered in ``FLState.comm`` (``wire_*``), its ppermute /
    all-gather is ISSUED at the top of round r+1 -- before the local-step
    scan, with no data dependency on it, so an async-collective backend
    overlaps the wire with the Q local steps -- and round r+1's mix
    consumes that one-round-stale neighbor information:

        sequential round r:   mixed_r = w_self*h_r + S_j W_ij recon_j^(r)
        pipelined  round r:   mixed_r = w_self*h_r + S_j W_ij recon_j^(r-1)

    i.e. exactly sequential-with-one-round-delay (tests/test_schedule.py
    proves equality against a hand-written delayed oracle). The first
    round mixes nothing (zero in-flight payload), the staleness price is
    quantified in experiments/staleness_ehr.json.

    Supported by the fused engines (their comm step already separates
    payload production from the collective); exact-wire engines raise at
    build time.
    """

    name = "pipelined"
    depth = 1

    def build_round(self, engine, eval_grads, schedule, cfg, local_step):
        # The ingest collective on the IN-FLIGHT payload is the pre-scan
        # hook: traced first, so it precedes the local-step scan in the
        # jaxpr and depends on nothing the scan computes -- that is the
        # overlap window.
        ingest, comm_step = engine.make_pipelined_round(
            eval_grads, schedule, cfg
        )
        return _assemble_round(cfg, local_step, comm_step, pre_scan=ingest,
                               step_mask=engine.make_step_mask(cfg))


@register_schedule
class BoundedStalenessSchedule(RoundSchedule):
    """Depth-k generalization of the pipelined round: k wire payloads
    ride in flight in ``FLState.comm`` (a ring buffer of
    ``wire_q`` / ``wire_pos`` / ``wire_scales``), the collective consumes
    the OLDEST one, and the mix uses k-round-stale neighbor information:

        round r:   mixed_r = w_self*h_r + S_j W_ij recon_j^(r-k)

    -- exactly sequential-with-k-round-delay (tests/test_bounded_staleness
    proves equality against a hand-written k-delayed oracle), a straggler
    budget of k rounds before a late payload must be dropped. ``k=1`` IS
    the pipelined schedule (bit-identical trajectories, same comm-state
    contract). The staleness price is swept in
    experiments/straggler_ehr.json; the alpha controller
    (``core.schedules.robust_alpha_scale``) compensates the slower
    mixing. Fused engines only, like the pipelined schedule.
    """

    name = "bounded_staleness"

    def __init__(self, k: int = 1):
        k = int(k)
        if k < 1:
            raise ValueError(f"bounded staleness depth k={k} must be >= 1")
        self.depth = k

    def spec(self) -> str:
        return f"{self.name}:k={self.depth}"

    def build_round(self, engine, eval_grads, schedule, cfg, local_step):
        ingest, comm_step = engine.make_pipelined_round(
            eval_grads, schedule, cfg
        )
        return _assemble_round(cfg, local_step, comm_step, pre_scan=ingest,
                               step_mask=engine.make_step_mask(cfg))


def _check_flat_params(cfg: FLConfig, params: PyTree, name: str) -> None:
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        raise ValueError("empty parameter pytree")
    for leaf in leaves:
        if leaf.shape[:1] != (cfg.n_nodes,):
            raise ValueError(
                f"param leaf {leaf.shape} is not node-stacked for n={cfg.n_nodes}"
            )
    if len(leaves) != 1 or leaves[0].ndim != 2:
        raise ValueError(
            f"{name} engine state must be the packed (nodes, total) flat "
            "buffer (core.packing.pack)"
        )


def _make_flat_eval_grads(layout: FlatLayout, grad_fn):
    def eval_grads(params: jnp.ndarray, batch: PyTree):
        # The tree view exists only inside this call. The conversion
        # copies (the buffer and the leaves tile differently on a TPU);
        # unpack/pack_like go through 128-lane rows, so the copies are
        # plain passes, not a relayout loop per node (core/packing.py).
        losses, grads = grad_fn(unpack(params, layout), batch)
        return losses, pack_like(grads, layout)

    return eval_grads


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------


class GossipEngine(abc.ABC):
    """One round engine: state representation + wire + mixing semantics.

    Subclasses set ``name`` (the registry key) and ``layout`` (the
    :class:`FlatLayout` for flat-state engines, None for tree state), and
    either implement :meth:`mix` (exact-wire engines; the base
    :meth:`make_comm_step` then runs the paper's mix-then-adapt Eqs. 2/3)
    or override :meth:`make_comm_step` entirely (fused engines).
    """

    name: ClassVar[str] = "abstract"
    #: True for engines that only run on a device mesh (no ``simulated``)
    needs_mesh: ClassVar[bool] = False
    layout: Optional[FlatLayout] = None
    #: the engine's :class:`RoundSchedule` (sequential unless the engine
    #: was built pipelined -- the schedule is part of the comm-state
    #: contract, so it is fixed at construction)
    round_schedule: RoundSchedule = _SCHEDULES["sequential"]
    #: the engine's :class:`~repro.core.dynamics.TopologyProgram` -- the
    #: THIRD round axis (engine = WHAT moves, schedule = WHEN, program =
    #: over WHICH graph). Fixed at construction like the schedule: a
    #: dynamic program adds the ``topo_round`` / ``topo_key`` counters to
    #: the comm-state contract and turns the mixing weights into traced
    #: per-round operands of the ONE compiled round function.
    topology_program: TopologyProgram = STATIC
    #: the engine's :class:`~repro.core.heterogeneity.NodeProgram` -- the
    #: FOURTH round axis (over WHICH nodes, at WHAT speed): per-round
    #: traced compute-rate masks for the local-step scan and payload
    #: drop gates folded into the realized W_r
    #: (:func:`~repro.core.heterogeneity.compose_node_gate` renormalizes
    #: the missing weight into the self-loop, so every realized round
    #: stays symmetric doubly stochastic). Same zero-recompile discipline
    #: as the topology program: one ``node_key`` in ``FLState.comm``,
    #: everything per-round is a traced operand of the ONE compiled round.
    node_program: NodeProgram = HOMOGENEOUS
    #: the engine's :class:`~repro.core.privacy.PrivacySpec` -- the FIFTH
    #: round axis (what the wire does to the PAYLOAD: pairwise transport
    #: pads and/or clip + Gaussian DP noise). Engines that realize it
    #: override :attr:`_priv_rng`; the base engines carry the spec only
    #: so the checkpoint manifest can record/refuse it uniformly.
    privacy: PrivacySpec = PRIVACY_NONE
    #: the engine's :class:`~repro.core.scope.FederationScope` -- the
    #: SIXTH round axis (which bytes EXIST on the wire: the shared
    #: sub-ranges of the flat buffer that gossip mixes; everything else
    #: is a per-node private slice that stays bit-untouched). Engines
    #: that realize it slice the wire stage to the shared columns; the
    #: base engines carry the spec only so the checkpoint manifest can
    #: record/refuse it uniformly.
    scope: FederationScope = SCOPE_FULL

    # -- dynamic-round contract (topology + node programs) -----------------

    @property
    def dynamic_topology(self) -> bool:
        return not self.topology_program.is_static

    @property
    def dynamic_nodes(self) -> bool:
        return not self.node_program.is_static

    @property
    def dynamic_round(self) -> bool:
        """True when ANY per-round traced operand exists (dynamic graph
        or heterogeneous/faulty nodes) -- the condition that selects the
        traced-W round layout."""
        return self.dynamic_topology or self.dynamic_nodes

    @property
    def _priv_rng(self) -> bool:
        """True when the engine REALIZES a privacy transform that
        consumes round-time RNG (pads / DP noise) -- it then carries
        ``priv_key`` + the shared ``topo_round`` counter in
        ``FLState.comm`` so masked/noised rounds are checkpoint-exact.
        Base engines never do; the fused engines override."""
        return False

    @property
    def _scope_round(self) -> bool:
        """True when the scope gates per-round behaviour on the round
        counter (``layerwise:freq=``) -- the engine then carries the
        shared ``topo_round`` counter in ``FLState.comm`` even under a
        static topology, so restores replay the identical gate phase."""
        return self.scope.needs_round

    def _topo_keys(self) -> Tuple[str, ...]:
        """Comm keys the dynamic programs contribute: the shared round
        counter (round index the NEXT comm step will mix under), the
        topology program's base RNG key + Markov state buffers, the
        node program's base RNG key, and the privacy base key -- all
        checkpointed, so a mid-churn / mid-outage / mid-noise restore
        replays the identical round sequence."""
        keys: Tuple[str, ...] = ()
        if self.dynamic_round or self._priv_rng or self._scope_round:
            keys += ("topo_round",)
        if self.dynamic_topology:
            keys += ("topo_key",) + self.topology_program.state_keys()
        if self.dynamic_nodes:
            keys += ("node_key",)
        if self._priv_rng:
            keys += ("priv_key",)
        return keys

    def _topo_sds(self) -> Dict[str, jax.ShapeDtypeStruct]:
        sds = {
            "topo_round": jax.ShapeDtypeStruct((), jnp.int32),
            "topo_key": jax.ShapeDtypeStruct((2,), jnp.uint32),
            "node_key": jax.ShapeDtypeStruct((2,), jnp.uint32),
            "priv_key": jax.ShapeDtypeStruct((2,), jnp.uint32),
        }
        sds.update(self.topology_program.state_sds())
        return sds

    def _topo_init(self) -> Dict[str, jnp.ndarray]:
        init = {
            "topo_round": jnp.int32(0),
            "topo_key": jnp.asarray(self.topology_program.init_key()),
            "node_key": jnp.asarray(self.node_program.init_key()),
            "priv_key": jnp.asarray(self.privacy.init_key()),
        }
        # jnp.asarray: program init states are eager numpy (jit-safe); a
        # raw ndarray leaf would cost one extra executable on round 1.
        init.update({k: jnp.asarray(v)
                     for k, v in self.topology_program.init_state().items()})
        return init

    def _static_round_w(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """The engine's compile-time ``(w_off, w_diag)`` as jnp constants
        -- what :meth:`_round_gates` starts from when the topology is
        static but a node program gates payloads. Engines that never
        materialize a dense W reject node programs at build time
        instead."""
        raise NotImplementedError(
            f"the {self.name!r} engine does not expose its static W; "
            "node programs are unsupported on this build"
        )

    def _round_gates(self, comm: Dict[str, jnp.ndarray]):
        """ONE derivation of the round's realized mixing weights from
        BOTH dynamic axes: the topology program's per-round W (stateful
        Markov churn advances its up/down state here), then the node
        program's payload gate folded in by
        :func:`~repro.core.heterogeneity.compose_node_gate`. Returns
        ``(w_off_r, w_diag_r, new_comm_entries, metrics)`` -- the per-
        round W is a traced OPERAND of the one compiled round, the
        counter/state advance rides in the returned comm entries, and
        the metrics report the realized edge/payload fractions."""
        # a static round without privacy or scope keeps no counter; its
        # gates are the fixed W
        r = comm.get("topo_round")
        new_comm: Dict[str, jnp.ndarray] = (
            {} if r is None else {"topo_round": r + 1})
        metrics: Dict[str, jnp.ndarray] = {}
        topo = self.topology_program
        if self.dynamic_topology:
            key = comm["topo_key"]
            tstate = {k: comm[k] for k in topo.state_keys()}
            w_off_r, w_diag_r, tnew = topo.round_weights_state(r, key, tstate)
            new_comm["topo_key"] = key
            new_comm.update(tnew)
            metrics["edge_fraction"] = topo.edge_fraction(w_off_r)
        else:
            w_off_r, w_diag_r = self._static_round_w()
        if self.dynamic_nodes:
            nkey = comm["node_key"]
            up = self.node_program.wire_gate(r, nkey)
            w_off_r, w_diag_r = compose_node_gate(w_off_r, w_diag_r, up)
            new_comm["node_key"] = nkey
            metrics["payload_fraction"] = jnp.mean(up.astype(jnp.float32))
        if self._priv_rng:
            new_comm["priv_key"] = comm["priv_key"]
        return w_off_r, w_diag_r, new_comm, metrics

    def _priv_comm(self, comm: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """The advanced privacy/scope counter entries for STATIC rounds
        (a dynamic round advances ``topo_round`` in :meth:`_round_gates`,
        which also passes ``priv_key`` through)."""
        if self.dynamic_round or not (self._priv_rng or self._scope_round):
            return {}
        out: Dict[str, jnp.ndarray] = {"topo_round": comm["topo_round"] + 1}
        if self._priv_rng:
            out["priv_key"] = comm["priv_key"]
        return out

    def make_step_mask(self, cfg: FLConfig):
        """The heterogeneous-compute hook for ``_assemble_round``: None
        for homogeneous programs (the scan runs unmasked, zero overhead),
        else ``step_mask(state) -> (q-1, n)`` traced from the round
        counter + node key in ``FLState.comm`` -- stragglers run fewer
        effective local steps as MASKED iterations of the one compiled
        scan."""
        prog = self.node_program
        if getattr(prog, "heterogeneous_wire_k", False) and not getattr(
            self, "supports_wire_k", False
        ):
            raise ValueError(
                f"node program {prog.spec()!r} modulates per-node wire k, "
                f"which the {self.name!r} engine does not support -- use "
                "engine='sharded_fused' (top-k wire with an EF residual)"
            )
        if not prog.heterogeneous_compute or cfg.q <= 1:
            return None

        def step_mask(state: FLState) -> jnp.ndarray:
            return prog.step_gate(
                state.comm["topo_round"], state.comm["node_key"], cfg.q
            )

        return step_mask

    def mix_dynamic(self, buf: PyTree, w_off_r: jnp.ndarray,
                    w_diag_r: jnp.ndarray) -> PyTree:
        """Exact-wire mixing against a TRACED per-round W (engines that
        support dynamic programs on the exact-wire path override this;
        the fused engines take the per-round W as kernel operands
        instead)."""
        raise NotImplementedError(
            f"the {self.name!r} engine does not support dynamic topology "
            "programs on this build"
        )

    # -- protocol ----------------------------------------------------------

    def comm_keys(self, cfg: FLConfig) -> Tuple[str, ...]:
        """Names of the engine's extra wire-state buffers in
        ``FLState.comm`` (shapes/dtypes per :meth:`comm_state_sds`)."""
        return self._topo_keys()

    def comm_state_sds(
        self, cfg: FLConfig
    ) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
        """Shape/dtype of every comm buffer (trace-time safe -- the
        lowering-only dry runs build their state specs from this)."""
        keys = self.comm_keys(cfg)
        if not keys:
            return None
        topo = self._topo_sds()
        buf_keys = [k for k in keys if k not in topo]
        if buf_keys and self.layout is None:
            raise NotImplementedError(
                f"{type(self).__name__} declares comm buffers but no layout"
            )
        sds = (
            jax.ShapeDtypeStruct((cfg.n_nodes, self.layout.total), jnp.float32)
            if self.layout is not None else None
        )
        return {k: topo[k] if k in topo else sds for k in keys}

    def init_comm_state(
        self, cfg: FLConfig, params: PyTree
    ) -> Optional[Dict[str, jnp.ndarray]]:
        """Zero-initialized wire state (zeros = the first round
        effectively transmits the full parameters, and a pipelined
        engine's first in-flight payload dequantizes to nothing); a
        dynamic program's counter starts at round 0 with its base key."""
        sds = self.comm_state_sds(cfg)
        if sds is None:
            return None
        comm = {k: jnp.zeros(s.shape, s.dtype) for k, s in sds.items()}
        comm.update({k: v for k, v in self._topo_init().items() if k in comm})
        return comm

    def local_step(self, params: PyTree, grads: PyTree, alpha,
                   mask=None) -> PyTree:
        """Eq. 4 in the engine's state representation (works unchanged for
        tree state and for the single-leaf flat buffer). The update is
        computed at the wider of (leaf, fp32) and stored back at the
        leaf's dtype -- bf16 flat storage keeps fp32 only in transient
        arithmetic, never in the stored buffer. ``mask`` is the node
        program's (n,) compute gate for this scan iteration: a masked
        node's update is zeroed (it sits the iteration out) without
        touching the compiled scan shape."""
        a = alpha if mask is None else alpha * mask.astype(jnp.float32)

        def upd(p, g):
            am = a if mask is None else a.reshape(
                a.shape + (1,) * (p.ndim - 1)
            )
            return (
                p.astype(jnp.float32) - am * g.astype(jnp.float32)
            ).astype(p.dtype)

        return _tm(upd, params, grads)

    def mix(self, buf: PyTree) -> PyTree:
        """Exact-wire W application (theta <- W theta) on the engine's
        state representation. Fused engines do not expose a standalone
        mix -- their W lives inside the comm-step kernel."""
        raise NotImplementedError(
            f"{type(self).__name__} mixes inside its fused comm step"
        )

    def wire_bytes(self, cfg: FLConfig) -> Optional[float]:
        """Per-round egress summed over all nodes (None: engine does not
        account -- e.g. the tree engine, whose payload depends on the
        pytree; see training.metrics.comm_bytes_per_gossip)."""
        return None

    # -- round building ----------------------------------------------------

    def check_params(self, cfg: FLConfig, params: PyTree) -> None:
        """Validate the initial state representation (called by
        ``init_fl_state``); base checks node-stacking only."""
        leaves = jax.tree_util.tree_leaves(params)
        if not leaves:
            raise ValueError("empty parameter pytree")
        for leaf in leaves:
            if leaf.shape[:1] != (cfg.n_nodes,):
                raise ValueError(
                    f"param leaf {leaf.shape} is not node-stacked for "
                    f"n={cfg.n_nodes}"
                )

    def make_eval_grads(self, grad_fn):
        """Adapt the vmapped per-node grad fn to the engine's state
        representation (identity for tree state)."""
        return grad_fn

    def params_view(self, params: PyTree) -> PyTree:
        """The pytree view of the engine's parameter state (unpacks flat
        buffers; identity for tree state)."""
        if self.layout is None:
            return params
        return unpack(params, self.layout)

    def init_state(self, cfg: FLConfig, params: PyTree) -> FLState:
        from repro.core.fl import init_fl_state

        return init_fl_state(cfg, params, engine=self)

    def _known_comm_keys(self) -> frozenset:
        """EVERY comm key this engine could ever carry (a cfg-independent
        superset of :meth:`comm_keys` over both algorithms and all
        schedule depths) -- what :meth:`restore_comm` validates restored
        dicts against. Engines with wire buffers extend it."""
        return frozenset(
            ("topo_round", "topo_key", "node_key", "priv_key")
            + tuple(self.topology_program.state_keys())
        )

    def _check_restored_comm_keys(
        self, comm: Dict[str, jnp.ndarray]
    ) -> None:
        """Refuse restored comm dicts carrying keys this engine does not
        know: a silent extra key is a forward-compat hazard (state from a
        newer wire contract would be dropped on the floor, then
        re-initialized to something inconsistent on the next save)."""
        unknown = sorted(set(comm) - self._known_comm_keys())
        if unknown:
            raise ValueError(
                f"restored comm state carries keys {unknown} the "
                f"{self.name!r} engine does not know (known: "
                f"{sorted(self._known_comm_keys())}). The checkpoint was "
                "written under a different wire contract -- rebuild the "
                "engine with the checkpoint manifest's engine/schedule/"
                "topology/node-program/privacy specs (training.checkpoint "
                "restores them verbatim), or migrate the comm dict by "
                "dropping keys the manifest marks as derived."
            )

    def restore_comm(
        self, comm: Dict[str, jnp.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """Rebuild DERIVED wire-state buffers after a checkpoint restore
        (identity for engines whose comm buffers are all independent).
        Always validates the restored keys first: unknown keys raise
        (see :meth:`_check_restored_comm_keys`)."""
        self._check_restored_comm_keys(comm)
        return comm

    def is_derived_comm_key(self, key: str) -> bool:
        """True for comm buffers that are DERIVED from the independent
        ones (:meth:`restore_comm` rebuilds them from recon): a
        checkpoint's derived keys may safely be dropped when the restore
        template's comm contract no longer carries them -- e.g. a STATIC
        sharded checkpoint's ``mix_recon`` seeding a dynamic-topology run
        whose contract replaced it with per-direction accumulators."""
        return False

    def make_comm_step(self, eval_grads, schedule, cfg: FLConfig):
        """Default EXACT-WIRE comm step: ``self.mix`` applies W, then the
        optimizer update (mix-then-adapt, the paper's Eqs. 2/3). Under a
        dynamic :class:`~repro.core.dynamics.TopologyProgram` the round's
        W is a TRACED operand -- derived from the ``topo_round`` /
        ``topo_key`` counters in ``FLState.comm`` and applied through
        :meth:`mix_dynamic` -- so ONE compiled round function serves
        every round of the program."""
        wire = self.wire_bytes(cfg)
        dynamic = self.dynamic_round

        def comm_step(state: FLState, batch: PyTree):
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)

            with jax.named_scope(STAGE_WIRE):
                gate_metrics: Dict[str, jnp.ndarray] = {}
                if not dynamic:
                    mix, comm = self.mix, state.comm
                else:
                    w_off_r, w_diag_r, new_entries, gate_metrics = (
                        self._round_gates(state.comm)
                    )
                    mix = lambda buf: self.mix_dynamic(
                        buf, w_off_r, w_diag_r
                    )
                    comm = dict(state.comm)
                    comm.update(new_entries)

                # adapt at fp32, store back at the state dtype (bf16 flat
                # storage narrows only what is STORED, never the
                # arithmetic)
                def adapt(wp, t):
                    return (
                        wp.astype(jnp.float32)
                        - alpha * t.astype(jnp.float32)
                    ).astype(wp.dtype)

                if cfg.algorithm == "dsgd":
                    params = _tm(adapt, mix(state.params), grads)
                    new_state = state._replace(
                        step=step, params=params, comm=comm
                    )
                else:
                    tracker = _tm(
                        lambda wt, gn, gp: wt + gn.astype(wt.dtype) - gp,
                        mix(state.tracker), grads, state.prev_grad,
                    )
                    params = _tm(adapt, mix(state.params), tracker)
                    new_state = state._replace(
                        step=step,
                        params=params,
                        tracker=tracker,
                        prev_grad=_tm(
                            lambda g, p: g.astype(p.dtype), grads,
                            state.prev_grad,
                        ),
                        comm=comm,
                    )

            return new_state, self._round_metrics(
                cfg, losses, grads, alpha, new_state, wire, gate_metrics
            )

        return comm_step

    def _round_metrics(self, cfg: FLConfig, losses, grads, alpha,
                       new_state: FLState, egress, gate_metrics):
        """The comm step's metrics dict, under the ``fl_metrics`` stage:
        mean loss, ||mean_i grad_i||^2, consensus error, alpha,
        comm_rounds, ``wire_bytes`` (when the engine accounts its wire,
        ``egress`` not None), the engine's state metrics, then the round's
        topology and node gate metrics. ``losses`` is the per-site losses,
        or ``(losses, counters)`` from a loss that counts: each counter's
        mean over sites joins the dict."""
        counters = {}
        if isinstance(losses, tuple):
            losses, counters = losses
        with jax.named_scope(STAGE_METRICS):
            metrics = {
                "loss": jnp.mean(losses),
                "alpha": alpha,
                "grad_norm_sq": _mean_grad_norm_sq(grads),
                "consensus_err": _consensus_error(new_state.params),
                "comm_rounds": jnp.float32(1.0),
            }
            if egress is not None:
                metrics["wire_bytes"] = jnp.float32(egress)
            metrics.update(self._state_metrics(cfg, new_state))
            metrics.update({k: jnp.mean(v) for k, v in counters.items()})
        metrics.update(gate_metrics)
        return metrics

    def _state_metrics(self, cfg: FLConfig,
                       new_state: FLState) -> Dict[str, jnp.ndarray]:
        """Metrics of the engine's own wire state (none for the exact
        wire)."""
        return {}

    def make_pipelined_round(self, eval_grads, schedule, cfg: FLConfig):
        """The split comm machinery the :class:`PipelinedSchedule` needs:
        ``(ingest, comm_step)`` where ``ingest(state)`` issues the
        collective on the IN-FLIGHT payload (None for engines whose mix
        has no separate collective) and ``comm_step(state, batch, stale)``
        produces this round's payload and mixes with the stale neighbor
        term. Exact-wire engines do not implement it."""
        raise ValueError(
            f"the {self.name!r} engine is sequential-only; the pipelined "
            "schedule needs the fused engines' split produce/collective "
            "comm step (use 'fused' or 'sharded_fused')"
        )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type[GossipEngine]] = {}


def register_engine(cls: Type[GossipEngine]) -> Type[GossipEngine]:
    """Class decorator: make ``cls`` resolvable by ``get_engine(cls.name)``.
    The registry is the ONE list of engine names every CLI / example /
    checkpoint manifest consults -- never hardcode the strings."""
    if cls.name in _REGISTRY:
        raise ValueError(f"duplicate engine name {cls.name!r}")
    _REGISTRY[cls.name] = cls
    return cls


def get_engine(name: str) -> Type[GossipEngine]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {engine_names()}"
        ) from None


def engine_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Exact-wire engines
# ---------------------------------------------------------------------------


@register_engine
class TreeEngine(GossipEngine):
    """Node-stacked pytree state; mixing delegated to any tree-level
    gossip backend from ``core.mixing`` (dense-W simulated, mesh
    ppermute, all-gather)."""

    name = "tree"

    def __init__(self, gossip: GossipFn):
        self._gossip = gossip

    def mix(self, tree: PyTree) -> PyTree:
        return self._gossip(tree)

    @classmethod
    def simulated(cls, w: np.ndarray, stacked_params: PyTree, *,
                  wire_dtype=None, topk=None, round_schedule=None,
                  storage_dtype=None, topology_program=None,
                  node_program=None, privacy=None, scope=None, **_ignored):
        """Single-host build: dense-W backend; state stays the input tree."""
        _reject_scope(scope, cls.name)
        _reject_topk(topk, cls.name)
        _require_sequential(round_schedule, cls.name)
        _reject_storage_dtype(storage_dtype, cls.name)
        _reject_privacy(
            privacy, cls.name,
            "engine's pytree wire has no quantize epilogue to pad or "
            "noise",
        )
        _reject_dynamic_program(
            topology_program, cls.name,
            "engine bakes W into its tree-level gossip backend",
        )
        _reject_node_program(
            node_program, cls.name,
            "engine bakes W into its tree-level gossip backend",
        )
        return cls(make_dense_gossip(w, wire_dtype)), stacked_params

    @classmethod
    def from_mesh(cls, mesh: Mesh, node_axes: Sequence[str], stacked_sds,
                  *, specs=None, wire_dtype=None, axes_subset=None,
                  topk=None, round_schedule=None, storage_dtype=None,
                  topology_program=None, node_program=None, privacy=None,
                  scope=None, **_ignored):
        _reject_scope(scope, cls.name)
        _reject_topk(topk, cls.name)
        _require_sequential(round_schedule, cls.name)
        _reject_storage_dtype(storage_dtype, cls.name)
        _reject_privacy(
            privacy, cls.name,
            "engine's pytree wire has no quantize epilogue to pad or "
            "noise",
        )
        _reject_dynamic_program(
            topology_program, cls.name,
            "engine bakes W into its tree-level gossip backend",
        )
        _reject_node_program(
            node_program, cls.name,
            "engine bakes W into its tree-level gossip backend",
        )
        if specs is None:
            raise ValueError("tree engine from_mesh needs the param specs")
        return cls(
            make_mesh_gossip(mesh, node_axes, specs, wire_dtype=wire_dtype,
                             axes_subset=axes_subset)
        )


@register_engine
class FlatEngine(GossipEngine):
    """The state is ONE packed ``(nodes, total)`` buffer end to end;
    mixing is a flat-native backend (one matmul / one ppermute per torus
    direction / one all-gather per round, independent of leaf count).

    ``storage_dtype`` selects the buffer's STORAGE precision
    (``layout.storage_dtype``): the fp32 default is lossless; bf16
    halves the HBM traffic of every buffer-wide op -- the flat mixing
    backends already accumulate their weighted sum in fp32 and cast back
    to the buffer dtype, so only storage narrows, never the mix
    accumulator (equivalence vs fp32 at relaxed tolerance is tested in
    tests/test_schedule.py; the HBM-traffic win is a bench row)."""

    name = "flat"

    def __init__(self, mix_fn: Callable[[jnp.ndarray], jnp.ndarray],
                 layout: FlatLayout, *, topology_program=None,
                 node_program=None, wire_dtype=None, w=None, privacy=None):
        self._mix = mix_fn
        self.layout = layout
        self.topology_program = resolve_program(topology_program)
        self.node_program = resolve_node_program(node_program)
        # The flat engine GAINS the privacy knob but realizes only the
        # vacuous half: its simulated wire is one in-process matmul, so
        # secure_agg is trivially satisfied (no per-edge payload exists
        # to intercept) and is accepted as a no-op; DP is refused at the
        # build sites (no EF epilogue to absorb the noise).
        self.privacy = _reject_dp(
            privacy, self.name, "engine ships an exact un-quantized wire "
            "with no error-feedback residual"
        )
        self._wire_dtype = wire_dtype
        self._w_np = None if w is None else np.asarray(w, dtype=np.float64)
        if self.dynamic_topology and not self.topology_program.bound:
            raise ValueError(
                "a dynamic FlatEngine needs the program bound to the base "
                "W (use FlatEngine.simulated, which binds it)"
            )
        if self.dynamic_nodes:
            if self._w_np is None:
                raise ValueError(
                    "a FlatEngine under a node program needs the dense W "
                    "(use FlatEngine.simulated, which passes it)"
                )
            self.node_program = self.node_program.bind(self._w_np.shape[0])

    def _static_round_w(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        _, w_self, w_off = _split_w_np(self._w_np, self._w_np.shape[0])
        return jnp.asarray(w_off, jnp.float32), jnp.asarray(
            w_self, jnp.float32
        )

    @property
    def storage_dtype(self):
        return jnp.dtype(self.layout.storage_dtype)

    def mix(self, flat: jnp.ndarray) -> jnp.ndarray:
        return self._mix(flat)

    def mix_dynamic(self, flat: jnp.ndarray, w_off_r: jnp.ndarray,
                    w_diag_r: jnp.ndarray) -> jnp.ndarray:
        """Dense flat mixing against the TRACED per-round W: same
        fp32-accumulate / wire-dtype semantics as ``make_dense_flat_mix``
        with the traced ``(w_off_r, w_diag_r)`` in place of the baked
        constants -- one matmul, no recompiles across rounds."""
        from repro.core.mixing import _wire

        xf = flat.astype(jnp.float32)
        sent = _wire(xf, self._wire_dtype)
        return (w_off_r @ sent + w_diag_r[:, None] * xf).astype(flat.dtype)

    def check_params(self, cfg: FLConfig, params: PyTree) -> None:
        _check_flat_params(cfg, params, self.name)

    def make_eval_grads(self, grad_fn):
        return _make_flat_eval_grads(self.layout, grad_fn)

    @classmethod
    def simulated(cls, w: np.ndarray, stacked_params: PyTree, *,
                  scale_chunk: int = 1, wire_dtype=None, topk=None,
                  round_schedule=None, storage_dtype=None,
                  topology_program=None, node_program=None, privacy=None,
                  scope=None, **_ignored):
        _reject_scope(scope, cls.name)
        _reject_topk(topk, cls.name)
        _require_sequential(round_schedule, cls.name)
        prog = resolve_program(topology_program).bind(w)
        flat, layout = pack(stacked_params, pad_to=scale_chunk,
                            buffer_dtype=storage_dtype or jnp.float32)
        return cls(make_dense_flat_mix(w, wire_dtype), layout,
                   topology_program=prog, node_program=node_program,
                   wire_dtype=wire_dtype, w=w, privacy=privacy), flat

    @classmethod
    def from_mesh(cls, mesh: Mesh, node_axes: Sequence[str], stacked_sds,
                  *, wire_dtype=None, axes_subset=None, scale_chunk: int = 512,
                  topk=None, round_schedule=None, storage_dtype=None,
                  topology_program=None, node_program=None, privacy=None,
                  scope=None, **_ignored):
        _reject_scope(scope, cls.name)
        _reject_topk(topk, cls.name)
        _require_sequential(round_schedule, cls.name)
        _reject_privacy(
            privacy, cls.name,
            "engine's mesh build ships raw fp32 payloads through a baked "
            "ppermute backend (no pad/noise epilogue)",
        )
        _reject_dynamic_program(
            topology_program, cls.name,
            "engine's mesh build mixes through a baked ppermute backend",
        )
        _reject_node_program(
            node_program, cls.name,
            "engine's mesh build mixes through a baked ppermute backend",
        )
        layout = pack_layout(stacked_sds, pad_to=scale_chunk,
                             storage_dtype=storage_dtype or jnp.float32)
        return cls(
            make_mesh_flat_mix(mesh, node_axes, wire_dtype=wire_dtype,
                               axes_subset=axes_subset),
            layout,
        )


# ---------------------------------------------------------------------------
# Fused engines
# ---------------------------------------------------------------------------


_WIRE_DTYPE_MSG = (
    "the fused engines' wire is always difference-coded int8; wire_dtype "
    "only applies to the tree/flat exact-wire engines"
)


def _reject_wire_dtype(wire_dtype) -> None:
    if wire_dtype is not None:
        raise ValueError(_WIRE_DTYPE_MSG)


def _reject_topk(topk, name: str) -> None:
    if topk is not None:
        raise ValueError(
            f"topk is a fused-engine knob (sub-int8 sparsified wire); the "
            f"{name!r} engine ships an exact wire -- use 'fused' or "
            "'sharded_fused'"
        )


def _reject_dynamic_program(program, name: str, reason: str) -> TopologyProgram:
    """Resolve a topology-program spec and refuse non-static programs on
    builds that cannot trace per-round weights (returns the resolved
    STATIC program otherwise, so callers can store it uniformly)."""
    prog = resolve_program(program)
    if not prog.is_static:
        raise ValueError(
            f"topology program {prog.spec()!r} needs traced per-round "
            f"mixing weights; the {name!r} {reason} -- use the 'fused' "
            "engine (any W) or 'sharded_fused' on the circulant wire"
        )
    return prog


def _reject_node_program(program, name: str, reason: str) -> NodeProgram:
    """Resolve a node-program spec and refuse non-homogeneous programs
    on builds that cannot trace per-round gates (same discipline as
    :func:`_reject_dynamic_program`)."""
    prog = resolve_node_program(program)
    if not prog.is_static:
        raise ValueError(
            f"node program {prog.spec()!r} needs traced per-round "
            f"compute/payload gates; the {name!r} {reason} -- use the "
            "'flat' (simulated), 'fused', or 'sharded_fused' engine"
        )
    return prog


def _reject_privacy(privacy, name: str, reason: str) -> PrivacySpec:
    """Resolve a privacy spec and refuse ACTIVE specs on engines whose
    wire cannot realize them (returns the resolved inactive spec
    otherwise, same discipline as :func:`_reject_dynamic_program`)."""
    p = resolve_privacy(privacy)
    if p.active:
        raise ValueError(
            f"privacy spec {p.spec()!r}: the {name!r} {reason} -- use "
            "'fused' (dp; secure_agg is vacuously satisfied in-process) "
            "or 'sharded_fused' on the circulant wire (dp + secure_agg)"
        )
    return p


def _reject_scope(scope, name: str) -> FederationScope:
    """Resolve a federation-scope spec and refuse non-full scopes on
    engines whose wire cannot slice the buffer (returns the resolved
    FULL scope otherwise, same discipline as the other axis rejects)."""
    s = resolve_scope(scope)
    if not s.is_full:
        raise ValueError(
            f"federation scope {s.spec()!r}: the {name!r} engine ships "
            "the whole state through a baked exact-wire backend (no "
            "column slicing) -- use the 'fused' engine, or "
            "'sharded_fused' for sub-range scopes on the mesh wire"
        )
    return s


def _reject_dp(privacy, name: str, reason: str) -> PrivacySpec:
    """Resolve a privacy spec, allowing ``secure_agg`` (a no-op where
    no per-edge payload ever exists to read) but refusing DP on engines
    without the EF quantize epilogue that absorbs the noise."""
    p = resolve_privacy(privacy)
    if p.dp:
        raise ValueError(
            f"privacy spec {p.spec()!r}: the {name!r} {reason}, so DP "
            "noise would accumulate unabsorbed -- use the 'fused' or "
            "'sharded_fused' engine (error-feedback wire epilogue)"
        )
    return p


def _reject_storage_dtype(storage_dtype, name: str) -> None:
    if storage_dtype is not None and jnp.dtype(storage_dtype) != jnp.float32:
        raise ValueError(
            f"storage_dtype is a flat-buffer knob (bf16 buffer with fp32 "
            f"mix accumulation); the {name!r} engine has no flat buffer "
            "-- use 'flat', 'fused', or 'sharded_fused'"
        )


#: storage dtypes the FUSED engines accept: the params/tracker buffer may
#: be stored narrow (halving its HBM traffic), but the EF recon/residual
#: wire state stays fp32 regardless -- the residual must not be rounded.
_FUSED_STORAGE_DTYPES = ("float32", "bfloat16")


def _split_w_np(w: np.ndarray, n: int):
    """Shape-checked (w, diag, off-diag) via ``mixing._split_w``."""
    w = np.asarray(w, dtype=np.float64)
    if w.shape != (n, n):
        raise ValueError(f"W shape {w.shape} != ({n}, {n})")
    w_self, w_off = _split_w(w)
    return w, w_self, w_off


def _degrees(w: np.ndarray) -> np.ndarray:
    return (np.abs(w - np.diag(np.diag(w))) > 0).sum(axis=1)


def _dequant(q: jnp.ndarray, scales: jnp.ndarray, scale_chunk: int):
    """(n, t) int8 + (n, t//chunk) fp32 scales -> (n, t) fp32."""
    n, t = q.shape
    q3 = q.astype(jnp.float32).reshape(n, t // scale_chunk, scale_chunk)
    return (q3 * scales[:, :, None]).reshape(n, t)


class _FusedBase(GossipEngine):
    """Shared knobs + validation of the fused (CHOCO int8 wire) engines."""

    def __init__(self, layout: FlatLayout, *, scale_chunk: int = 512,
                 topk: Optional[int] = None, error_feedback: bool = True,
                 difference_coding: bool = True, impl: str = "pallas",
                 round_schedule=None, topology_program=None,
                 node_program=None, privacy=None, scope=None):
        if impl not in ("pallas", "jnp"):
            raise ValueError(f"unknown impl {impl!r}")
        if impl == "pallas":
            from repro.kernels.gossip.ops import require_topk_lowering

            require_topk_lowering(topk)
        if scale_chunk < 1:
            raise ValueError("scale_chunk must be >= 1")
        if topk is not None and not (1 <= topk):
            raise ValueError("topk must be >= 1 or None")
        if layout.total % scale_chunk:
            raise ValueError(
                f"layout.total {layout.total} not a multiple of scale_chunk "
                f"{scale_chunk}; pack with pad_to={scale_chunk}"
            )
        if jnp.dtype(layout.storage_dtype).name not in _FUSED_STORAGE_DTYPES:
            raise ValueError(
                f"the {self.name!r} engine stores the flat buffer in "
                f"{_FUSED_STORAGE_DTYPES} only (got "
                f"{jnp.dtype(layout.storage_dtype).name!r}); the wire math "
                "and the EF recon/residual state run fp32 either way"
            )
        self.layout = layout
        #: params/tracker storage dtype; wire math always accumulates fp32
        self._store = jnp.dtype(layout.storage_dtype)
        self.scale_chunk = scale_chunk
        self.topk = topk
        self.error_feedback = error_feedback
        self.difference_coding = difference_coding
        self.impl = impl
        self.round_schedule = resolve_schedule(round_schedule)
        self.topology_program = resolve_program(topology_program)
        self.node_program = resolve_node_program(node_program)
        self.privacy = resolve_privacy(privacy)
        if self.privacy.dp and not error_feedback:
            raise ValueError(
                "dp noise rides the EF residual (res-substitution in the "
                "wire-stage epilogue); build the engine with "
                "error_feedback=True or drop the dp token"
            )
        self.scope = resolve_scope(scope)
        # -- scoped geometry: which COLUMNS of the flat buffer the wire
        # sees. A sub-range scope (backbone / ranges) gathers the shared
        # columns into a contiguous chunk-aligned wire buffer, runs the
        # UNMODIFIED wire kernels on it, and scatters the mixed result
        # back around the untouched private columns -- so recon /
        # residual / collectives / wire bytes all shrink to the shared
        # slice. The layerwise scope keeps the full wire (bytes
        # unchanged, recon stays consistent) and gates only the
        # head-column MIX on the traced round counter.
        self._scoped = not self.scope.is_full and not self.scope.needs_round
        self._gate_mask = None
        if self._scoped:
            shared = self.scope.shared_ranges(layout)
            self._wire_layout, self._local_ranges = scoped_layout(
                layout, shared, scale_chunk
            )
            self._local_shared = sum(b - a for a, b in self._local_ranges)
            self._local_padded = self._wire_layout.shard_width
        else:
            self._wire_layout = layout
            self._local_ranges = ((0, layout.shard_width),)
            self._local_shared = self._local_padded = layout.shard_width
            if isinstance(self.scope, LayerwiseScope):
                gate = np.zeros((1, layout.total), np.bool_)
                for a, b in self.scope.gate_ranges(layout):
                    gate[:, a:b] = True
                self._gate_mask = jnp.asarray(gate)

    # -- scope hooks --------------------------------------------------------

    @property
    def wire_layout(self) -> FlatLayout:
        """The layout the WIRE operates at: ``layout`` itself for the
        full / layerwise scopes, the gathered shared-slice layout for
        sub-range scopes. Comm-state widths, wire-byte accounting, and
        DP noise all derive from this, so a scoped wire shrinks every
        one of them proportionally."""
        return self._wire_layout

    def _scope_shards(self, width: int) -> int:
        """How many shard tiles a buffer of trailing ``width`` spans.

        The scoped ranges are PER-SHARD (``scoped_layout`` guarantees
        uniformity); a full-width row (the fused dense path) repeats
        them across every shard, a per-tile row (the shard_map body)
        carries exactly one copy. Width disambiguates: with shards > 1
        the tile width ``shard_width`` differs from ``total``."""
        return 1 if width == self.layout.shard_width else self.layout.shards

    def _gather_cols(self, x: jnp.ndarray) -> jnp.ndarray:
        """Gather the SHARED columns of a buffer row-block (full-width
        or one shard tile) into the contiguous wire buffer, repeating
        the per-shard ranges across shards and zero-padding each
        shard's slice to the chunk multiple (padding behaves exactly
        like the layout's structural tail padding -- zero forever, zero
        wire mass)."""
        if not self._scoped:
            return x
        sw = self.layout.shard_width
        pad = self._local_padded - self._local_shared
        segs = []
        for s in range(self._scope_shards(x.shape[-1])):
            base = s * sw
            segs.extend(
                jax.lax.slice_in_dim(x, base + a, base + b, axis=-1)
                for a, b in self._local_ranges
            )
            if pad:
                segs.append(jnp.zeros(x.shape[:-1] + (pad,), x.dtype))
        return jnp.concatenate(segs, axis=-1)

    def _scatter_cols(self, local_full: jnp.ndarray,
                      mixed_scoped: jnp.ndarray) -> jnp.ndarray:
        """Interleave the mixed SHARED columns back into the locally
        updated full-width row-block: private columns come bit-untouched
        from ``local_full``, shared columns from the wire's mix (the
        wire buffer's zero per-shard tail padding is dropped)."""
        sw = self.layout.shard_width
        segs = []
        for s in range(self._scope_shards(local_full.shape[-1])):
            base = s * sw
            pos_full = base
            pos_s = s * self._local_padded
            for a, b in self._local_ranges:
                if base + a > pos_full:
                    segs.append(jax.lax.slice_in_dim(
                        local_full, pos_full, base + a, axis=-1))
                segs.append(jax.lax.slice_in_dim(
                    mixed_scoped, pos_s, pos_s + (b - a), axis=-1))
                pos_s += b - a
                pos_full = base + b
            if pos_full < base + sw:
                segs.append(jax.lax.slice_in_dim(
                    local_full, pos_full, base + sw, axis=-1))
        return jnp.concatenate(segs, axis=-1)

    def _scope_finish(self, mixed_s: jnp.ndarray, x: jnp.ndarray,
                      g: jnp.ndarray, alpha, fire=None) -> jnp.ndarray:
        """DSGD round epilogue under a scope: rebuild the full-width fp32
        params from the kernel's mixed output. Sub-range scopes scatter
        the (wire-width) mix around the private columns' plain local
        update ``x - alpha g``; the layerwise scope SELECTS the local
        update on the gated head columns when the round does not fire
        (an exact where, so non-firing rounds leave the head bit-equal
        to a never-gossiped trajectory). Full scope is the identity."""
        if not self._scoped and fire is None:
            return mixed_s
        local = self._f32(x) - alpha * self._f32(g)
        if self._scoped:
            return self._scatter_cols(local, mixed_s)
        return jnp.where(self._gate_mask & ~fire, local, mixed_s)

    def _scope_finish_gt(self, mx_s: jnp.ndarray, mt_s: jnp.ndarray,
                         x: jnp.ndarray, t: jnp.ndarray, g: jnp.ndarray,
                         gp: jnp.ndarray, alpha, fire=None):
        """DSGT twin of :meth:`_scope_finish`: the private columns'
        tracker follows the unmixed recursion ``t + g - g_prev`` and the
        params follow ``x - alpha * tracker`` -- identical to what the
        kernel computes on those columns minus the W contraction."""
        if not self._scoped and fire is None:
            return mx_s, mt_s
        th = self._f32(t) + self._f32(g) - self._f32(gp)
        xl = self._f32(x) - alpha * th
        if self._scoped:
            return self._scatter_cols(xl, mx_s), self._scatter_cols(th, mt_s)
        keep = self._gate_mask & ~fire
        return jnp.where(keep, xl, mx_s), jnp.where(keep, th, mt_s)

    def _scope_fire(self, comm: Dict[str, jnp.ndarray]):
        """The layerwise scope's traced gate for THIS round (None when
        the scope never gates) -- derived from the checkpointed round
        counter, so one compiled round serves every phase of the
        frequency."""
        if not self._scope_round:
            return None
        return self.scope.fire(comm["topo_round"])

    # -- privacy hooks ------------------------------------------------------

    @property
    def _dp(self) -> bool:
        return self.privacy.dp

    @property
    def _sa_wire(self) -> bool:
        """True when this build physically masks a transported payload
        (only the sharded circulant wire does; the dense single-host
        engines have no per-edge transport, so their secure_agg is
        vacuously satisfied and numerically a no-op)."""
        return False

    @property
    def _priv_rng(self) -> bool:
        return self._dp or self._sa_wire

    def _noise_scale(self) -> float:
        """Gaussian-mechanism std: ``sigma * clip``."""
        return float(self.privacy.dp_sigma * self.privacy.dp_clip)

    def _dp_kwargs(self):
        """The ``dp_clip`` kwarg forwarded to the wire-stage kernels
        (the noise arrays are per-round traced operands)."""
        return {"dp_clip": float(self.privacy.dp_clip)} if self._dp else {}

    def _dp_noise_full(self, comm: Dict[str, jnp.ndarray], n: int,
                       tracker: bool = False) -> jnp.ndarray:
        """This round's (n, total) Gaussian draw from the checkpointed
        privacy counter -- the fused engine's whole-matrix twin of the
        sharded per-row draw (bitwise-identical rows: the element
        counter is global)."""
        from repro.core.privacy import NOISE_STREAM

        stream = NOISE_STREAM + (TRACKER_STREAM_OFFSET if tracker else 0)
        return dp_noise(
            comm["priv_key"], comm["topo_round"], jnp.arange(n),
            self.wire_layout.total, self._noise_scale(), stream=stream,
        )

    def _privacy_metrics(self, cfg: FLConfig, new_state: FLState):
        """The (epsilon, delta) moments bound over the WIRE RELEASES so
        far: noise is drawn once per comm round (``step / q`` rounds,
        the q local steps between rounds release nothing), and the DSGT
        round releases TWO noised wires (x and tracker), doubling its
        per-round composition count."""
        if not self._dp:
            return {}
        wires = 2 if cfg.algorithm == "dsgt" else 1
        return {
            "dp_epsilon": epsilon_traced(
                self.privacy.dp_sigma,
                (new_state.step // cfg.q) * wires,
                self.privacy.delta,
            )
        }

    def _known_comm_keys(self) -> frozenset:
        return super()._known_comm_keys() | frozenset(
            base + suffix
            for base in ("recon", "residual", "wire_q", "wire_scales")
            for suffix in ("", "_t")
        )

    @property
    def pipelined(self) -> bool:
        """True for every non-blocking schedule (depth >= 1): the round
        splits into produce / collective / stale mix."""
        return self.round_schedule.depth >= 1

    @property
    def staleness_depth(self) -> int:
        return self.round_schedule.depth

    def _static_w_np(self) -> np.ndarray:
        """The engine's compile-time dense W (the fused engine's ``w``,
        the sharded engine's dense equivalent)."""
        raise NotImplementedError

    def _static_round_w(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        w = self._static_w_np()
        _, w_self, w_off = _split_w_np(w, w.shape[0])
        return jnp.asarray(w_off, jnp.float32), jnp.asarray(
            w_self, jnp.float32
        )

    # -- depth-k ring-buffer helpers ---------------------------------------
    #
    # Ring convention (both fused engines): slot 0 is the OLDEST in-flight
    # payload, slot -1 the newest. The consumer reads slot 0; the producer
    # appends at the end, dropping the consumed slot -- one concatenate on
    # the leading-(n) comm buffers, no collective touches more than ONE
    # slot per round (the wire-byte invariant tools/bench_guard.py guards).

    def _ring_slot0(self, comm: Dict[str, jnp.ndarray],
                    keys: Sequence[str]) -> Tuple[jnp.ndarray, ...]:
        """The oldest in-flight payload's buffers: the (n, width) buffers
        themselves at depth 1 (the pipelined double-buffer layout,
        unchanged), the ``[:, 0]`` ring slice at depth >= 2."""
        if self.staleness_depth <= 1:
            return tuple(comm[k] for k in keys)
        return tuple(comm[k][:, 0] for k in keys)

    def _push_wire(self, old_comm: Dict[str, jnp.ndarray],
                   comm: Dict[str, jnp.ndarray], keys: Sequence[str],
                   vals: Sequence[jnp.ndarray]) -> None:
        """Store this round's produced payload: replace at depth 1, ring
        push (drop slot 0, append at the end) at depth >= 2."""
        if self.staleness_depth <= 1:
            comm.update(zip(keys, vals))
            return
        for k, v in zip(keys, vals):
            comm[k] = jnp.concatenate(
                [old_comm[k][:, 1:], v[:, None]], axis=1
            )

    def check_params(self, cfg: FLConfig, params: PyTree) -> None:
        _check_flat_params(cfg, params, self.name)

    def make_eval_grads(self, grad_fn):
        return _make_flat_eval_grads(self.layout, grad_fn)

    def _kernel_kwargs(self):
        return dict(
            scale_chunk=self.scale_chunk,
            error_feedback=self.error_feedback,
            difference_coding=self.difference_coding,
            topk=self.topk,
        )

    def _edge_bytes(self) -> int:
        """Wire bytes one node ships to ONE neighbor per wire per round
        (the SCOPED wire width -- a sub-range scope shrinks it)."""
        return flat_wire_bytes(
            self.wire_layout, 1, self.scale_chunk, self.topk
        )

    # -- narrow-storage helpers --------------------------------------------
    #
    # storage_dtype='bfloat16' stores the params/tracker buffer narrow;
    # every wire-stage input upcasts to fp32 at the kernel boundary
    # (_f32) and every mixed output is stored back narrow (_st), so the
    # int8 wire, the EF recon/residual, and the mix accumulation are
    # bit-for-bit the fp32 computation of the ROUNDED buffer.

    def _f32(self, x: jnp.ndarray) -> jnp.ndarray:
        return x if x.dtype == jnp.float32 else x.astype(jnp.float32)

    def _st(self, x: jnp.ndarray) -> jnp.ndarray:
        return x if x.dtype == self._store else x.astype(self._store)

    def _state_metrics(self, cfg: FLConfig,
                       new_state: FLState) -> Dict[str, jnp.ndarray]:
        """``ef_residual_rms``, the RMS of the parameter-wire EF residual
        -- the adaptive-k signal (``topk_schedule``): a large residual
        means the wire is dropping mass faster than EF re-injects it, so
        the schedule densifies k -- and the privacy metrics."""
        res = new_state.comm["residual"]
        return {
            "ef_residual_rms": jnp.sqrt(
                jnp.mean(res.astype(jnp.float32) ** 2)
            ),
            **self._privacy_metrics(cfg, new_state),
        }


@register_engine
class FusedEngine(_FusedBase):
    """The round megakernel on a dense compile-time W: local update + int8
    quantize (top-k sparsified when ``topk`` is set) + W-row mix + error
    feedback, ONE Pallas call per comm round (``kernels.gossip``;
    ``impl="jnp"`` runs the bit-identical chunked oracle, which is what
    GSPMD partitions in the sharded dry run)."""

    name = "fused"

    def __init__(self, w: np.ndarray, layout: FlatLayout, **kw):
        super().__init__(layout, **kw)
        self.w = np.asarray(w, dtype=np.float64)
        # binding validates per-round Assumption 1 over a sample of the
        # program's emitted rounds (core.dynamics.validate_program)
        self.topology_program.bind(self.w)
        self.node_program = self.node_program.bind(self.w.shape[0])

    def _static_w_np(self) -> np.ndarray:
        return self.w

    def _ring_depth(self) -> int:
        """Ring slots the DENSE engine needs for depth-k staleness: its
        recon buffer already lags the mix by construction (the ``k=1``
        ``stale_mix`` kernel needs ZERO extra buffers), so with
        difference coding the k-round-stale reconstruction is recovered
        by subtracting the last k-1 in-flight payloads from recon
        (``recon^(r-1) - sum dq^(r-1..r-k+1) == recon^(r-k)`` exactly);
        without difference coding recon IS the last payload, so the ring
        holds k and the mix reads the oldest slot."""
        k = self.staleness_depth
        if k <= 1:
            return 0
        return k - 1 if self.difference_coding else k

    def comm_keys(self, cfg: FLConfig) -> Tuple[str, ...]:
        keys = ("recon", "residual")
        if self._ring_depth():
            keys += ("wire_q", "wire_scales")
        if cfg.algorithm == "dsgt":
            keys += ("recon_t", "residual_t")
            if self._ring_depth():
                keys += ("wire_q_t", "wire_scales_t")
        return keys + self._topo_keys()

    def comm_state_sds(
        self, cfg: FLConfig
    ) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
        # wire state (recon / residual / in-flight rings) lives at the
        # SCOPED wire width: a sub-range scope shrinks every buffer
        n, t = cfg.n_nodes, self.wire_layout.total
        rd = self._ring_depth()
        topo = self._topo_sds()

        def buf(key):
            if key in topo:
                return topo[key]
            if key.startswith("wire_q"):
                return jax.ShapeDtypeStruct((n, rd, t), jnp.int8)
            if key.startswith("wire_scales"):
                return jax.ShapeDtypeStruct(
                    (n, rd, t // self.scale_chunk), jnp.float32
                )
            return jax.ShapeDtypeStruct((n, t), jnp.float32)

        keys = self.comm_keys(cfg)
        return {k: buf(k) for k in keys} or None

    def wire_bytes(self, cfg: FLConfig) -> float:
        wires = 2 if cfg.algorithm == "dsgt" else 1
        return float(wires * _degrees(self.w).sum() * self._edge_bytes())

    def make_comm_step(self, eval_grads, schedule, cfg: FLConfig):
        if self._ring_depth():
            return self._make_bounded_comm_step(eval_grads, schedule, cfg)
        _, w_self, w_off = _split_w_np(self.w, cfg.n_nodes)
        if self.impl == "pallas":
            from repro.kernels.gossip.ops import fused_round, fused_round_gt
        else:
            from repro.kernels.gossip.ref import (
                fused_round_gt_ref as fused_round_gt,
                fused_round_ref as fused_round,
            )
        # Pipelined: the kernel's stale_mix flag contracts W against the
        # INPUT recon -- which IS the neighbor reconstruction as of the
        # end of the previous round -- so the dense engine needs no extra
        # in-flight buffers: it is the exact single-host oracle of the
        # sharded pipelined round. (Bounded staleness at k=1 lands here
        # too -- it IS the pipelined round, bit-identically.)
        kw = dict(self._kernel_kwargs(), stale_mix=self.pipelined)
        egress = self.wire_bytes(cfg)
        dynamic = self.dynamic_round
        dp = self._dp
        n = cfg.n_nodes

        def comm_step(state: FLState, batch: PyTree):
            if state.comm is None:
                raise ValueError(
                    "fused rounds need init_fl_state(..., engine=...)"
                )
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)

                # Dynamic topology / node gates: the kernels already take
                # (w_off, w_self) as runtime operands, so the per-round
                # realized W is simply the traced program output -- same
                # kernel, same compilation, all rounds.
                gate_metrics: Dict[str, jnp.ndarray] = {}
                if dynamic:
                    w_off_r, w_self_r, topo_comm, gate_metrics = (
                        self._round_gates(state.comm)
                    )
                else:
                    w_off_r, w_self_r = w_off, w_self
                    topo_comm = self._priv_comm(state.comm)
                dpkw = dict(self._dp_kwargs())
                if dp:
                    dpkw["dp_noise"] = self._dp_noise_full(state.comm, n)
                # Scope: the kernel runs UNCHANGED on the gathered shared
                # columns; private columns never enter it and are rebuilt by
                # _scope_finish[_gt] from the plain local update.
                fire = self._scope_fire(state.comm)

                if cfg.algorithm == "dsgd":
                    mixed, recon, res, _ = fused_round(
                        self._gather_cols(self._f32(state.params)),
                        self._gather_cols(grads), state.comm["recon"],
                        state.comm["residual"], w_off_r, w_self_r, alpha,
                        **kw, **dpkw,
                    )
                    mixed = self._scope_finish(
                        mixed, state.params, grads, alpha, fire
                    )
                    new_state = state._replace(
                        step=step, params=self._st(mixed),
                        comm={"recon": recon, "residual": res, **topo_comm},
                    )
                else:
                    if dp:
                        dpkw["dp_noise_t"] = self._dp_noise_full(
                            state.comm, n, tracker=True
                        )
                    mx, mt, nrx, nsx, nrt, nst, _, _ = fused_round_gt(
                        self._gather_cols(self._f32(state.params)),
                        self._gather_cols(self._f32(state.tracker)),
                        self._gather_cols(grads),
                        self._gather_cols(self._f32(state.prev_grad)),
                        state.comm["recon"], state.comm["residual"],
                        state.comm["recon_t"], state.comm["residual_t"],
                        w_off_r, w_self_r, alpha, **kw, **dpkw,
                    )
                    mx, mt = self._scope_finish_gt(
                        mx, mt, state.params, state.tracker, grads,
                        state.prev_grad, alpha, fire,
                    )
                    new_state = FLState(
                        step=step, params=self._st(mx), tracker=self._st(mt),
                        prev_grad=self._st(grads),
                        comm={"recon": nrx, "residual": nsx,
                              "recon_t": nrt, "residual_t": nst, **topo_comm},
                    )

            return new_state, self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, gate_metrics
            )

        return comm_step

    def _make_bounded_comm_step(self, eval_grads, schedule, cfg: FLConfig):
        """The depth-k (k >= 2) round: the wire stage runs unchanged (ONE
        Pallas call -- same kernel the sharded engine's shards run), the
        mix contracts W against the k-round-STALE reconstruction
        recovered from the in-flight ring (see :meth:`_ring_depth`), and
        this round's payload is pushed onto the ring. Proven equal to the
        hand-written k-delayed sequential oracle in
        tests/test_bounded_staleness.py."""
        _, w_self, w_off = _split_w_np(self.w, cfg.n_nodes)
        if self.impl == "pallas":
            from repro.kernels.gossip.ops import wire_stage, wire_stage_gt
        else:
            from repro.kernels.gossip.ref import (
                wire_stage_gt_ref as wire_stage_gt,
                wire_stage_ref as wire_stage,
            )
        kw = self._kernel_kwargs()
        egress = self.wire_bytes(cfg)
        dynamic = self.dynamic_round
        dp = self._dp
        n = cfg.n_nodes
        dc = self.difference_coding
        chunk = self.scale_chunk
        w_off32 = jnp.asarray(w_off, jnp.float32)
        w_self32 = jnp.asarray(w_self, jnp.float32)

        def stale_recon(recon, wq, wsc):
            """recon^(r-k) from recon^(r-1) and the ring (difference
            coding), or the oldest in-flight payload directly (no
            difference coding: recon IS the payload)."""
            if not dc:
                return _dequant(wq[:, 0], wsc[:, 0], chunk)
            mix = recon
            for j in range(wq.shape[1]):
                mix = mix - _dequant(wq[:, j], wsc[:, j], chunk)
            return mix

        def push(wq, wsc, q, sc):
            return (
                jnp.concatenate([wq[:, 1:], q[:, None]], axis=1),
                jnp.concatenate([wsc[:, 1:], sc[:, None]], axis=1),
            )

        def comm_step(state: FLState, batch: PyTree):
            if state.comm is None:
                raise ValueError(
                    "fused rounds need init_fl_state(..., engine=...)"
                )
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)
                alpha32 = jnp.asarray(alpha, jnp.float32)

                gate_metrics: Dict[str, jnp.ndarray] = {}
                if dynamic:
                    w_off_r, w_self_r, topo_comm, gate_metrics = (
                        self._round_gates(state.comm)
                    )
                    w_off_r = jnp.asarray(w_off_r, jnp.float32)
                    w_self_r = jnp.asarray(w_self_r, jnp.float32)
                else:
                    w_off_r, w_self_r = w_off32, w_self32
                    topo_comm = self._priv_comm(state.comm)
                dpkw = dict(self._dp_kwargs())
                if dp:
                    dpkw["dp_noise"] = self._dp_noise_full(state.comm, n)
                fire = self._scope_fire(state.comm)

                c = state.comm
                if cfg.algorithm == "dsgd":
                    h, q, sc, nrecon, nres = wire_stage(
                        self._gather_cols(self._f32(state.params)),
                        self._gather_cols(grads), c["recon"],
                        c["residual"], alpha32, **kw, **dpkw,
                    )
                    mix = stale_recon(
                        c["recon"], c["wire_q"], c["wire_scales"]
                    )
                    mixed = self._st(self._scope_finish(
                        w_off_r @ mix + w_self_r[:, None] * h,
                        state.params, grads, alpha32, fire,
                    ))
                    nwq, nwsc = push(c["wire_q"], c["wire_scales"], q, sc)
                    new_state = state._replace(
                        step=step, params=mixed,
                        comm={"recon": nrecon, "residual": nres,
                              "wire_q": nwq, "wire_scales": nwsc, **topo_comm},
                    )
                else:
                    if dp:
                        dpkw["dp_noise_t"] = self._dp_noise_full(
                            state.comm, n, tracker=True
                        )
                    (h, t_half, qx, scx, nrx, nsx, qt, sct, nrt, nst) = (
                        wire_stage_gt(
                            self._gather_cols(self._f32(state.params)),
                            self._gather_cols(self._f32(state.tracker)),
                            self._gather_cols(grads),
                            self._gather_cols(self._f32(state.prev_grad)),
                            c["recon"], c["residual"], c["recon_t"],
                            c["residual_t"], alpha32, **kw, **dpkw,
                        )
                    )
                    mix_x = stale_recon(
                        c["recon"], c["wire_q"], c["wire_scales"]
                    )
                    mix_t = stale_recon(
                        c["recon_t"], c["wire_q_t"], c["wire_scales_t"]
                    )
                    mixed_x, mixed_t = self._scope_finish_gt(
                        w_off_r @ mix_x + w_self_r[:, None] * h,
                        w_off_r @ mix_t + w_self_r[:, None] * t_half,
                        state.params, state.tracker, grads, state.prev_grad,
                        alpha32, fire,
                    )
                    mixed_x = self._st(mixed_x)
                    mixed_t = self._st(mixed_t)
                    nwq, nwsc = push(c["wire_q"], c["wire_scales"], qx, scx)
                    nwqt, nwsct = push(
                        c["wire_q_t"], c["wire_scales_t"], qt, sct
                    )
                    new_state = FLState(
                        step=step, params=mixed_x, tracker=mixed_t,
                        prev_grad=self._st(grads),
                        comm={"recon": nrx, "residual": nsx,
                              "recon_t": nrt, "residual_t": nst,
                              "wire_q": nwq, "wire_scales": nwsc,
                              "wire_q_t": nwqt, "wire_scales_t": nwsct,
                              **topo_comm},
                    )

            return new_state, self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, gate_metrics
            )

        return comm_step

    def make_pipelined_round(self, eval_grads, schedule, cfg: FLConfig):
        """The dense engine has no separate collective (its 'wire' is the
        in-kernel W contraction), so ingest is None and the comm step --
        built with ``stale_mix`` -- ignores the stale argument."""
        if not self.pipelined:
            raise ValueError(
                "engine was built with round_schedule='sequential'; build "
                "it with round_schedule='pipelined'"
            )
        comm_step = self.make_comm_step(eval_grads, schedule, cfg)
        return None, lambda state, batch, stale: comm_step(state, batch)

    @classmethod
    def simulated(cls, w: np.ndarray, stacked_params: PyTree, *,
                  scale_chunk: int = 512, topk=None, impl: str = "pallas",
                  error_feedback: bool = True, difference_coding: bool = True,
                  wire_dtype=None, round_schedule=None, storage_dtype=None,
                  topology_program=None, node_program=None, privacy=None,
                  scope=None, **_ignored):
        _reject_wire_dtype(wire_dtype)
        flat, layout = pack(stacked_params, pad_to=scale_chunk,
                            buffer_dtype=storage_dtype or jnp.float32)
        return cls(w, layout, scale_chunk=scale_chunk, topk=topk, impl=impl,
                   error_feedback=error_feedback,
                   difference_coding=difference_coding,
                   round_schedule=round_schedule,
                   topology_program=topology_program,
                   node_program=node_program, privacy=privacy,
                   scope=scope), flat

    @classmethod
    def from_mesh(cls, mesh: Mesh, node_axes: Sequence[str], stacked_sds,
                  *, wire_dtype=None, axes_subset=None, scale_chunk: int = 512,
                  topk=None, impl: str = "pallas",
                  error_feedback: bool = True,
                  difference_coding: bool = True, self_weight=None,
                  round_schedule=None, storage_dtype=None,
                  topology_program=None, node_program=None, privacy=None,
                  scope=None, **_ignored):
        """Mesh build: W is the dense equivalent of the circulant torus the
        ppermute backend realizes over the node axes (directions restricted
        to ``axes_subset`` for hierarchical gossip). The lowering-only dry
        run passes ``impl="jnp"``: GSPMD partitions the jnp oracle, not a
        Pallas call."""
        _reject_wire_dtype(wire_dtype)
        w = mesh_gossip_dense_equivalent(
            {a: mesh.shape[a] for a in node_axes}, self_weight=self_weight,
            axes_subset=axes_subset,
        )
        layout = pack_layout(stacked_sds, pad_to=scale_chunk,
                             storage_dtype=storage_dtype or jnp.float32)
        return cls(w, layout, scale_chunk=scale_chunk, topk=topk, impl=impl,
                   error_feedback=error_feedback,
                   difference_coding=difference_coding,
                   round_schedule=round_schedule,
                   topology_program=topology_program,
                   node_program=node_program, privacy=privacy,
                   scope=scope)


@register_engine
class ShardedFusedEngine(_FusedBase):
    """The shard_map-native fused round for real meshes.

    Each device owns its node's row of the flat buffer (sharded
    ``P(node_axes, None)``) and its node's W row. Per round, inside ONE
    shard_map body:

      1. the WIRE STAGE -- local update (DSGD) / tracker arithmetic +
         update (DSGT), difference coding, top-k masking, int8 quantize,
         EF -- runs as ONE Pallas call on this shard's rows
         (``kernels.gossip.wire_stage[_gt]``; ``impl="jnp"`` uses the
         bit-identical oracle);
      2. the payload crosses the wire: one ``ppermute`` per torus
         direction for the circulant W realized by the mesh node axes
         (``w=None``), or one ``all_gather`` over the node axes for an
         arbitrary dense W. With ``topk`` the COMPACT buffers move --
         (k int8 values, k int16 positions, fp32 scales) per chunk, the
         bytes ``flat_wire_bytes`` accounts -- and the receive side
         scatter-accumulates them back to dense
         (``kernels.gossip.ref.scatter_compact_dq``); without ``topk``
         the dense int8 payload + scales move as before;
      3. the mix finishes against the running neighbor-reconstruction
         accumulator: ``mix_recon' = mix_recon + sum_j W_ij dq_j``,
         ``mixed = w_self * h + mix_recon'`` -- O(params/node) state,
         bit-equal (up to summation order) to ``FusedEngine`` on the
         dense equivalent W.

    Under the PIPELINED round schedule the same three stages split in
    time: the comm step stores this round's wire buffers in
    ``FLState.comm`` (``wire_q`` / ``wire_pos`` / ``wire_scales``), the
    NEXT round's ingest runs stage 2 on them before its local-step scan,
    and the mix consumes that one-round-stale term
    (``make_pipelined_round``). Mid-pipeline checkpoints restore
    consistently: ``restore_comm`` rebuilds
    ``mix_recon == W_off @ (recon - dq(in-flight wire))``.
    """

    name = "sharded_fused"
    needs_mesh = True
    supports_wire_k = True

    def __init__(self, mesh: Mesh, node_axes: Sequence[str],
                 layout: FlatLayout, *, w: Optional[np.ndarray] = None,
                 self_weight: Optional[float] = None, axes_subset=None,
                 compact: Optional[bool] = None,
                 model_axis: Optional[str] = None, **kw):
        # Two-axis (gossip_node x model_shard) rounds: with model_axis
        # set, each node's flat buffer row is column-tiled across that
        # mesh axis -- every shard_map body runs per (node, shard) tile,
        # the wire stage is one Pallas call per tile, and the gossip
        # collectives stay on the NODE axes only (the model axis never
        # appears in a ppermute/all_gather), so the per-shard operand
        # bytes are exactly flat_wire_bytes / shards.
        if model_axis is not None:
            if model_axis not in mesh.axis_names:
                raise ValueError(
                    f"model_axis {model_axis!r} not in mesh axes "
                    f"{tuple(mesh.axis_names)}"
                )
            if model_axis in tuple(node_axes):
                raise ValueError(
                    f"model_axis {model_axis!r} is also a gossip node "
                    "axis; the two-axis round shards parameter columns "
                    "over a DIFFERENT axis than the one enumerating nodes"
                )
        self.model_axis = model_axis
        self.model_shards = (
            int(mesh.shape[model_axis]) if model_axis is not None else 1
        )
        if layout.shards != self.model_shards:
            layout = layout.with_shards(self.model_shards)
        super().__init__(layout, **kw)
        if isinstance(self.scope, LayerwiseScope):
            raise ValueError(
                f"federation scope {self.scope.spec()!r}: the layerwise "
                "round-gated mix needs the dense in-kernel W contraction; "
                "the sharded wire accumulates neighbor terms across "
                "collectives -- use --fl-engine fused, or a static "
                "sub-range scope ('backbone' / 'ranges:') here"
            )
        if self.layout.shard_width % self.scale_chunk:
            raise ValueError(
                f"per-shard width {self.layout.shard_width} not a multiple "
                f"of scale_chunk {self.scale_chunk}; pack with "
                f"pad_to={self.scale_chunk} and shards={self.model_shards} "
                "so every shard tile holds whole quantization chunks"
            )
        # The compact wire is only the wire when it is actually SMALLER
        # than dense int8 (k values + k positions + scale <= chunk +
        # scale). `compact=None` auto-enables it exactly in that regime,
        # so the collective operand bytes ALWAYS equal flat_wire_bytes
        # (whose dense cap then never binds for this engine); an
        # explicitly requested uneconomic compact wire is refused rather
        # than shipped while the accounting reports the dense fallback.
        economic = self.topk is not None and self._compact_is_economic()
        if compact is None:
            compact = economic
        if compact:
            if self.topk is None or not (1 <= self.topk < self.scale_chunk):
                raise ValueError(
                    "the compact wire needs a sparsified payload: set "
                    f"1 <= topk < scale_chunk (got topk={self.topk}, "
                    f"scale_chunk={self.scale_chunk}) or pass compact=False"
                )
            if not economic:
                raise ValueError(
                    f"compact encoding of topk={self.topk} costs more than "
                    f"the dense int8 chunk ({self.topk} values + "
                    f"{compact_index_bytes(self.scale_chunk, self.topk)} "
                    f"index bytes > {self.scale_chunk} columns); ship the "
                    "dense wire (compact=False) or lower topk"
                )
        self.compact_wire = bool(compact)
        # The index encoding that actually crosses the collective: the
        # cheaper of explicit positions (k x int16/int32) and the
        # presence bitmap (chunk/8 B, byte-aligned chunks) -- the SAME
        # boundary packing.compact_index_bytes accounts, so flat_wire_bytes
        # IS the operand bytes. Bitmap wins for k > chunk/16.
        self.wire_encoding = "dense"
        if self.compact_wire:
            pos_b = self.topk * jnp.dtype(
                compact_pos_dtype(self.scale_chunk)
            ).itemsize
            bb = bitmap_bytes_per_chunk(self.scale_chunk)
            self.wire_encoding = (
                "bitmap" if (bb is not None and bb < pos_b) else "positions"
            )
        self.mesh = mesh
        self.node_axes = tuple(node_axes)
        self.n_nodes = int(np.prod([mesh.shape[a] for a in self.node_axes]))
        self.axes_subset = tuple(axes_subset) if axes_subset else None
        self.self_weight = self_weight
        if w is None:
            # circulant torus W over the node axes: ppermute wire
            self.w_dense = None
            self.w_self, self.dirs = _mesh_dirs(
                mesh, self.node_axes, self.axes_subset, self_weight
            )
        else:
            w = np.asarray(w, dtype=np.float64)
            if w.shape != (self.n_nodes,) * 2:
                raise ValueError(
                    f"W shape {w.shape} != ({self.n_nodes},) * 2"
                )
            self.w_dense = w
            self.w_self, self.dirs = None, None
        # Dynamic programs gate EITHER wire with zero extra collectives:
        # on the CIRCULANT wire the ppermutes run every round unchanged
        # and a dropped link only zeroes its mixing contribution -- the
        # running neighbor term generalizes from ONE pre-weighted
        # mix_recon to one UNWEIGHTED accumulator per torus direction
        # (each tracks that neighbor's reconstruction exactly), weighted
        # per round by the program's traced gate. On the DENSE all-gather
        # wire every dq already reaches every node, so each node keeps an
        # unweighted replica of ALL reconstructions (``nbr_recon_all``,
        # (n, t) per node -- n x the per-node memory of the circulant
        # accumulators, the price of an arbitrary dense W under churn)
        # and contracts its traced W_r row against it at mix time.
        self.topology_program.bind(self.dense_equivalent())
        self.node_program = self.node_program.bind(self.n_nodes)
        # per-direction sender index: node i receives from _dir_src[d][i],
        # and ships its own payload to _dir_dst[d][i] (the inverse roll)
        # -- row-major node order, identical to dense_equivalent. The dst
        # table keys the SENDER side of the pairwise transport pads.
        self._dir_src: Tuple[np.ndarray, ...] = ()
        self._dir_dst: Tuple[np.ndarray, ...] = ()
        if self.dirs is not None:
            names = list(self.node_axes)
            sizes = [self.mesh.shape[a] for a in names]
            idx = np.arange(self.n_nodes).reshape(sizes)
            self._dir_src = tuple(
                np.roll(idx, shift, axis=names.index(axis_name)).reshape(-1)
                for axis_name, shift, _ in self.dirs
            )
            self._dir_dst = tuple(
                np.roll(idx, -shift, axis=names.index(axis_name)).reshape(-1)
                for axis_name, shift, _ in self.dirs
            )
        if self.privacy.secure_agg and self.dirs is None:
            raise ValueError(
                f"privacy spec {self.privacy.spec()!r}: secure_agg needs "
                "the circulant ppermute wire (per-edge payloads to pad); "
                "the dense all-gather wire broadcasts every payload to "
                "every node, so pairwise pads cannot conceal it -- drop "
                "w= (use the mesh torus W) or drop the secure_agg token"
            )
        if getattr(self.node_program, "heterogeneous_wire_k", False):
            if self.topk is None:
                raise ValueError(
                    f"node program {self.node_program.spec()!r} modulates "
                    "per-node wire k; build the engine with topk= so there "
                    "is a k to modulate"
                )
            if not self.error_feedback:
                raise ValueError(
                    "per-node wire k rides the EF residual (entries a slow "
                    "uplink truncates re-ship later); build with "
                    "error_feedback=True"
                )
            if self._dp:
                raise ValueError(
                    "per-node wire k truncates the noised payload AFTER "
                    "clipping, which breaks the DP calibration; drop the "
                    "dp token or the wire-k program"
                )

    def _compact_is_economic(self) -> bool:
        """True when the compact (values + cheapest index encoding +
        scale) chunk is no larger than the dense int8 chunk -- the regime
        where the compact wire is THE wire and ``flat_wire_bytes``'s
        dense cap never binds. The index encoding is the cheaper of
        explicit positions and the presence bitmap
        (``packing.compact_index_bytes``)."""
        if self.topk is None:
            return False
        idx = compact_index_bytes(self.scale_chunk, self.topk)
        return self.topk + idx <= self.scale_chunk

    @property
    def _sa_wire(self) -> bool:
        """The circulant ppermute wire is the one place a per-edge
        payload physically exists, so it is the one place the pairwise
        pads are real (masked immediately before each ppermute, unmasked
        immediately after -- zero extra collectives, identical operand
        shapes/dtypes, bit-identical arithmetic after the receive)."""
        return self.privacy.secure_agg and self.dirs is not None

    # -- comm-state contract ----------------------------------------------

    def _wire_key_names(self, suffix: str = "") -> Tuple[str, ...]:
        """Names of ONE wire's in-flight payload buffers (pipelined only):
        the int8 values, the index encoding (compact wire: explicit
        positions or the presence bitmap, per ``wire_encoding``), and the
        scales -- exactly what crosses the collective, double-buffered in
        ``FLState.comm`` for one round."""
        if not self.compact_wire:
            names = ("wire_q", "wire_scales")
        elif self.wire_encoding == "bitmap":
            names = ("wire_q", "wire_bits", "wire_scales")
        else:
            names = ("wire_q", "wire_pos", "wire_scales")
        return tuple(n + suffix for n in names)

    def _nbr_key_names(self, suffix: str = "") -> Tuple[str, ...]:
        """Dynamic-round accumulators: one per torus direction on the
        circulant wire, each tracking THAT neighbor's reconstruction (sum
        of every dq that crossed from it), or ONE all-node replica
        (``nbr_recon_all``, (n, n, t) sharded by receiver) on the dense
        all-gather wire. Both replace the single pre-weighted
        ``mix_recon`` -- under a per-round W the weights cannot be folded
        into the running sum, so the weighting moves to mix time (the
        traced gate). Present only with difference coding (without it the
        mix term is rebuilt from the current round's wire alone)."""
        if not (self.dynamic_round and self.difference_coding):
            return ()
        if self.dirs is None:
            return ("nbr_recon_all" + suffix,)
        return tuple(
            f"nbr_recon_{d}{suffix}" for d in range(len(self.dirs))
        )

    def comm_keys(self, cfg: FLConfig) -> Tuple[str, ...]:
        if self.dynamic_round:
            keys = ("recon", "residual") + self._nbr_key_names("")
            if self.pipelined:
                keys += self._wire_key_names("")
            if cfg.algorithm == "dsgt":
                keys += ("recon_t", "residual_t") + self._nbr_key_names("_t")
                if self.pipelined:
                    keys += self._wire_key_names("_t")
            return keys + self._topo_keys()
        keys = ("recon", "residual", "mix_recon")
        if self.pipelined:
            keys += self._wire_key_names("")
        if cfg.algorithm == "dsgt":
            keys += ("recon_t", "residual_t", "mix_recon_t")
            if self.pipelined:
                keys += self._wire_key_names("_t")
        # static rounds under an active privacy transform still need the
        # counter + key (the pads/noise advance with the round index)
        return keys + self._topo_keys()

    def comm_state_sds(
        self, cfg: FLConfig
    ) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
        # every wire/EF/neighbor buffer lives at the SCOPED wire width
        # (identical to layout.total under the full scope)
        n, t = cfg.n_nodes, self.wire_layout.total
        n_chunks = t // self.scale_chunk
        pos_dtype = compact_pos_dtype(self.scale_chunk)
        topo = self._topo_sds()
        # depth-k rings carry k in-flight payloads per wire buffer: a
        # (n, k, width) middle axis. Depth 1 keeps the flat pipelined
        # (n, width) layout (same contract as before, bit-compatible
        # checkpoints).
        k = self.staleness_depth

        def ring(width, dtype):
            shape = (n, width) if k <= 1 else (n, k, width)
            return jax.ShapeDtypeStruct(shape, dtype)

        def buf(key):
            if key in topo:
                return topo[key]
            if key.startswith("wire_q"):
                width = n_chunks * self.topk if self.compact_wire else t
                return ring(width, jnp.int8)
            if key.startswith("wire_pos"):
                return ring(n_chunks * self.topk, pos_dtype)
            if key.startswith("wire_bits"):
                return ring(n_chunks * (self.scale_chunk // 8), jnp.uint8)
            if key.startswith("wire_scales"):
                return ring(n_chunks, jnp.float32)
            if key.startswith("nbr_recon_all"):
                return jax.ShapeDtypeStruct((n, n, t), jnp.float32)
            return jax.ShapeDtypeStruct((n, t), jnp.float32)

        keys = self.comm_keys(cfg)
        return {k: buf(k) for k in keys} or None

    def is_derived_comm_key(self, key: str) -> bool:
        """The neighbor-mix accumulators -- ``mix_recon[_t]`` (static) and
        ``nbr_recon_{d}[_t]`` (dynamic) -- are all rebuilt from recon by
        :meth:`restore_comm`, so either contract's checkpoint can seed
        the other (modulo the topology-program equality check in
        ``training.checkpoint``)."""
        return key.startswith("mix_recon") or key.startswith("nbr_recon_")

    def _known_comm_keys(self) -> frozenset:
        extra = ["mix_recon", "mix_recon_t", "nbr_recon_all",
                 "nbr_recon_all_t", "wire_pos", "wire_pos_t",
                 "wire_bits", "wire_bits_t"]
        if self.dirs is not None:
            extra += [
                f"nbr_recon_{d}{suffix}"
                for d in range(len(self.dirs))
                for suffix in ("", "_t")
            ]
        return super()._known_comm_keys() | frozenset(extra)

    def dense_equivalent(self) -> np.ndarray:
        """The dense W this engine realizes (the ``FusedEngine`` oracle)."""
        if self.w_dense is not None:
            return self.w_dense
        return mesh_gossip_dense_equivalent(
            {a: self.mesh.shape[a] for a in self.node_axes},
            self_weight=self.self_weight,
            axes_subset=self.axes_subset,
        )

    def _edge_bytes(self) -> int:
        """What ONE neighbor payload physically costs on this wire: the
        compact encoding when the compact-gather epilogue is on (values +
        positions + scales -- the collective's actual operand bytes,
        strictly below dense by the economic check in ``__init__``), the
        DENSE int8 bytes otherwise (a masked-dense top-k payload still
        moves every column; ``compact=False`` is the equivalence baseline
        and the fallback for an uneconomic k)."""
        return flat_wire_bytes(
            self.wire_layout, 1, self.scale_chunk,
            self.topk if self.compact_wire else None,
        )

    def wire_bytes(self, cfg: FLConfig) -> float:
        wires = 2 if cfg.algorithm == "dsgt" else 1
        return float(
            wires * _degrees(self.dense_equivalent()).sum() * self._edge_bytes()
        )

    def _edge_bytes_per_shard(self) -> int:
        """One neighbor payload's cost per (node, shard) tile -- the
        1/shards column slice of :meth:`_edge_bytes`, priced by the same
        boundary (``packing.flat_wire_bytes_per_shard``)."""
        return flat_wire_bytes_per_shard(
            self.wire_layout, 1, self.scale_chunk,
            self.topk if self.compact_wire else None,
        )

    def wire_bytes_per_shard(self, cfg: FLConfig) -> float:
        """Collective operand bytes per round per model shard: on the
        two-axis mesh every ppermute/all_gather moves one (node, shard)
        column tile, so this is exactly ``wire_bytes / model_shards``
        (jaxpr-asserted in tests/test_two_axis.py)."""
        wires = 2 if cfg.algorithm == "dsgt" else 1
        return float(
            wires * _degrees(self.dense_equivalent()).sum()
            * self._edge_bytes_per_shard()
        )

    def _dq_full(self, wire: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
        """Dense dequant of one wire's payload buffers, at any row AND
        column count: per-(node, shard) tiles inside shard_map, or the
        full (n, .) buffers at restore time -- the dense width is always
        recovered from the scales buffer (chunks per row never straddle
        a shard boundary)."""
        if self.compact_wire:
            t = wire[-1].shape[-1] * self.scale_chunk
            if self.wire_encoding == "bitmap":
                from repro.kernels.gossip.ref import scatter_bitmap_dq

                vals, bits, scales = wire
                return scatter_bitmap_dq(
                    vals, bits, scales, self.scale_chunk, t
                )
            from repro.kernels.gossip.ref import scatter_compact_dq

            q, pos, scales = wire
            return scatter_compact_dq(
                q, pos, scales, self.scale_chunk, t
            )
        q, scales = wire
        return _dequant(q, scales, self.scale_chunk)

    # -- engine-owned partition specs --------------------------------------

    def params_spec(self) -> P:
        """The flat (n, total) buffer's partition spec on this mesh:
        rows over the gossip node axes, columns over the model axis
        (replicated when the engine was built without one)."""
        return P(self.node_axes, self.model_axis)

    def comm_state_specs(self, cfg: FLConfig) -> Dict[str, P]:
        """Partition specs for every comm buffer, matching
        :meth:`comm_state_sds` key for key: node-major buffers shard
        rows over the node axes and their LAST (width) dim over the
        model axis whenever the width tiles evenly (wire and recon
        buffers do; per-node gates and counters replicate their trailing
        dims). Consumers (``launch/dryrun.py``, the train drivers) take
        these instead of re-deriving placement by rank."""
        sds = self.comm_state_sds(cfg) or {}
        out: Dict[str, P] = {}
        s = self.model_shards
        for key, v in sds.items():
            shape = v.shape
            if len(shape) >= 2 and shape[0] == cfg.n_nodes:
                last = (
                    self.model_axis
                    if self.model_axis is not None
                    and shape[-1] % s == 0 and shape[-1] >= s
                    else None
                )
                out[key] = P(
                    self.node_axes, *((None,) * (len(shape) - 2)), last
                )
            else:
                out[key] = P()
        return out

    def restore_comm(
        self, comm: Dict[str, jnp.ndarray]
    ) -> Dict[str, jnp.ndarray]:
        """The mix_recon accumulators are DERIVED state, so a restore
        (possibly from a fused checkpoint that never had them) rebuilds
        them from the restored recon instead of trusting whatever the
        template carried. Sequential invariant: ``mix_recon == W_off @
        recon`` at every round boundary. Pipelined: the sender has already
        advanced recon by the IN-FLIGHT payload its neighbors have not
        mixed yet, so ``mix_recon == W_off @ (recon - dq(wire))`` -- with
        a zero wire (restore from a sequential/fused checkpoint) the
        formulas coincide, which is what makes mid-pipeline restores and
        cross-schedule restores both land in a self-consistent state."""
        self._check_restored_comm_keys(comm)
        w = self.dense_equivalent()
        w_off = jnp.asarray(w - np.diag(np.diag(w)), jnp.float32)
        comm = dict(comm)

        def effective_recon(recon_key: str, suffix: str) -> jnp.ndarray:
            """recon minus EVERY in-flight payload: the sender has
            advanced recon by k payloads its neighbors have not mixed
            yet, so the neighbor-visible reconstruction subtracts the
            whole ring (one buffer at depth 1)."""
            recon = jnp.asarray(comm[recon_key], jnp.float32)
            names = self._wire_key_names(suffix)
            if self.pipelined and all(k in comm for k in names):
                bufs = tuple(jnp.asarray(comm[k]) for k in names)
                if self.staleness_depth <= 1:
                    recon = recon - self._dq_full(bufs)
                else:
                    for j in range(self.staleness_depth):
                        recon = recon - self._dq_full(
                            tuple(b[:, j] for b in bufs)
                        )
            return recon

        if self.dynamic_round:
            # per-direction accumulators are DERIVED the same way
            # mix_recon is: nbr_recon_d[i] tracks neighbor src_d(i)'s
            # reconstruction at the same wire lag, i.e. a row permutation
            # of the (restored) full recon matrix; the dense wire's
            # nbr_recon_all[i] is every node's replica of the SAME matrix
            def rebuild(suffix: str) -> None:
                eff = effective_recon(
                    "recon" + suffix, suffix
                )
                names = self._nbr_key_names(suffix)
                if self.dirs is None:
                    for name in names:
                        comm[name] = jnp.broadcast_to(
                            eff[None], (self.n_nodes,) + eff.shape
                        )
                    return
                for d, name in enumerate(names):
                    comm[name] = eff[self._dir_src[d]]

            rebuild("")
            if "recon_t" in comm:
                rebuild("_t")
            return comm

        comm["mix_recon"] = w_off @ effective_recon("recon", "")
        if "recon_t" in comm:
            comm["mix_recon_t"] = w_off @ effective_recon("recon_t", "_t")
        return comm

    # -- the shard_map round ----------------------------------------------

    def _my_index(self) -> jnp.ndarray:
        """This device's row-major node index (trace-time, inside the
        shard_map body) -- the composition of the node-axis indices,
        identical to the ``dense_equivalent`` row order."""
        idx = 0
        for a in self.node_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _transport(self, wire: Tuple[jnp.ndarray, ...], d: int,
                   priv, stream_base: int) -> Tuple[jnp.ndarray, ...]:
        """ONE direction's masked transport: pad the payload with the
        sender-side edge pad, ppermute every buffer, remove the
        receiver-side pad. Pads are a pure counter hash of (priv_key,
        round, undirected pair index) with the antisymmetric sign fixed
        by ``sender < receiver``, so both endpoints derive the same
        words and mask∘unmask is the exact identity -- the collective's
        operand shapes, dtypes, and count are byte-for-byte those of the
        plaintext wire. With ``priv=None`` this IS the plaintext wire."""
        axis_name, shift, _w = self.dirs[d]
        size = self.mesh.shape[axis_name]
        perm = [(i, (i + shift) % size) for i in range(size)]
        with jax.named_scope(STAGE_TRANSPORT):
            if priv is not None:
                key, r = priv
                n = self.n_nodes
                my = self._my_index()
                dst = jnp.asarray(self._dir_dst[d])[my]
                wire = mask_wire(
                    wire, key, r, pair_index(my, dst, n), my < dst,
                    stream_base=stream_base,
                )
            recv = tuple(
                jax.lax.ppermute(b, axis_name, perm) for b in wire
            )
            if priv is not None:
                src = jnp.asarray(self._dir_src[d])[my]
                recv = mask_wire(
                    recv, key, r, pair_index(src, my, n), src < my,
                    stream_base=stream_base, unmask=True,
                )
        return recv

    def _wire_mix(self, wire: Tuple[jnp.ndarray, ...], w_off_rows,
                  priv=None, stream_base: int = PAD_STREAM):
        """Move one wire's payload buffers over the collective and return
        ``sum_j W_ij dq_j`` for this shard's rows. ``wire`` is (q, scales)
        for the dense int8 wire or (q, pos, scales) for the compact
        top-k wire -- EVERY buffer in the tuple is a collective operand,
        so the bytes that move are exactly ``flat_wire_bytes``.
        ``w_off_rows``: replicated (n, n) off-diagonal W (dense-W
        all-gather wire only; ignored for the circulant ppermute wire).
        ``priv``: the traced ``(priv_key, round)`` pair when secure_agg
        masks the transport (see :meth:`_transport`)."""
        rows = wire[0].shape[0]
        # local dense width: total/shards inside a two-axis shard_map
        # body, the full total on a node-only mesh or at restore time
        t = wire[-1].shape[-1] * self.scale_chunk
        if self.dirs is not None:
            acc = jnp.zeros((rows, t), jnp.float32)
            for d, (_axis, _shift, weight) in enumerate(self.dirs):
                recv = self._transport(wire, d, priv, stream_base)
                if d:
                    # dequantize one direction after the other: the TPU
                    # compiler gives each dequant full-row temporaries
                    # (the scales broadcast to the row, and a relayout of
                    # it), which are then freed before the next
                    # direction's exist -- 3.2e9 B less at the peak of a
                    # 425M-parameter site
                    acc, recv = jax.lax.optimization_barrier((acc, recv))
                acc = acc + jnp.float32(weight) * self._dq_full(recv)
            return acc
        # arbitrary dense W: ONE all-gather per wire buffer (secure_agg
        # is rejected at build on this wire -- nothing to pad)
        n = self.n_nodes
        with jax.named_scope(STAGE_TRANSPORT):
            gathered = tuple(
                jax.lax.all_gather(b[0], self.node_axes, tiled=False)
                .reshape(n, -1)
                for b in wire
            )
        dq = self._dq_full(gathered)
        row = _allgather_row(self.mesh, self.node_axes, w_off_rows)  # (n,)
        return (row @ dq)[None]

    # -- dynamic-topology machinery ----------------------------------------

    def _recv_dqs(self, wire: Tuple[jnp.ndarray, ...], priv=None,
                  stream_base: int = PAD_STREAM):
        """Per-direction receive: the SAME ppermutes as :meth:`_wire_mix`
        (one per wire buffer per direction -- churn adds zero
        collectives), returning each direction's dense dequantized
        payload UNWEIGHTED so the per-round gate can weight it at mix
        time. Masked transport per :meth:`_transport`: unmask happens
        HERE, at the boundary, so the gate weights plaintext arithmetic
        -- a dropped edge drops both directions of its pad with it."""
        out = []
        for d in range(len(self.dirs)):
            recv = self._transport(wire, d, priv, stream_base)
            out.append(self._dq_full(recv))
        return out

    def _dir_gates(self, comm: Dict[str, jnp.ndarray]):
        """The round's traced per-direction mixing weights, derived
        OUTSIDE the shard_map (tiny (n, n) arithmetic) from BOTH dynamic
        axes via :meth:`_round_gates`: ``dgate (n, D)`` where
        ``dgate[i, d] = W_r[i, src_d(i)]`` (zero when the link or either
        endpoint is down), ``ddiag (n, 1)`` the folded self weights, the
        advanced topo/node comm entries, and the realized-fraction
        metrics."""
        w_off_r, w_diag_r, new_comm, gate_metrics = self._round_gates(comm)
        ar = jnp.arange(self.n_nodes)
        dgate = jnp.stack(
            [w_off_r[ar, jnp.asarray(src)] for src in self._dir_src], axis=1
        ).astype(jnp.float32)
        ddiag = w_diag_r.reshape(self.n_nodes, 1).astype(jnp.float32)
        return dgate, ddiag, new_comm, gate_metrics

    def _static_w_np(self) -> np.ndarray:
        return self.dense_equivalent()

    def _make_produce(self):
        """The wire-stage kernels (compact or dense epilogue), normalized
        to return the wire payload as ONE tuple matching
        ``_wire_key_names`` order."""
        if self.impl == "pallas":
            from repro.kernels.gossip.ops import (
                wire_stage,
                wire_stage_compact,
                wire_stage_gt,
                wire_stage_gt_compact,
            )
        else:
            from repro.kernels.gossip.ref import (
                wire_stage_compact_ref as wire_stage_compact,
                wire_stage_gt_compact_ref as wire_stage_gt_compact,
                wire_stage_gt_ref as wire_stage_gt,
                wire_stage_ref as wire_stage,
            )
        kw = self._kernel_kwargs()
        clip_kw = self._dp_kwargs()

        def dpkw(noise, noise_t=None):
            """The per-call DP kwargs: empty without noise (the original
            kernel call, bit-identical), clip + this round's traced
            noise rows otherwise."""
            if noise is None:
                return {}
            out = dict(clip_kw, dp_noise=noise)
            if noise_t is not None:
                out["dp_noise_t"] = noise_t
            return out

        if self.compact_wire:
            # Bitmap wire: on the Pallas path the re-encode (position
            # argsort + bit-pack) is an IN-KERNEL epilogue -- the kernel
            # emits (values, packed bitmap) directly, so nothing touches
            # the explicit positions after the pallas_call. The jnp path
            # (and heterogeneous wire-k, which truncates on explicit
            # positions BEFORE encoding) keeps the post-kernel re-encode.
            # Either way the collective operands are the bitmap buffers
            # and the pallas_call count is unchanged.
            wk = bool(getattr(self.node_program, "heterogeneous_wire_k",
                              False))
            kernel_bitmap = (self.wire_encoding == "bitmap"
                             and self.impl == "pallas" and not wk)
            if kernel_bitmap:
                kw = dict(kw, bitmap=True)

                def encode(q, pos, sc):
                    # kernel already emitted (vals, bits)
                    return q, pos, sc
            elif self.wire_encoding == "bitmap":
                from repro.kernels.gossip.ref import compact_to_bitmap

                def encode(q, pos, sc):
                    vals, bits = compact_to_bitmap(
                        q, pos, self.scale_chunk, self.topk
                    )
                    return vals, bits, sc
            else:
                def encode(q, pos, sc):
                    return q, pos, sc

            def produce(x, g, recon, res, alpha, noise=None, kvec=None):
                h, q, pos, sc, nrecon, nres = wire_stage_compact(
                    x, g, recon, res, alpha, **kw, **dpkw(noise)
                )
                if kvec is not None:
                    q, ddq = self._hetero_truncate(q, sc, kvec, pos=pos)
                    nrecon, nres = nrecon - ddq, nres + ddq
                return h, encode(q, pos, sc), nrecon, nres

            def produce_gt(x, t, g, gp, rx, sx, rt, st, alpha,
                           noise=None, noise_t=None, kvec=None):
                (h, th, qx, px, scx, nrx, nsx,
                 qt, pt, sct, nrt, nst) = wire_stage_gt_compact(
                    x, t, g, gp, rx, sx, rt, st, alpha, **kw,
                    **dpkw(noise, noise_t)
                )
                if kvec is not None:
                    qx, ddx = self._hetero_truncate(qx, scx, kvec, pos=px)
                    nrx, nsx = nrx - ddx, nsx + ddx
                    qt, ddt = self._hetero_truncate(qt, sct, kvec, pos=pt)
                    nrt, nst = nrt - ddt, nst + ddt
                return (h, th, encode(qx, px, scx), nrx, nsx,
                        encode(qt, pt, sct), nrt, nst)
        else:
            def produce(x, g, recon, res, alpha, noise=None, kvec=None):
                h, q, sc, nrecon, nres = wire_stage(
                    x, g, recon, res, alpha, **kw, **dpkw(noise)
                )
                if kvec is not None:
                    q, ddq = self._hetero_truncate(q, sc, kvec)
                    nrecon, nres = nrecon - ddq, nres + ddq
                return h, (q, sc), nrecon, nres

            def produce_gt(x, t, g, gp, rx, sx, rt, st, alpha,
                           noise=None, noise_t=None, kvec=None):
                (h, th, qx, scx, nrx, nsx,
                 qt, sct, nrt, nst) = wire_stage_gt(
                    x, t, g, gp, rx, sx, rt, st, alpha, **kw,
                    **dpkw(noise, noise_t)
                )
                if kvec is not None:
                    qx, ddx = self._hetero_truncate(qx, scx, kvec)
                    nrx, nsx = nrx - ddx, nsx + ddx
                    qt, ddt = self._hetero_truncate(qt, sct, kvec)
                    nrt, nst = nrt - ddt, nst + ddt
                return h, th, (qx, scx), nrx, nsx, (qt, sct), nrt, nst

        if self._scoped:
            # scoped wire: gather the SHARED columns of every per-tile
            # buffer before the (unmodified) wire-stage kernel -- the
            # whole produce path (quantize, top-k, EF, encodings) then
            # runs at the wire width; the round bodies scatter the mixed
            # result back around the untouched private columns.
            produce_full, produce_gt_full = produce, produce_gt

            def produce(x, g, *a, **k):
                return produce_full(
                    self._gather_cols(x), self._gather_cols(g), *a, **k
                )

            def produce_gt(x, t, g, gp, *a, **k):
                return produce_gt_full(
                    self._gather_cols(x), self._gather_cols(t),
                    self._gather_cols(g), self._gather_cols(gp), *a, **k
                )

        return produce, produce_gt

    # -- heterogeneous wire k ----------------------------------------------

    def _hetero_truncate(self, q, scales, kvec, pos=None):
        """Zero all but each node's k_i largest-|q| wire entries per
        chunk (ties broken by position -- deterministic), returning the
        truncated values and the dense dequant of what was DROPPED so
        the caller can move it from the shipped reconstruction back into
        the EF residual. Runs on the kernel's (values, positions) output
        BEFORE any bitmap re-encode, inside the shard_map body: k_i is a
        traced operand, every buffer shape stays static (jit cache 1)."""
        width = self.topk if pos is not None else self.scale_chunk
        rows = q.shape[0]
        qc = q.reshape(rows, -1, width)
        mag = jnp.abs(qc.astype(jnp.int32))
        rank = jnp.argsort(jnp.argsort(-mag, axis=-1), axis=-1)
        keep = rank < kvec.reshape(rows, 1, 1)
        kept = jnp.where(keep, qc, jnp.int8(0)).reshape(q.shape)
        dropped = jnp.where(keep, jnp.int8(0), qc).reshape(q.shape)
        if pos is not None:
            from repro.kernels.gossip.ref import scatter_compact_dq

            ddq = scatter_compact_dq(
                dropped, pos, scales, self.scale_chunk,
                scales.shape[-1] * self.scale_chunk,
            )
        else:
            ddq = _dequant(dropped, scales, self.scale_chunk)
        return kept, ddq

    def _wire_k_vec(self, comm: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """This round's per-node wire k: the node program's fraction
        gate clipped to [1, topk] integers -- a traced (n, 1) operand of
        the one compiled round (nodes speeding up or slowing down never
        recompile)."""
        frac = self.node_program.wire_k_gate(
            comm["topo_round"], comm["node_key"]
        )
        k = jnp.clip(jnp.round(frac * jnp.float32(self.topk)), 1, self.topk)
        return k.astype(jnp.int32).reshape(self.n_nodes, 1)

    def _wire_k_bytes(self, kvec: jnp.ndarray, wires: int) -> jnp.ndarray:
        """Traced per-node wire-byte accounting under heterogeneous k:
        ``flat_wire_bytes``'s per-chunk boundary with the traced k_i in
        place of the static topk -- what each node's egress WOULD cost
        on a k_i-sized wire (the physical buffers stay topk-wide; jit
        shapes are static). Summed over nodes x degree x wires."""
        chunk = self.scale_chunk
        n_chunks = self.wire_layout.total // chunk
        k = kvec.reshape(-1).astype(jnp.float32)
        idx = k * jnp.dtype(compact_pos_dtype(chunk)).itemsize
        bb = bitmap_bytes_per_chunk(chunk)
        if bb is not None:
            idx = jnp.minimum(idx, jnp.float32(bb))
        per_chunk = jnp.minimum(k + idx + 4.0, jnp.float32(chunk + 4))
        deg = jnp.asarray(_degrees(self.dense_equivalent()), jnp.float32)
        return jnp.float32(wires) * jnp.sum(deg * n_chunks * per_chunk)

    def _self_weight(self, w_diag):
        if self.dirs is not None:
            return jnp.float32(self.w_self)
        return jax.lax.dynamic_slice_in_dim(w_diag, self._my_index(), 1)[0]

    def _round_constants(self, cfg: FLConfig):
        if cfg.n_nodes != self.n_nodes:
            raise ValueError(
                f"cfg.n_nodes {cfg.n_nodes} != mesh node axes product "
                f"{self.n_nodes}"
            )
        if self.w_dense is None:
            # rank-matched placeholders; the circulant wire never reads them
            w_diag = jnp.zeros((1,), jnp.float32)
            w_off = jnp.zeros((1, 1), jnp.float32)
        else:
            _, w_diag, w_off = _split_w_np(self.w_dense, self.n_nodes)
        return w_diag, w_off

    def _mix_dirs_dynamic(self, dqs, nbrs, dgate):
        """Fold one wire's per-direction dq into the neighbor-recon
        accumulators and weight by the round's gate: ``mix_i = sum_d
        dgate[i, d] * nbr_recon_d'`` == the dense ``W_r_off @ recon'``
        row exactly. Without difference coding the neighbor recon IS this
        round's dq (nothing accumulates)."""
        dc = self.difference_coding
        mix, new_nbrs = None, []
        for d in range(len(self.dirs)):
            nb = (nbrs[d] + dqs[d]) if dc else dqs[d]
            if dc:
                new_nbrs.append(nb)
            term = dgate[:, d:d + 1] * nb
            mix = term if mix is None else mix + term
        return mix, tuple(new_nbrs)

    def _make_dynamic_round(self, eval_grads, schedule, cfg: FLConfig,
                            pipelined: bool):
        """ONE builder for both dynamic-topology round layouts -- the
        sequential and pipelined rounds differ ONLY in where the
        per-direction dqs come from (in-body ppermutes vs the ingested
        in-flight wire) and in whether this round's wire rides out in
        comm, so both are parameterized here instead of maintained as
        near-duplicate bodies (the static schedules share
        ``_assemble_round`` the same way). Wire stage and ppermute count
        are identical to the static engine (churn adds zero collectives,
        zero recompiles); the mix is weighted by the round's traced gate
        against per-direction neighbor-recon accumulators. Returns
        ``(ingest_or_None, comm_step(state, batch, stale))``."""
        self._round_constants(cfg)  # shape validation only
        if self.dirs is None:
            return self._make_dynamic_round_dense(
                eval_grads, schedule, cfg, pipelined
            )
        produce, produce_gt = self._make_produce()
        egress = self.wire_bytes(cfg)
        # buffers whose width is (a fixed fraction of) layout.total tile
        # over the model axis; per-node gates/counters do not
        spec = P(self.node_axes, self.model_axis)
        nspec = P(self.node_axes, None)
        n_dirs = len(self.dirs)
        wk = bool(getattr(self.node_program, "heterogeneous_wire_k", False))
        n_wk = 1 if wk else 0
        nbr_keys = self._nbr_key_names("")
        nbr_keys_t = self._nbr_key_names("_t")
        nnbr = len(nbr_keys)
        # pipelined extras: D ingested-dq operands per wire, and this
        # round's wire buffers appended to the outputs / comm keys
        wire_keys = self._wire_key_names("") if pipelined else ()
        wire_keys_t = self._wire_key_names("_t") if pipelined else ()
        n_adds = n_dirs if pipelined else 0
        n_wire = len(wire_keys)
        dp, sa = self._dp, self._sa_wire
        n_noise = 1 if dp else 0
        # pipelined transport lives in ingest; sequential transport lives
        # in the comm body -- the pad operands ride wherever the
        # ppermutes actually are
        sa_body = sa and not pipelined
        n_priv = 2 if sa_body else 0
        priv_specs = (P(None), P()) if sa_body else ()
        t_stream = PAD_STREAM + TRACKER_STREAM_OFFSET

        def mix_one(wire, nbrs, adds, dgate, priv, stream_base):
            dqs = (adds if pipelined
                   else self._recv_dqs(wire, priv=priv,
                                       stream_base=stream_base))
            return self._mix_dirs_dynamic(dqs, nbrs, dgate)

        def split_priv(tail):
            priv = (tail[0], tail[1]) if sa_body else None
            return tail[n_priv:], priv

        def body(x, g, recon, res, *rest):
            nbrs = rest[:nnbr]
            adds = rest[nnbr:nnbr + n_adds]
            k0 = nnbr + n_adds
            dgate, ddiag = rest[k0:k0 + 2]
            kvec = rest[k0 + 2] if wk else None
            alpha = rest[k0 + 2 + n_wk]
            tail, priv = split_priv(rest[k0 + 3 + n_wk:])
            h, wire, nrecon, nres = produce(x, g, recon, res, alpha, *tail,
                                            kvec=kvec)
            mix, new_nbrs = mix_one(wire, nbrs, adds, dgate, priv,
                                    PAD_STREAM)
            mixed = self._scope_finish(ddiag * h + mix, x, g, alpha)
            out = (mixed, nrecon, nres) + new_nbrs
            return out + (wire if pipelined else ())

        def body_gt(x, t, g, gp, rx, sx, rt, st, *rest):
            nbrs_x = rest[:nnbr]
            nbrs_t = rest[nnbr:2 * nnbr]
            adds_x = rest[2 * nnbr:2 * nnbr + n_adds]
            adds_t = rest[2 * nnbr + n_adds:2 * nnbr + 2 * n_adds]
            k = 2 * nnbr + 2 * n_adds
            dgate, ddiag = rest[k:k + 2]
            kvec = rest[k + 2] if wk else None
            alpha = rest[k + 2 + n_wk]
            tail, priv = split_priv(rest[k + 3 + n_wk:])
            (h, t_half, wire_x, nrx, nsx, wire_t, nrt, nst) = produce_gt(
                x, t, g, gp, rx, sx, rt, st, alpha, *tail, kvec=kvec
            )
            mix_x, new_x = mix_one(wire_x, nbrs_x, adds_x, dgate, priv,
                                   PAD_STREAM)
            mix_t, new_t = mix_one(wire_t, nbrs_t, adds_t, dgate, priv,
                                   t_stream)
            mixed_x, mixed_t = self._scope_finish_gt(
                ddiag * h + mix_x, ddiag * t_half + mix_t,
                x, t, g, gp, alpha,
            )
            out = (mixed_x, mixed_t, nrx, nsx, nrt, nst) + new_x + new_t
            return out + ((wire_x + wire_t) if pipelined else ())

        sm_dsgd = _shard_map(
            body, mesh=self.mesh,
            in_specs=(spec,) * (4 + nnbr + n_adds) + (nspec, nspec)
            + (nspec,) * n_wk + (P(),)
            + priv_specs + (spec,) * n_noise,
            out_specs=(spec,) * (3 + nnbr + n_wire),
        )
        sm_dsgt = _shard_map(
            body_gt, mesh=self.mesh,
            in_specs=(spec,) * (8 + 2 * nnbr + 2 * n_adds)
            + (nspec, nspec) + (nspec,) * n_wk + (P(),)
            + priv_specs + (spec,) * (2 * n_noise),
            out_specs=(spec,) * (6 + 2 * nnbr + 2 * n_wire),
        )

        ingest = None
        if pipelined:
            def make_ingest(stream_base: int):
                def ingest_body(*args):
                    if sa:
                        wire = tuple(args[:n_wire])
                        priv = tuple(args[n_wire:])
                    else:
                        wire, priv = tuple(args), None
                    return tuple(self._recv_dqs(
                        wire, priv=priv, stream_base=stream_base
                    ))

                return _shard_map(
                    ingest_body, mesh=self.mesh,
                    in_specs=(spec,) * n_wire
                    + ((P(None), P()) if sa else ()),
                    out_specs=(spec,) * n_dirs,
                )

            sm_ingest = make_ingest(PAD_STREAM)
            sm_ingest_t = make_ingest(t_stream)

            def ingest(state: FLState):
                if state.comm is None or wire_keys[0] not in state.comm:
                    raise ValueError(
                        "pipelined rounds need init_fl_state(..., "
                        "engine=...) with the pipelined engine (in-flight "
                        "wire buffers)"
                    )
                priv = (
                    (state.comm["priv_key"], state.comm["topo_round"])
                    if sa else ()
                )
                # the collective consumes the OLDEST ring slot only --
                # k in-flight payloads never multiply the operand bytes
                stale = {"dqs": sm_ingest(
                    *self._ring_slot0(state.comm, wire_keys), *priv
                )}
                if cfg.algorithm == "dsgt":
                    stale["dqs_t"] = sm_ingest_t(
                        *self._ring_slot0(state.comm, wire_keys_t), *priv
                    )
                return stale

        def comm_step(state: FLState, batch: PyTree, stale):
            if state.comm is None:
                raise ValueError(
                    "fused rounds need init_fl_state(..., engine=...)"
                )
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)
                alpha32 = jnp.asarray(alpha, jnp.float32)
                dgate, ddiag, topo_comm, gate_metrics = self._dir_gates(
                    state.comm
                )
                kops = (self._wire_k_vec(state.comm),) if wk else ()
                adds = tuple(stale["dqs"]) if pipelined else ()
                priv = (
                    (state.comm["priv_key"], state.comm["topo_round"])
                    if sa_body else ()
                )
                noises = (
                    (self._dp_noise_full(state.comm, cfg.n_nodes),)
                    if dp else ()
                )

                if cfg.algorithm == "dsgd":
                    outs = sm_dsgd(
                        self._f32(state.params), grads, state.comm["recon"],
                        state.comm["residual"],
                        *[state.comm[k] for k in nbr_keys],
                        *adds, dgate, ddiag, *kops, alpha32, *priv, *noises,
                    )
                    mixed, nrecon, nres = outs[:3]
                    comm = {"recon": nrecon, "residual": nres, **topo_comm}
                    # output order == key order by construction of the bodies
                    comm.update(zip(nbr_keys, outs[3:3 + nnbr]))
                    self._push_wire(
                        state.comm, comm, wire_keys, outs[3 + nnbr:]
                    )
                    new_state = state._replace(
                        step=step, params=self._st(mixed), comm=comm
                    )
                else:
                    adds_t = tuple(stale["dqs_t"]) if pipelined else ()
                    if dp:
                        noises += (self._dp_noise_full(state.comm, cfg.n_nodes,
                                                       tracker=True),)
                    outs = sm_dsgt(
                        self._f32(state.params), self._f32(state.tracker),
                        grads, self._f32(state.prev_grad),
                        state.comm["recon"], state.comm["residual"],
                        state.comm["recon_t"], state.comm["residual_t"],
                        *[state.comm[k] for k in nbr_keys],
                        *[state.comm[k] for k in nbr_keys_t],
                        *adds, *adds_t, dgate, ddiag, *kops, alpha32,
                        *priv, *noises,
                    )
                    (mx, mt, nrx, nsx, nrt, nst) = outs[:6]
                    comm = {"recon": nrx, "residual": nsx,
                            "recon_t": nrt, "residual_t": nst, **topo_comm}
                    comm.update(zip(
                        nbr_keys + nbr_keys_t, outs[6:6 + 2 * nnbr]
                    ))
                    self._push_wire(
                        state.comm, comm, wire_keys + wire_keys_t,
                        outs[6 + 2 * nnbr:],
                    )
                    new_state = FLState(
                        step=step, params=self._st(mx), tracker=self._st(mt),
                        prev_grad=self._st(grads), comm=comm,
                    )

            metrics = self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, gate_metrics
            )
            if wk:
                with jax.named_scope(STAGE_METRICS):
                    metrics["wire_bytes_effective"] = self._wire_k_bytes(
                        kops[0], wires=2 if cfg.algorithm == "dsgt" else 1
                    )
            return new_state, metrics

        return ingest, comm_step

    def _make_dynamic_round_dense(self, eval_grads, schedule, cfg: FLConfig,
                                  pipelined: bool):
        """Dynamic round on the DENSE all-gather wire: the same ONE
        all-gather per wire buffer as the static dense path (a dynamic
        program adds zero collectives), but the pre-weighted ``mix_recon``
        accumulator -- impossible under a per-round W -- is replaced by
        ``nbr_recon_all``: every dq reaches every node anyway, so each
        node keeps an UNWEIGHTED (n, t) replica of all reconstructions
        and contracts its traced W_r row against it at mix time
        (``mix_i = W_r[i] @ nbr_recon_all_i``). Pipelined/bounded rounds
        gather the ring's OLDEST in-flight payload inside the comm body
        (the dense wire has no separate pre-scan collective) and push
        this round's payload onto the ring."""
        produce, produce_gt = self._make_produce()
        egress = self.wire_bytes(cfg)
        spec = P(self.node_axes, self.model_axis)
        nspec = P(self.node_axes, None)
        spec3 = P(self.node_axes, None, self.model_axis)
        wk = bool(getattr(self.node_program, "heterogeneous_wire_k", False))
        n_wk = 1 if wk else 0
        dc = self.difference_coding
        n = self.n_nodes
        nbr_keys = self._nbr_key_names("")
        nbr_keys_t = self._nbr_key_names("_t")
        nnbr = len(nbr_keys)  # 1 with difference coding, else 0
        wire_keys = self._wire_key_names("") if pipelined else ()
        wire_keys_t = self._wire_key_names("_t") if pipelined else ()
        n_wire = len(wire_keys)
        n_stale = n_wire if pipelined else 0
        dp = self._dp
        n_noise = 1 if dp else 0

        def gather_dq(wire):
            """ONE all-gather per wire buffer -> every node's dense dq."""
            with jax.named_scope(STAGE_TRANSPORT):
                gathered = tuple(
                    jax.lax.all_gather(
                        b[0], self.node_axes, tiled=False
                    ).reshape(n, -1)
                    for b in wire
                )
            return self._dq_full(gathered)

        def mix_one(wire, stale_wire, nbr, w_row):
            dq = gather_dq(stale_wire if pipelined else wire)
            new_all = (nbr[0] + dq) if dc else dq  # (n, t)
            mix = (w_row[0] @ new_all)[None]
            return mix, ((new_all[None],) if dc else ())

        def body(x, g, recon, res, *rest):
            nbrs = rest[:nnbr]
            stale_wire = rest[nnbr:nnbr + n_stale]
            k = nnbr + n_stale
            w_row, ddiag = rest[k:k + 2]
            kvec = rest[k + 2] if wk else None
            alpha = rest[k + 2 + n_wk]
            noises = rest[k + 3 + n_wk:]
            h, wire, nrecon, nres = produce(x, g, recon, res, alpha,
                                            *noises, kvec=kvec)
            mix, new_nbr = mix_one(wire, stale_wire, nbrs[0] if dc else None,
                                   w_row)
            mixed = self._scope_finish(ddiag * h + mix, x, g, alpha)
            out = (mixed, nrecon, nres) + new_nbr
            return out + (wire if pipelined else ())

        def body_gt(x, t, g, gp, rx, sx, rt, st, *rest):
            nbrs_x = rest[:nnbr]
            nbrs_t = rest[nnbr:2 * nnbr]
            stale_x = rest[2 * nnbr:2 * nnbr + n_stale]
            stale_t = rest[2 * nnbr + n_stale:2 * nnbr + 2 * n_stale]
            k = 2 * nnbr + 2 * n_stale
            w_row, ddiag = rest[k:k + 2]
            kvec = rest[k + 2] if wk else None
            alpha = rest[k + 2 + n_wk]
            noises = rest[k + 3 + n_wk:]
            (h, t_half, wire_x, nrx, nsx, wire_t, nrt, nst) = produce_gt(
                x, t, g, gp, rx, sx, rt, st, alpha, *noises, kvec=kvec
            )
            mix_x, new_x = mix_one(wire_x, stale_x,
                                   nbrs_x[0] if dc else None, w_row)
            mix_t, new_t = mix_one(wire_t, stale_t,
                                   nbrs_t[0] if dc else None, w_row)
            mixed_x, mixed_t = self._scope_finish_gt(
                ddiag * h + mix_x, ddiag * t_half + mix_t,
                x, t, g, gp, alpha,
            )
            out = (mixed_x, mixed_t, nrx, nsx, nrt, nst) + new_x + new_t
            return out + ((wire_x + wire_t) if pipelined else ())

        sm_dsgd = _shard_map(
            body, mesh=self.mesh,
            in_specs=(spec,) * 4 + (spec3,) * nnbr + (spec,) * n_stale
            + (nspec, nspec) + (nspec,) * n_wk + (P(),)
            + (spec,) * n_noise,
            out_specs=(spec,) * 3 + (spec3,) * nnbr + (spec,) * n_wire,
        )
        sm_dsgt = _shard_map(
            body_gt, mesh=self.mesh,
            in_specs=(spec,) * 8 + (spec3,) * 2 * nnbr
            + (spec,) * 2 * n_stale + (nspec, nspec)
            + (nspec,) * n_wk + (P(),)
            + (spec,) * (2 * n_noise),
            out_specs=(spec,) * 6 + (spec3,) * 2 * nnbr
            + (spec,) * 2 * n_wire,
        )

        def comm_step(state: FLState, batch: PyTree, stale):
            if state.comm is None:
                raise ValueError(
                    "fused rounds need init_fl_state(..., engine=...)"
                )
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)
                alpha32 = jnp.asarray(alpha, jnp.float32)
                w_off_r, w_diag_r, topo_comm, gate_metrics = self._round_gates(
                    state.comm
                )
                w_row = jnp.asarray(w_off_r, jnp.float32)
                ddiag = jnp.asarray(w_diag_r, jnp.float32).reshape(n, 1)
                kops = (self._wire_k_vec(state.comm),) if wk else ()
                adds = (
                    self._ring_slot0(state.comm, wire_keys)
                    if pipelined else ()
                )
                noises = (
                    (self._dp_noise_full(state.comm, cfg.n_nodes),)
                    if dp else ()
                )

                if cfg.algorithm == "dsgd":
                    outs = sm_dsgd(
                        self._f32(state.params), grads, state.comm["recon"],
                        state.comm["residual"],
                        *[state.comm[k] for k in nbr_keys],
                        *adds, w_row, ddiag, *kops, alpha32, *noises,
                    )
                    mixed, nrecon, nres = outs[:3]
                    comm = {"recon": nrecon, "residual": nres, **topo_comm}
                    comm.update(zip(nbr_keys, outs[3:3 + nnbr]))
                    self._push_wire(
                        state.comm, comm, wire_keys, outs[3 + nnbr:]
                    )
                    new_state = state._replace(
                        step=step, params=self._st(mixed), comm=comm
                    )
                else:
                    adds_t = (
                        self._ring_slot0(state.comm, wire_keys_t)
                        if pipelined else ()
                    )
                    if dp:
                        noises += (self._dp_noise_full(state.comm, cfg.n_nodes,
                                                       tracker=True),)
                    outs = sm_dsgt(
                        self._f32(state.params), self._f32(state.tracker),
                        grads, self._f32(state.prev_grad),
                        state.comm["recon"], state.comm["residual"],
                        state.comm["recon_t"], state.comm["residual_t"],
                        *[state.comm[k] for k in nbr_keys],
                        *[state.comm[k] for k in nbr_keys_t],
                        *adds, *adds_t, w_row, ddiag, *kops, alpha32, *noises,
                    )
                    (mx, mt, nrx, nsx, nrt, nst) = outs[:6]
                    comm = {"recon": nrx, "residual": nsx,
                            "recon_t": nrt, "residual_t": nst, **topo_comm}
                    comm.update(zip(
                        nbr_keys + nbr_keys_t, outs[6:6 + 2 * nnbr]
                    ))
                    self._push_wire(
                        state.comm, comm, wire_keys + wire_keys_t,
                        outs[6 + 2 * nnbr:],
                    )
                    new_state = FLState(
                        step=step, params=self._st(mx), tracker=self._st(mt),
                        prev_grad=self._st(grads), comm=comm,
                    )

            metrics = self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, gate_metrics
            )
            if wk:
                with jax.named_scope(STAGE_METRICS):
                    metrics["wire_bytes_effective"] = self._wire_k_bytes(
                        kops[0], wires=2 if cfg.algorithm == "dsgt" else 1
                    )
            return new_state, metrics

        return None, comm_step

    def _make_comm_step_dynamic(self, eval_grads, schedule, cfg: FLConfig):
        _, comm_step = self._make_dynamic_round(
            eval_grads, schedule, cfg, pipelined=False
        )
        return lambda state, batch: comm_step(state, batch, None)

    def make_comm_step(self, eval_grads, schedule, cfg: FLConfig):
        if self.dynamic_round:
            return self._make_comm_step_dynamic(eval_grads, schedule, cfg)
        w_diag, w_off = self._round_constants(cfg)
        produce, produce_gt = self._make_produce()
        egress = self.wire_bytes(cfg)
        spec = P(self.node_axes, self.model_axis)

        # With difference coding, recon_j' = recon_j + dq_j, so the
        # neighbor-mix term accumulates: mix_recon' = mix_recon + S W dq.
        # WITHOUT it, recon_j' = dq_j alone, so the term is rebuilt from
        # this round's wire and mix_recon stays zero (replace, don't sum).
        dc = self.difference_coding
        # Privacy operands ride the SAME shard_map call: DP noise rows
        # shard like every (n, t) buffer; the pad key/round replicate.
        dp, sa = self._dp, self._sa_wire
        n_noise = 1 if dp else 0
        priv_specs = (P(None), P()) if sa else ()
        t_stream = PAD_STREAM + TRACKER_STREAM_OFFSET

        def split_extra(extra, wires):
            noises = extra[:n_noise * wires]
            priv = tuple(extra[n_noise * wires:]) or None
            return noises, priv

        def body(x, g, recon, res, mix_recon, alpha, w_diag, w_off, *extra):
            noises, priv = split_extra(extra, 1)
            h, wire, nrecon, nres = produce(x, g, recon, res, alpha, *noises)
            mix_add = self._wire_mix(wire, w_off, priv=priv)
            new_mix = mix_recon + mix_add if dc else mix_add
            mixed = self._scope_finish(
                self._self_weight(w_diag) * h + new_mix, x, g, alpha
            )
            return mixed, nrecon, nres, new_mix

        def body_gt(x, t, g, gp, rx, sx, mrx, rt, st, mrt, alpha, w_diag,
                    w_off, *extra):
            noises, priv = split_extra(extra, 2)
            (h, t_half, wire_x, nrx, nsx, wire_t, nrt, nst) = produce_gt(
                x, t, g, gp, rx, sx, rt, st, alpha, *noises
            )
            w_self = self._self_weight(w_diag)
            mix_x = self._wire_mix(wire_x, w_off, priv=priv)
            mix_t = self._wire_mix(wire_t, w_off, priv=priv,
                                   stream_base=t_stream)
            new_mrx = mrx + mix_x if dc else mix_x
            new_mrt = mrt + mix_t if dc else mix_t
            mixed_x, mixed_t = self._scope_finish_gt(
                w_self * h + new_mrx, w_self * t_half + new_mrt,
                x, t, g, gp, alpha,
            )
            return mixed_x, mixed_t, nrx, nsx, new_mrx, nrt, nst, new_mrt

        rep = P(None, None)
        sm_dsgd = _shard_map(
            body, mesh=self.mesh,
            in_specs=(spec,) * 5 + (P(), P(None), rep)
            + (spec,) * n_noise + priv_specs,
            out_specs=(spec,) * 4,
        )
        sm_dsgt = _shard_map(
            body_gt, mesh=self.mesh,
            in_specs=(spec,) * 10 + (P(), P(None), rep)
            + (spec,) * (2 * n_noise) + priv_specs,
            out_specs=(spec,) * 8,
        )

        def priv_operands(comm, wires):
            ops = ()
            if dp:
                ops += (self._dp_noise_full(comm, cfg.n_nodes),)
                if wires == 2:
                    ops += (self._dp_noise_full(comm, cfg.n_nodes,
                                                tracker=True),)
            if sa:
                ops += (comm["priv_key"], comm["topo_round"])
            return ops

        def comm_step(state: FLState, batch: PyTree):
            if state.comm is None:
                raise ValueError(
                    "fused rounds need init_fl_state(..., engine=...)"
                )
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)
                alpha32 = jnp.asarray(alpha, jnp.float32)
                priv_comm = self._priv_comm(state.comm)

                if cfg.algorithm == "dsgd":
                    mixed, nrecon, nres, new_mix = sm_dsgd(
                        self._f32(state.params), grads, state.comm["recon"],
                        state.comm["residual"], state.comm["mix_recon"],
                        alpha32, w_diag, w_off, *priv_operands(state.comm, 1),
                    )
                    new_state = state._replace(
                        step=step, params=self._st(mixed),
                        comm={"recon": nrecon, "residual": nres,
                              "mix_recon": new_mix, **priv_comm},
                    )
                else:
                    (mx, mt, nrx, nsx, nmrx, nrt, nst, nmrt) = sm_dsgt(
                        self._f32(state.params), self._f32(state.tracker),
                        grads, self._f32(state.prev_grad),
                        state.comm["recon"], state.comm["residual"],
                        state.comm["mix_recon"], state.comm["recon_t"],
                        state.comm["residual_t"], state.comm["mix_recon_t"],
                        alpha32, w_diag, w_off, *priv_operands(state.comm, 2),
                    )
                    new_state = FLState(
                        step=step, params=self._st(mx), tracker=self._st(mt),
                        prev_grad=self._st(grads),
                        comm={"recon": nrx, "residual": nsx, "mix_recon": nmrx,
                              "recon_t": nrt, "residual_t": nst,
                              "mix_recon_t": nmrt, **priv_comm},
                    )

            return new_state, self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, {}
            )

        return comm_step

    def _make_pipelined_round_dynamic(self, eval_grads, schedule,
                                      cfg: FLConfig):
        """Dynamic-topology pipelined round: ingest ppermutes the
        IN-FLIGHT wire per direction (before the local-step scan, exactly
        like the static path) but returns the per-direction dq
        UNWEIGHTED; the comm step folds each into its neighbor-recon
        accumulator and weights by THIS round's traced gate -- one-round-
        stale neighbor state mixed over the current round's graph,
        matching the fused engine's ``stale_mix`` with per-round W."""
        return self._make_dynamic_round(
            eval_grads, schedule, cfg, pipelined=True
        )

    def make_pipelined_round(self, eval_grads, schedule, cfg: FLConfig):
        """The split round: ``ingest`` runs the collective on the
        IN-FLIGHT payload buffers (``wire_*`` in ``FLState.comm``) --
        nothing it reads depends on this round's compute, so it lands
        BEFORE the local-step scan in the jaxpr; ``comm_step`` produces
        this round's payload (stored for the next round), folds the
        ingested stale neighbor term into ``mix_recon``, and mixes
        ``w_self * h + mix_recon'`` -- one-round-stale neighbor
        information, exactly sequential-with-delay."""
        if not self.pipelined:
            raise ValueError(
                "engine was built with round_schedule='sequential'; build "
                "it with round_schedule='pipelined'"
            )
        if self.dynamic_round:
            return self._make_pipelined_round_dynamic(
                eval_grads, schedule, cfg
            )
        w_diag, w_off = self._round_constants(cfg)
        produce, produce_gt = self._make_produce()
        egress = self.wire_bytes(cfg)
        spec = P(self.node_axes, self.model_axis)
        rep = P(None, None)
        nw = 3 if self.compact_wire else 2
        dc = self.difference_coding
        wire_keys = self._wire_key_names("")
        wire_keys_t = self._wire_key_names("_t")
        dp, sa = self._dp, self._sa_wire
        n_noise = 1 if dp else 0

        # The masked transport lives entirely inside ingest (the comm
        # bodies carry no collective): mask -> ppermute -> unmask with
        # the CURRENT round counter on both ends -- pads never need to
        # match the payload's production round, only the two transport
        # endpoints, which share the replicated (key, r) operands.
        def make_ingest(stream_base: int):
            def ingest_body(*args):
                if sa:
                    wire, w_off = args[:nw], args[nw]
                    priv = tuple(args[nw + 1:])
                else:
                    wire, w_off, priv = args[:-1], args[-1], None
                return self._wire_mix(tuple(wire), w_off, priv=priv,
                                      stream_base=stream_base)

            return _shard_map(
                ingest_body, mesh=self.mesh,
                in_specs=(spec,) * nw + (rep,)
                + ((P(None), P()) if sa else ()),
                out_specs=spec,
            )

        sm_ingest = make_ingest(PAD_STREAM)
        sm_ingest_t = make_ingest(PAD_STREAM + TRACKER_STREAM_OFFSET)

        def ingest(state: FLState):
            if state.comm is None or wire_keys[0] not in state.comm:
                raise ValueError(
                    "pipelined rounds need init_fl_state(..., engine=...) "
                    "with the pipelined engine (in-flight wire buffers)"
                )
            priv = (
                (state.comm["priv_key"], state.comm["topo_round"])
                if sa else ()
            )
            # the collective consumes the OLDEST ring slot only -- depth-k
            # staleness never multiplies the operand bytes per round
            stale = {"mix": sm_ingest(
                *self._ring_slot0(state.comm, wire_keys), w_off, *priv
            )}
            if cfg.algorithm == "dsgt":
                stale["mix_t"] = sm_ingest_t(
                    *self._ring_slot0(state.comm, wire_keys_t), w_off, *priv
                )
            return stale

        # The comm bodies carry NO collective: the wire payload produced
        # here is stored in comm and ingested at the top of the next round.
        def body(x, g, recon, res, mix_recon, mix_add, alpha, w_diag,
                 *noises):
            h, wire, nrecon, nres = produce(x, g, recon, res, alpha,
                                            *noises)
            stale_mix = mix_recon + mix_add if dc else mix_add
            mixed = self._scope_finish(
                self._self_weight(w_diag) * h + stale_mix, x, g, alpha
            )
            return (mixed, nrecon, nres, stale_mix) + wire

        def body_gt(x, t, g, gp, rx, sx, mrx, rt, st, mrt, add_x, add_t,
                    alpha, w_diag, *noises):
            (h, t_half, wire_x, nrx, nsx, wire_t, nrt, nst) = produce_gt(
                x, t, g, gp, rx, sx, rt, st, alpha, *noises
            )
            w_self = self._self_weight(w_diag)
            stale_x = mrx + add_x if dc else add_x
            stale_t = mrt + add_t if dc else add_t
            mixed_x, mixed_t = self._scope_finish_gt(
                w_self * h + stale_x, w_self * t_half + stale_t,
                x, t, g, gp, alpha,
            )
            return ((mixed_x, mixed_t, nrx, nsx, stale_x, nrt, nst, stale_t)
                    + wire_x + wire_t)

        sm_dsgd = _shard_map(
            body, mesh=self.mesh,
            in_specs=(spec,) * 6 + (P(), P(None)) + (spec,) * n_noise,
            out_specs=(spec,) * (4 + nw),
        )
        sm_dsgt = _shard_map(
            body_gt, mesh=self.mesh,
            in_specs=(spec,) * 12 + (P(), P(None)) + (spec,) * (2 * n_noise),
            out_specs=(spec,) * (8 + 2 * nw),
        )

        def comm_step(state: FLState, batch: PyTree, stale):
            step = state.step + 1
            alpha = schedule(step)
            losses, grads = eval_grads(state.params, batch)
            with jax.named_scope(STAGE_WIRE):
                grads = grads.astype(jnp.float32)
                alpha32 = jnp.asarray(alpha, jnp.float32)
                priv_comm = self._priv_comm(state.comm)
                noises = (
                    (self._dp_noise_full(state.comm, cfg.n_nodes),)
                    if dp else ()
                )

                if cfg.algorithm == "dsgd":
                    outs = sm_dsgd(
                        self._f32(state.params), grads, state.comm["recon"],
                        state.comm["residual"], state.comm["mix_recon"],
                        stale["mix"], alpha32, w_diag, *noises,
                    )
                    mixed, nrecon, nres, new_mix = outs[:4]
                    comm = {"recon": nrecon, "residual": nres,
                            "mix_recon": new_mix, **priv_comm}
                    self._push_wire(state.comm, comm, wire_keys, outs[4:])
                    new_state = state._replace(
                        step=step, params=self._st(mixed), comm=comm
                    )
                else:
                    if dp:
                        noises += (self._dp_noise_full(state.comm, cfg.n_nodes,
                                                       tracker=True),)
                    outs = sm_dsgt(
                        self._f32(state.params), self._f32(state.tracker),
                        grads, self._f32(state.prev_grad),
                        state.comm["recon"], state.comm["residual"],
                        state.comm["mix_recon"], state.comm["recon_t"],
                        state.comm["residual_t"], state.comm["mix_recon_t"],
                        stale["mix"], stale["mix_t"], alpha32, w_diag, *noises,
                    )
                    (mx, mt, nrx, nsx, nmrx, nrt, nst, nmrt) = outs[:8]
                    comm = {"recon": nrx, "residual": nsx, "mix_recon": nmrx,
                            "recon_t": nrt, "residual_t": nst,
                            "mix_recon_t": nmrt, **priv_comm}
                    self._push_wire(
                        state.comm, comm, wire_keys, outs[8:8 + nw]
                    )
                    self._push_wire(
                        state.comm, comm, wire_keys_t, outs[8 + nw:]
                    )
                    new_state = FLState(
                        step=step, params=self._st(mx), tracker=self._st(mt),
                        prev_grad=self._st(grads), comm=comm,
                    )

            return new_state, self._round_metrics(
                cfg, losses, grads, alpha, new_state, egress, {}
            )

        return ingest, comm_step

    @classmethod
    def simulated(cls, w, stacked_params, **_ignored):
        raise ValueError(
            "sharded_fused needs a device mesh (use from_mesh); on a single "
            "host use the 'fused' engine -- identical math, dense W"
        )

    @classmethod
    def from_mesh(cls, mesh: Mesh, node_axes: Sequence[str], stacked_sds,
                  *, wire_dtype=None, axes_subset=None, scale_chunk: int = 512,
                  topk=None, impl: str = "pallas", w=None,
                  error_feedback: bool = True, difference_coding: bool = True,
                  self_weight=None, compact=None, round_schedule=None,
                  storage_dtype=None, topology_program=None,
                  node_program=None, privacy=None, model_axis=None,
                  scope=None, **_ignored):
        _reject_wire_dtype(wire_dtype)
        shards = int(mesh.shape[model_axis]) if model_axis is not None else 1
        layout = pack_layout(
            stacked_sds, pad_to=scale_chunk,
            storage_dtype=storage_dtype or jnp.float32, shards=shards,
        )
        return cls(mesh, node_axes, layout, w=w, axes_subset=axes_subset,
                   self_weight=self_weight, model_axis=model_axis,
                   scale_chunk=scale_chunk,
                   topk=topk, impl=impl, error_feedback=error_feedback,
                   difference_coding=difference_coding, compact=compact,
                   round_schedule=round_schedule,
                   topology_program=topology_program,
                   node_program=node_program, privacy=privacy,
                   scope=scope)
