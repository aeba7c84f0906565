"""The program's round stages in a device trace.

The program opens a ``jax.named_scope`` for each stage of its round
(``fl_local``, ``fl_wire``, ``fl_transport``, ``fl_metrics``), and the
compiled program carries the scope in each instruction's ``op_name``
metadata. ``stage_map`` reads the compiled round's text (the text that
``harness.wire_kernel_names`` reads) into a map from instruction name,
which is how a device trace names an operation, to stage.
``stage_seconds`` splits the busy time of one chip's operations (in the
plain form of ``trace_reduce.load``) among the stages.

Nothing in ``harness.run`` calls these yet: reading them into per-layer
metrics needs ``run`` to pass the map to ``trace_reduce.reduce`` and the
reduction to add each chip's ``stage_s``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

__all__ = ["STAGES", "UNSCOPED", "stage_map", "stage_seconds"]

#: the program's round stages, as its named scopes write them
STAGES = ("fl_local", "fl_wire", "fl_transport", "fl_metrics")
#: the stage of an instruction that the program put under none
UNSCOPED = "unscoped"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def stage_map(hlo_text: str) -> Dict[str, str]:
    """Each compiled instruction's stage: the innermost component of its
    ``op_name`` path that is a stage name (stages nest where a collective
    sits inside the sharded engine's wire stage). An instruction with no
    stage in its metadata -- the compiler makes layout copies and the
    loops that run them without any -- takes the stage of the first of
    its operands that has one, found through operands that have none;
    failing that it is ``UNSCOPED``. A program without stage scopes maps
    every instruction to ``UNSCOPED``."""
    own: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        rhs, _, meta = rhs.partition("metadata={")
        m = _OP_NAME.search(meta)
        names = [c for c in m.group(1).split("/") if c in STAGES] if m else []
        name = lhs.split()[-1].lstrip("%")
        own[name] = names[-1] if names else UNSCOPED
        operands[name] = _REF.findall(rhs)
    out: Dict[str, str] = {}
    for root in own:
        todo = [root]
        while todo:  # depth first through operands without a stage
            name = todo[-1]
            if name in out:
                todo.pop()
                continue
            if own[name] != UNSCOPED:
                out[name] = own[name]
                todo.pop()
                continue
            refs = [r for r in operands[name] if r in own]
            pending = [r for r in refs if r not in out and r not in todo]
            if pending:
                todo.extend(reversed(pending))
                continue
            out[name] = next((out[r] for r in refs
                              if out.get(r, UNSCOPED) != UNSCOPED), UNSCOPED)
            todo.pop()
    return out


def stage_seconds(events, stages: Dict[str, str]) -> Dict[str, float]:
    """Busy seconds of each stage from one chip's ``[name, start_ns,
    end_ns]`` operations. Operations nest (a ``while`` spans its body's
    operations), so each busy instant goes to the innermost operation
    running then, the one that started last. An operation that ``stages``
    maps to no stage takes the stage of the innermost operation that
    contains it, else ``UNSCOPED``. The values sum to the union of the
    operations' intervals, the chip's busy time."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []  # open operations: (end, stage)
    t = None  # the instant attributed up to

    def advance(to):
        nonlocal t
        while stack:
            end, stage = stack[-1]
            if end > to:
                if to > t:
                    out[stage] = out.get(stage, 0.0) + (to - t)
                    t = to
                return
            if end > t:
                out[stage] = out.get(stage, 0.0) + (end - t)
                t = end
            stack.pop()
        t = to if t is None else max(t, to)

    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        advance(start)
        stage = stages.get(name, UNSCOPED)
        if stage == UNSCOPED:
            # the innermost open operation that contains this one
            stage = next((s for e, s in reversed(stack) if e >= end),
                         UNSCOPED)
        stack.append((end, stage))
    advance(float("inf"))
    return {k: v * 1e-9 for k, v in out.items()}
