"""The program's named scopes in a device trace.

The program opens a ``jax.named_scope`` for each stage of its round
(``fl_local``, ``fl_wire``, ``fl_transport``, ``fl_metrics``) and for some
parts inside them, and the compiled program carries the scopes in each
instruction's ``op_name`` metadata. ``scope_map`` reads the compiled
round's text (the text that ``harness.wire_kernel_names`` reads) into a map
from instruction name, which is how a device trace names an operation, to
the scopes it runs in; ``scope_seconds`` gives one chip's busy seconds in
each scope. ``stage_map`` and ``stage_seconds`` split the busy time among
the innermost stages, so that the parts sum to the busy time.

``harness.run`` maps the scopes that the per-layer readers declare
(``SCOPE`` in ``metrics/<name>.py``) after the window, and
``trace_reduce.reduce`` adds each chip's ``scope_s``.
"""

from __future__ import annotations

import re
from typing import Dict, Hashable, Iterable, List, Sequence, Tuple

__all__ = ["STAGES", "UNSCOPED", "scope_map", "scope_seconds", "stage_map",
           "stage_seconds", "scope_ms_per_round"]

#: the program's round stages, as its named scopes write them
STAGES = ("fl_local", "fl_wire", "fl_transport", "fl_metrics")
#: the stage of an instruction that the program put under none
UNSCOPED = "unscoped"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def scope_map(hlo_text: str,
              scopes: Iterable[str]) -> Dict[str, Tuple[str, ...]]:
    """Each compiled instruction's scopes: those of ``scopes`` that are a
    component of its ``op_name`` path, outermost first (scopes nest: the
    sharded engine's ``fl_transport`` sits inside ``fl_wire``). An
    instruction with none in its metadata -- the compiler makes layout
    copies and the loops that run them without any -- takes the scopes of
    the first of its operands that has some, found through operands that
    have none; failing that it has none."""
    scopes = set(scopes)
    own: Dict[str, Tuple[str, ...]] = {}
    operands: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        lhs, rhs = line.split(" = ", 1)
        rhs, _, meta = rhs.partition("metadata={")
        m = _OP_NAME.search(meta)
        name = lhs.split()[-1].lstrip("%")
        own[name] = tuple(c for c in m.group(1).split("/")
                          if c in scopes) if m else ()
        operands[name] = _REF.findall(rhs)
    out: Dict[str, Tuple[str, ...]] = {}
    for root in own:
        todo = [root]
        while todo:  # depth first through operands without a scope
            name = todo[-1]
            if name in out:
                todo.pop()
                continue
            if own[name]:
                out[name] = own[name]
                todo.pop()
                continue
            refs = [r for r in operands[name] if r in own]
            pending = [r for r in refs if r not in out and r not in todo]
            if pending:
                todo.extend(reversed(pending))
                continue
            out[name] = next((out[r] for r in refs if out.get(r)), ())
            todo.pop()
    return out


def stage_map(hlo_text: str) -> Dict[str, str]:
    """Each compiled instruction's innermost stage (``scope_map`` over
    ``STAGES``), ``UNSCOPED`` where it has none. A program without stage
    scopes maps every instruction to ``UNSCOPED``."""
    return {k: v[-1] if v else UNSCOPED
            for k, v in scope_map(hlo_text, STAGES).items()}


def _split(events, label_of: Dict[str, Hashable],
           none: Hashable) -> Dict[Hashable, float]:
    """Busy seconds of each label from one chip's ``[name, start_ns,
    end_ns]`` operations. Operations nest (a ``while`` spans its body's
    operations), so each busy instant goes to the innermost operation
    running then, the one that started last. An operation whose label is
    ``none`` takes the label of the innermost operation that contains it.
    The values sum to the union of the operations' intervals, the chip's
    busy time."""
    out: Dict[Hashable, float] = {}
    stack: List[Tuple[float, Hashable]] = []  # open operations: (end, label)
    t = None  # the instant attributed up to

    def advance(to):
        nonlocal t
        while stack:
            end, label = stack[-1]
            if end > to:
                if to > t:
                    out[label] = out.get(label, 0.0) + (to - t)
                    t = to
                return
            if end > t:
                out[label] = out.get(label, 0.0) + (end - t)
                t = end
            stack.pop()
        t = to if t is None else max(t, to)

    for name, start, end in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        advance(start)
        label = label_of.get(name, none)
        if label == none:
            # the innermost open operation that contains this one
            label = next((lab for e, lab in reversed(stack) if e >= end),
                         none)
        stack.append((end, label))
    advance(float("inf"))
    return {k: v * 1e-9 for k, v in out.items()}


def stage_seconds(events, stages: Dict[str, str]) -> Dict[str, float]:
    """Busy seconds of each innermost stage (``stage_map``) from one chip's
    operations; an operation without one is ``UNSCOPED`` unless an
    operation with a stage contains it. The values sum to the busy time."""
    return _split(events, stages, UNSCOPED)


def scope_seconds(events, scopes_of: Dict[str, Tuple[str, ...]]
                  ) -> Dict[str, float]:
    """Inclusive busy seconds of each scope (``scope_map``) from one chip's
    operations: each busy instant goes to the innermost operation running
    then, as in ``stage_seconds``, and counts once for every scope that
    operation runs in. A scope with no busy instant is left out."""
    out: Dict[str, float] = {}
    for label, secs in _split(events, scopes_of, ()).items():
        for scope in set(label):
            out[scope] = out.get(scope, 0.0) + secs
    return out


def scope_ms_per_round(ctx: dict, scope: str):
    """A per-layer reading: ``scope``'s busy ms a round, the mean over the
    chips the cell uses (a chip without it counts 0). None where no chip
    ran an operation in it."""
    chips: Sequence[dict] = ctx["trace"].get("chips", [])
    got = [c.get("scope_s", {}).get(scope) for c in chips]
    if not any(got):
        return None
    return 1e3 * sum(g or 0.0 for g in got) / len(chips) / ctx["rounds"]
