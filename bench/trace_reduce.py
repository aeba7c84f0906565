"""Reduction of a profiler trace to the quantities the per-layer metrics
read.

A trace is first brought to a plain form (``load``): for each chip used,
its device operations as ``[name, start_ns, end_ns]``, and the
benchmark's own host spans (``bench_window`` around the measured window;
``bench_batch``, ``bench_dispatch`` and ``bench_fetch`` around each
round's host work). ``reduce`` works on that form only, so a small trace
kept as JSON tests it without a chip.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<id>`` plane, named by their HLO instruction. Host spans come from the host plane. Both sit
on the profiler's one clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import stages

__all__ = ["load", "union_length", "reduce"]

HOST_SPANS = ("bench_window", "bench_batch", "bench_dispatch", "bench_fetch")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def op_name(raw: str) -> str:
    """A device operation's HLO instruction name: the trace may give the
    whole instruction text (``%fusion.3 = f32[...] fusion(...)``)."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def load(trace_dir: str, device_ids: Sequence[int]) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` in plain form."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) in device_ids and line.name == OPS_LINE:
                devices.setdefault(m.group(1), []).extend(
                    [op_name(e.name), e.start_ns, e.start_ns + e.duration_ns]
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def _merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def reduce(trace: dict, kernels: Sequence[str], top: int = 10,
           scopes_of: Optional[Dict[str, Tuple[str, ...]]] = None) -> dict:
    """Per-chip busy and kernel seconds inside the measured window, and the
    breakdown of device operations and idle gaps.

    ``kernels``: the device operation names of the wire stage.
    ``scopes_of``: ``stages.scope_map`` of the compiled round; with it each
    chip also has ``scope_s``, the inclusive busy seconds of each scope. A
    chip with no operation in the window is left out; with none at all the
    result has no ``chips``."""
    windows = [(s, e) for n, s, e in trace["host"] if n == "bench_window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench_window span, found {len(windows)}")
    lo, hi = windows[0]
    kernels = set(kernels)
    chips, op_time = [], {}
    for dev, events in sorted(trace["devices"].items()):
        ev = _clip(events, lo, hi)
        if not ev:
            continue
        busy = _merge((s, e) for _, s, e in ev)
        chips.append({
            "device": dev,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "kernel_s": sum(e - s for n, s, e in ev if n in kernels) * 1e-9,
            "kernel_events": sum(1 for n, _, _ in ev if n in kernels),
            "gaps": [(s, e) for (_, s), (e, _) in zip(busy, busy[1:])]
            + ([(lo, busy[0][0])] if busy[0][0] > lo else [])
            + ([(busy[-1][1], hi)] if busy[-1][1] < hi else []),
        })
        if scopes_of is not None:
            chips[-1]["scope_s"] = stages.scope_seconds(ev, scopes_of)
        for n, s, e in ev:
            op_time[n] = op_time.get(n, 0.0) + (e - s) * 1e-9
    out = {"window_s": (hi - lo) * 1e-9}
    if not chips:
        return out
    out["chips"] = chips
    out["device_ops"] = [[n, t / len(chips)] for n, t in
                         sorted(op_time.items(), key=lambda kv: -kv[1])[:top]]
    spans = [(n, s, e) for n, s, e in trace["host"] if n != "bench_window"]

    def host_during(s, e):
        best, name = 0.0, "no host span"
        for n, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, n
        return name

    gaps = sorted(chips[0]["gaps"], key=lambda g: g[0] - g[1])[:top]
    out["idle_gaps"] = [[host_during(s, e), (e - s) * 1e-9] for s, e in gaps]
    for c in chips:
        del c["gaps"]
    return out
