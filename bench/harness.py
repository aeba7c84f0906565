"""The benchmark harness.

``resolve`` finds everything a workload needs by the names in
``BENCHMARK.json``: the configuration (``configs/<config>.json``, with its
plain reference ``configs/<config>.py``), the traffic mix
(``traffic/<traffic>.json``), the program adapter (``systems/<system>.py``),
the engine adapter (``engines/<engine>.py``), the cell's limits
(``limits/<workload>.json``) and each per-layer metric's reader
(``metrics/<metric>.py``; one that declares ``SCOPE`` reads the device time
of that named scope of the program). ``run`` builds the cell from the seed, drives its
first rounds through the window's own call and feed, measures the window,
and compares those first rounds with the reference.

The round is ``make_fl_round``'s, jitted with its state donated, as the
program's trainer builds it; each round does the trainer's host work:
stack Q step batches, one call, fetch the round's scalar metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import correctness  # noqa: E402
import reference  # noqa: E402
import stages  # noqa: E402
import trace_reduce  # noqa: E402
from traffic import jax_seed, make_pool, mixing_weights, round_batch  # noqa: E402

__all__ = ["Cell", "resolve", "run", "result_line", "peaks",
           "wire_kernel_names"]

#: the round's scalar metrics the trainer fetches every round
FETCH = ("loss", "local_loss", "grad_norm_sq", "consensus_err", "alpha")
#: rounds set-up drives through the window's call; the reference follows them
SETUP_ROUNDS = 3


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_module(path: Path) -> ModuleType:
    name = "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: ModuleType  # the configuration's plain reference
    system: ModuleType  # the program's side of the configuration
    engine: ModuleType  # the program's round engine for this traffic
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, ModuleType]  # per-layer metric name -> its reader

    def scopes(self) -> List[str]:
        """The named scopes the cell's per-layer readers declare."""
        return sorted({r.SCOPE for r in self.readers.values()
                       if hasattr(r, "SCOPE")})


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(entry, workload):
    return "workloads" not in entry or workload in entry["workloads"]


def resolve(workload: str, smoke: bool = False, root: Path = ROOT) -> Cell:
    """The cell of ``workload``. ``smoke`` applies the files' ``smoke``
    sizes, for runs on the CPU at a size a test holds."""
    manifest = load_json(root / "BENCHMARK.json")
    wl = _named(manifest["workloads"], workload, "workload")
    config = load_json(root / _named(manifest["configs"], wl["config"],
                                     "configuration")["file"])
    bench = root / manifest["paths"][0]
    traffic = load_json(bench / "traffic" / f"{wl['traffic']}.json")
    per_layer = [m for m in manifest["per_layer"] if _applies(m, workload)]
    if smoke:
        config.update(config.get("smoke", {}))
        traffic.update(traffic.get("smoke", {}))
    return Cell(
        name=workload, chips=int(wl["chips"]), config=config, traffic=traffic,
        model=load_module(bench / "configs" / f"{config['name']}.py"),
        system=load_module(bench / "systems" / f"{config['system']}.py"),
        engine=load_module(bench / "engines" / f"{traffic['engine']}.py"),
        limits=load_json(bench / "limits" / f"{workload}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer,
        readers={m["name"]: load_module(bench / "metrics" / f"{m['name']}.py")
                 for m in per_layer},
    )


def init_params(cell: Cell, seed: int, device):
    """The seed's weights, made on the chip in one call, float32 with the
    values of the storage dtype (the program packs them without rounding)."""
    store = jnp.dtype(cell.traffic["storage_dtype"])

    def make(key):
        return jax.tree_util.tree_map(
            lambda a: a.astype(store).astype(jnp.float32),
            cell.model.init_params(cell.config, key))

    with jax.default_device(device):
        return jax.jit(make)(jax.random.key(jax_seed(seed)))


def _break(body, fault):
    """The timed path with one fault planted, for the harness's own tests:
    ``frozen`` returns the state unchanged, ``half_batch`` leaves out half
    of each site's batch."""
    if fault == "frozen":
        return lambda s, b: (s, body(s, b)[1])
    if fault == "half_batch":
        return lambda s, b: body(s, jax.tree_util.tree_map(
            lambda a: a[:, :, : a.shape[2] // 2], b))
    return body


class Federation:
    """One built cell: the jitted round, its state, and the feed."""

    def __init__(self, cell: Cell, seed: int, devices, fault=None):
        from repro.core import FLConfig
        from repro.core.schedules import constant, inv_sqrt

        t = cell.traffic
        self.q = int(t["q"])
        params = init_params(cell, seed, devices[0])
        loss_fn, shapes = cell.system.build(cell.config)
        got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
        if got != want:
            raise ValueError("the reference's parameters are not the "
                             f"program's: {got} != {want}")
        paths, _ = jax.tree_util.tree_flatten_with_path(params)
        self.names = [jax.tree_util.keystr(p) for p, _ in paths]
        self.total = reference.FlatView(params, int(t["scale_chunk"])).total
        self.fl_cfg = FLConfig(algorithm=t["algorithm"], q=self.q,
                               n_nodes=int(t["graph"]["n"]))
        self.w = mixing_weights(t["graph"])
        schedule = {"constant": constant, "inv_sqrt": inv_sqrt}[
            t.get("schedule", "constant")](float(t["alpha"]))
        body, self.state, view, self.theta0 = cell.engine.build(
            loss_fn, self.fl_cfg, schedule, t, params, self.w, devices, fault)
        del params
        self.round_fn = jax.jit(_break(body, fault), donate_argnums=(0,))
        self.pool = make_pool(cell.config, t, seed)
        alpha = jnp.float32(t["alpha"])  # the first step's

        def leaf_sq(flat):
            """Each leaf's sum of squares at each site: (leaves, sites)."""
            return jnp.stack([
                jnp.sum(jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)))
                for l in jax.tree_util.tree_leaves(view(flat))])

        if t["algorithm"] == "dsgt":
            grad = lambda s, th: s.prev_grad.astype(jnp.float32)  # noqa: E731
        else:
            # DSGD keeps no gradient: after round 1 recon + residual is the
            # payload h (both start at zero), so (theta0 - h) / alpha is the
            # round's gradients as the updates applied them
            grad = lambda s, th: (th.astype(jnp.float32)[None]  # noqa: E731
                                  - s.comm["recon"] - s.comm["residual"]) / alpha
        self._grad_sq = jax.jit(lambda s, th: leaf_sq(grad(s, th)))
        self._change_sq = jax.jit(lambda s, th: leaf_sq(
            s.params.astype(jnp.float32) - th.astype(jnp.float32)[None]))

    def norms(self, which: str) -> List[Dict[str, float]]:
        """Each site's per-leaf norms of the first gradient (``grad``) or of
        the change from the first weights (``change``)."""
        fn = self._grad_sq if which == "grad" else self._change_sq
        sq = np.asarray(fn(self.state, self.theta0), np.float64)
        return [reference.site_norms(self.names, col) for col in sq.T]

    def first_rounds(self) -> dict:
        """Drives the first ``SETUP_ROUNDS`` rounds through the window's own
        call and feed; returns what the reference is compared on: each
        round's loss, each site's first gradient and its change."""
        prog = {"losses": []}
        for r in range(SETUP_ROUNDS):
            prog["losses"].append(self.step(r)["loss"])
            if r == 0:
                prog["grad_sites"] = self.norms("grad")
        prog["change_sites"] = self.norms("change")
        self.theta0 = None
        return prog

    def step(self, r: int, spans: bool = False) -> Dict[str, float]:
        """One round with the trainer's host work; returns its metrics."""
        ann = (jax.profiler.TraceAnnotation if spans
               else lambda _: contextlib.nullcontext())
        with ann("bench_batch"):
            batch = round_batch(self.pool, self.q, r)
        with ann("bench_dispatch"):
            self.state, m = self.round_fn(self.state, batch)
        self.metrics = m
        with ann("bench_fetch"):
            vals = {k: float(m[k]) for k in FETCH}
            vals["iteration"] = int(self.state.step)
        return vals


def wire_kernel_names(hlo_text: str) -> List[str]:
    """Names of the compiled round's Pallas TPU kernels, as the device trace
    names their operations. Every Pallas call of these cells is a wire-stage
    kernel (the models run without Pallas)."""
    names = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line and "=" in line:
            lhs = line.split("=", 1)[0].strip()
            names.append(lhs.split()[-1].lstrip("%"))
    return names


def run(cell: Cell, seed: int, seconds: float, trace: bool = False,
        devices=None, fault: Optional[str] = None,
        t_start: Optional[float] = None,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                     flush=True)) -> dict:
    """Run the cell once; return the result line's fields."""
    t_start = time.perf_counter() if t_start is None else t_start
    devices = list(devices or jax.devices()[: cell.chips])
    compiles: List[str] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if "backend_compile" in name else None)

    fed = Federation(cell, seed, devices, fault)
    prog = fed.first_rounds()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; first losses {prog['losses']}")

    trace_dir = ROOT / ".bench_trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    n_compiles = len(compiles)
    rounds = failed = 0
    with (jax.profiler.TraceAnnotation("bench_window") if trace
          else contextlib.nullcontext()):
        t0 = time.perf_counter()
        while True:
            vals = fed.step(SETUP_ROUNDS + rounds, spans=trace)
            rounds += 1
            failed += not math.isfinite(vals["loss"])
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = len(compiles) - n_compiles
    log(f"window {window_s:.3f} s, {rounds} rounds, {failed} failed, "
        f"{in_window} compiles in the window; last round {vals}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    # the last window round's metrics dict, fetched once the window closed
    round_metrics = {k: float(v) if np.ndim(v) == 0 else np.asarray(v).tolist()
                     for k, v in fed.metrics.items()}
    kernels, scopes_of = [], None
    if trace:
        sds = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), fed.state)
        batch = round_batch(fed.pool, fed.q, 0)
        text = fed.round_fn.lower(sds, batch).compile().as_text()
        kernels = wire_kernel_names(text)
        scopes_of = stages.scope_map(text, cell.scopes())
        del text
    w, pool, q, total = fed.w, fed.pool, fed.q, fed.total
    del fed
    gc.collect()

    ref = reference.run_reference(
        cell.model, cell.config, cell.traffic,
        init_params(cell, seed, devices[0]), w,
        [round_batch(pool, q, r) for r in range(SETUP_ROUNDS)],
        devices=devices)
    numbers = correctness.readings(prog, ref)
    ok, checks = correctness.judge(numbers, cell.limits)
    out = {
        "correct": bool(ok and failed == 0),
        "attempted": rounds,
        "failed": failed,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
        "window_s": window_s,
        "rounds": rounds,
        "setup_s": setup_s,
        "round_ms": 1e3 * window_s / rounds,
        "flat_total": total,
        "round_metrics": round_metrics,
        "checks": checks,
    }
    if trace:
        tr = trace_reduce.reduce(
            trace_reduce.load(str(trace_dir), [d.id for d in devices]), kernels,
            scopes_of=scopes_of)
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["trace"] = tr
        log(json.dumps({"kernels": kernels, "trace": tr}))
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return out


def peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not in the table is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def result_line(cell: Cell, out: dict, peak: dict) -> dict:
    """The run's last line: the cell's end-to-end metrics, or with a trace
    its per-layer metrics, the device, and the compared numbers last."""
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": out["device"]}
    if "trace" in out:
        tr = out["trace"]
        t = cell.traffic
        n = int(t["graph"]["n"])
        ctx = {
            "trace": tr, "rounds": out["rounds"], "chips": cell.chips,
            "peaks": peak, "round_metrics": out["round_metrics"],
            "flops_per_round": cell.model.train_flops(cell.config, t)
            * n * int(t["q"]) * int(t["batch"]),
            "wire_bytes_per_chip": cell.engine.wire_bytes(
                t, out["flat_total"], n // cell.chips),
        }
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        chips = tr.get("chips", [])
        line["device"]["busy_s"] = (sum(c["busy_s"] for c in chips)
                                    / max(1, len(chips)))
        line["device"]["window_s"] = tr["window_s"]
        if chips:
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
    else:
        for m in cell.end_to_end:
            line["metrics"][m["name"]] = {"value": out[m["name"]],
                                          "unit": m["unit"]}
    line["checks"] = out["checks"]
    return line
