"""``kanana30b-ring4-4chip-dsgd-q4`` on four virtual CPU devices at the
``smoke`` sizes: the program comes out correct; the control and a run whose
timed path returns its state unchanged, leaves out half of each site's
batch or leaves out the exchange come out not correct. The compiled round
carries the ``mla`` and ``moe`` scopes on its forward, backward and
recomputed instructions, so the device trace can read them.

The cell's limits (``limits/<workload>.json``) are set on the chip at the
published widths. At the smoke width (d = 128) bfloat16 rounding reads
several times higher relative to the float32 reference, so the runs here
are judged against ``SMOKE_LIMITS``, set by the same rule from readings at
this size (program, seeds 2**31 + 12345 and 4160000101: loss 1.8e-4 /
1.1e-4, grad 3.6e-3 / 6.1e-3, change 2.2e-2 / 1.3e-2, site total 3.4e-4 /
9.8e-4; the e4m3 control 1.9e-3, 2.1e-2 / 2.5e-2, 4.6e-2 / 5.0e-2, 7.3e-3 /
7.6e-3). The chip's file is checked for its own ordering.

JAX fixes its device count when it starts, so the runs happen in one
subprocess that prints its readings; the tests read them."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from test_bench_harness import SEED  # noqa: E402

WORKLOAD = "kanana30b-ring4-4chip-dsgd-q4"
FAULTS = ("frozen", "half_batch", "no_exchange")
SCOPES = ("mla", "moe")
SMOKE_LIMITS = {"loss_gap": {"limit": 6e-4}, "grad_gap": {"limit": 1.5e-2},
                "change_gap": {"limit": 0.25},
                "site_total_change_gap": {"limit": 3e-3}}

_SCRIPT = textwrap.dedent("""
    import json, os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {bench!r})
    import jax
    import control, correctness, harness, reference, stages
    from traffic import round_batch

    cell = harness.resolve({workload!r}, smoke=True)
    cell.limits = {limits!r}
    devices = jax.devices()[:4]
    out = {{}}
    for fault in (None,) + {faults!r}:
        run = harness.run(cell, {seed}, 0.2, devices=devices, fault=fault,
                          log=lambda s: None)
        out[fault or "program"] = {{"correct": run["correct"],
                                    "checks": run["checks"]}}
        if fault is None:
            out["round_metrics"] = run["round_metrics"]
    pool = harness.make_pool(cell.config, cell.traffic, {seed})
    q = int(cell.traffic["q"])
    batches = [harness.round_batch(pool, q, r)
               for r in range(harness.SETUP_ROUNDS)]
    w = harness.mixing_weights(cell.traffic["graph"])

    def ref(**kw):
        return reference.run_reference(
            cell.model, cell.config, cell.traffic,
            harness.init_params(cell, {seed}, devices[0]), w, batches,
            devices=devices, **kw)

    numbers = correctness.readings(ref(rnd=control.round_mantissa(3)), ref())
    ok, checks = correctness.judge(numbers, cell.limits)
    out["control_e4m3"] = {{"correct": ok, "checks": checks}}

    fed = harness.Federation(cell, {seed}, devices)
    text = fed.round_fn.lower(fed.state, round_batch(fed.pool, q, 0)).compile().as_text()
    scoped = stages.scope_map(text, {scopes!r})
    kinds = {{}}
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if " = " not in line or not m:
            continue
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        path = m.group(1)
        kind = ("remat" if "rematted_computation" in path else
                "backward" if "transpose(" in path else "forward")
        for s in scoped.get(name, ()):
            kinds.setdefault(s, {{}})[kind] = kinds.get(s, {{}}).get(kind, 0) + 1
    out["scopes"] = kinds
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    script = _SCRIPT.format(bench=str(BENCH), workload=WORKLOAD,
                            faults=FAULTS, seed=SEED, scopes=SCOPES,
                            limits=SMOKE_LIMITS)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _failed(rec):
    return [k for k, c in rec["checks"].items() if not c["value"] <= c["limit"]]


def test_kanana_cell_is_correct(readings):
    rec = readings["program"]
    assert rec["correct"], rec["checks"]


def test_kanana_round_reports_the_expert_counters(readings):
    m = readings["round_metrics"]
    assert m["moe_held_assignments"] > 0
    assert m["moe_held_max_load"] >= 1.0


def test_kanana_control_is_not_correct(readings):
    rec = readings["control_e4m3"]
    assert not rec["correct"] and _failed(rec), rec["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_kanana_fault_is_not_correct(readings, fault):
    rec = readings[fault]
    assert not rec["correct"] and _failed(rec), rec["checks"]


@pytest.mark.parametrize("scope", SCOPES)
def test_scopes_cover_forward_backward_and_recomputation(readings, scope):
    kinds = readings["scopes"].get(scope, {})
    assert set(kinds) == {"forward", "backward", "remat"}, kinds
    assert all(v > 0 for v in kinds.values())


def test_chip_limits_lie_between_their_readings():
    """Each limit of the cell's file lies above the program's largest
    reading on the chip and below the reading of the control (at least 3x
    the program's) or of a fault (at least 10x) that it was set from."""
    import harness

    limits = harness.resolve(WORKLOAD).limits
    assert set(limits) == set(SMOKE_LIMITS)
    for k, v in limits.items():
        assert v["lower"] < v["limit"] < v["upper"], k
        need = 3 if v["upper_from"] == "control_e4m3" else 10
        assert v["upper"] >= need * v["lower"], k
