"""The four-chip cell on four virtual CPU devices at the ``smoke`` sizes:
its set-up, timed loop and comparison with the reference come out correct,
and the comparison fails what it guards against -- the control, and a run
whose timed path returns its state unchanged, leaves out half of each
site's batch or leaves out the exchange between sites.

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

import correctness  # noqa: E402
from test_bench_harness import SEED  # noqa: E402

WORKLOAD = "smollm360m-ring4-4chip-dsgd-q2"
FAULTS = ("frozen", "half_batch", "no_exchange")

_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {bench!r})
    import jax
    import control, correctness, harness, reference

    cell = harness.resolve({workload!r}, smoke=True)
    devices = jax.devices()[:4]
    out = {{}}
    for fault in (None,) + {faults!r}:
        run = harness.run(cell, {seed}, 0.2, devices=devices, fault=fault,
                          log=lambda s: None)
        out[fault or "program"] = {{"correct": run["correct"],
                                    "checks": run["checks"]}}
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

    plain = ref()
    numbers = correctness.readings(ref(rnd=control.round_mantissa(3)), plain)
    ok, checks = correctness.judge(numbers, cell.limits)
    out["control_e4m3"] = {{"correct": ok, "checks": checks}}
    out["reference"] = {{"losses": plain["losses"],
                         "site_losses": plain["site_losses"]}}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    script = _SCRIPT.format(bench=str(BENCH), workload=WORKLOAD,
                            faults=FAULTS, seed=SEED)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _failed(rec):
    return [k for k, c in rec["checks"].items() if not c["value"] <= c["limit"]]


def test_four_chip_cell_is_correct(readings):
    rec = readings["program"]
    assert rec["correct"], rec["checks"]
    assert set(rec["checks"]) == set(correctness.NUMBERS)


def test_four_chip_control_is_not_correct(readings):
    rec = readings["control_e4m3"]
    assert not rec["correct"] and _failed(rec), rec["checks"]
    # the per-site number parts the control from the program by the 3x an
    # upper reading needs (its limit is set from the chip's readings, which
    # read higher at the cell's own size)
    key = "site_total_change_gap"
    assert (rec["checks"][key]["value"]
            >= 3 * readings["program"]["checks"][key]["value"])


@pytest.mark.parametrize("fault", FAULTS)
def test_four_chip_fault_is_not_correct(readings, fault):
    rec = readings[fault]
    assert not rec["correct"] and _failed(rec), rec["checks"]


def test_reference_site_losses_average_to_losses(readings):
    ref = readings["reference"]
    assert len(ref["site_losses"]) == len(ref["losses"])
    for site, mean in zip(ref["site_losses"], ref["losses"]):
        assert len(site) == 4
        assert sum(site) / len(site) == pytest.approx(mean, rel=1e-6)


def test_judge_reads_only_the_numbers_its_limits_name():
    numbers = {"loss_gap": 1e-4, "grad_gap": 1e-2, "change_gap": 1e-2,
               "site_total_change_gap": 5.0}
    limits = {k: {"limit": 1.0} for k in correctness.ALWAYS}
    ok, checks = correctness.judge(numbers, limits)
    assert ok and set(checks) == set(correctness.ALWAYS)
    ok, checks = correctness.judge(
        numbers, dict(limits, site_total_change_gap={"limit": 1.0}))
    assert not ok and checks["site_total_change_gap"] == {"value": 5.0,
                                                           "limit": 1.0}
    with pytest.raises(ValueError):
        correctness.judge(numbers, {"loss_gap": {"limit": 1.0}})
