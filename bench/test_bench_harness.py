"""The harness's own functions on the CPU at the ``smoke`` sizes: each
cell's set-up, the timed loop, the comparison with the reference and the
last line's format. No device metric is computed here; ``run.py`` itself
refuses a device that is not a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import correctness  # noqa: E402
import harness  # noqa: E402

SEED = 2**31 + 12345  # larger than 32 signed bits hold
WORKLOADS = [w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
ONE_CHIP = [w for w in WORKLOADS if harness.resolve(w).chips == 1]


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def _check_line(cell, out):
    line = harness.result_line(cell, out, harness.peaks("TPU v5 lite"))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(line["checks"]) == set(cell.limits)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    json.dumps(line)
    return line


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_one_chip_cell_smoke(workload):
    import jax

    cell = harness.resolve(workload, smoke=True)
    out = harness.run(cell, SEED, 0.3, devices=jax.devices()[:1],
                      log=lambda s: None)
    line = _check_line(cell, out)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert out["setup_s"] > 0 and out["round_ms"] > 0
    # the last window round's metrics, fetched once after the window
    assert set(harness.FETCH) <= set(out["round_metrics"])
    assert all(isinstance(out["round_metrics"][k], float)
               for k in harness.FETCH)


def test_run_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ONE_CHIP[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], capture_output=True,
        text=True, timeout=300, cwd=ROOT, env=_env())
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


def test_lm_configuration_is_the_published_one():
    from repro.configs import get_config

    cell = harness.resolve("smollm360m-ring2-dsgd-q2")
    assert cell.system.model_config(cell.config) == get_config("smollm-360m")


def test_manifest_resolves_every_cell():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in manifest["workloads"]:
        cell = harness.resolve(w["name"])
        assert cell.per_layer and cell.end_to_end
        assert {"loss_gap", "grad_gap", "change_gap"} <= set(cell.limits)
        assert set(cell.limits) <= set(correctness.NUMBERS)
    for c in manifest["configs"]:
        assert c["file"].startswith(manifest["paths"][0] + "/")


def test_reader_sees_the_round_metrics():
    """A per-layer reader finds the last window round's metrics dict in its
    context, so a configuration's own counters need no harness edit."""
    import types

    cell = harness.resolve(ONE_CHIP[0])
    probe = types.ModuleType("probe")
    probe.read = lambda ctx: ctx["round_metrics"]["consensus_err"]
    cell.readers = {"probe": probe}
    cell.per_layer = [{"name": "probe", "unit": "1"}]
    out = {"correct": True, "attempted": 2, "failed": 0, "rounds": 2,
           "device": {}, "checks": {}, "flat_total": 1536,
           "round_metrics": {"loss": 0.5, "consensus_err": 0.25},
           "trace": {"window_s": 1.0}}
    line = harness.result_line(cell, out, harness.peaks("TPU v5 lite"))
    assert line["metrics"] == {"probe": {"value": 0.25, "unit": "1"}}
