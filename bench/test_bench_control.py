"""The comparison that decides ``correct`` has to fail what it guards
against, at a size a test run holds.

* The control: the plain reference put in the program's place with every
  matmul operand rounded to float8 e4m3's 3 mantissa bits reads over the
  cell's limits.
* The faults: a run whose timed path returns its state unchanged, leaves
  out half of each site's batch, or leaves out the exchange between sites
  comes out not correct.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
import correctness  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
from test_bench_harness import ONE_CHIP, SEED  # noqa: E402


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(workload):
    import jax

    cell = harness.resolve(workload, smoke=True)
    dev = jax.devices()[:1]
    pool = harness.make_pool(cell.config, cell.traffic, SEED)
    batches = [harness.round_batch(pool, int(cell.traffic["q"]), r)
               for r in range(harness.SETUP_ROUNDS)]
    w = harness.mixing_weights(cell.traffic["graph"])

    def ref(**kw):
        return reference.run_reference(
            cell.model, cell.config, cell.traffic,
            harness.init_params(cell, SEED, dev[0]), w, batches, devices=dev,
            **kw)

    numbers = correctness.readings(ref(rnd=control.round_mantissa(3)), ref())
    ok, checks = correctness.judge(numbers, cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_exchange"])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_fault_is_not_correct(workload, fault):
    import jax

    cell = harness.resolve(workload, smoke=True)
    out = harness.run(cell, SEED, 0.2, devices=jax.devices()[:1],
                      fault=fault, log=lambda s: None)
    assert not out["correct"], out["checks"]
