"""Suite config."""

import os

# Eigen's multi-threaded CPU contractions split a matmul's sum by the
# host's core count, so an f32 result (and its rounding to bf16) would
# change from machine to machine. The suite compares paths at bf16-ulp
# tolerances, so the CPU backend contracts single-threaded.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_cpu_multi_thread_eigen=false"
).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight tests (per-arch model compiles, real training "
        "runs, model-sized multi-device subprocesses); the fast tier-1 "
        "subset runs -m 'not slow' (see ROADMAP.md). Lightweight subprocess "
        "checks (e.g. the gossip HLO collective count) stay in the fast tier "
        "so CI always asserts them.",
    )
