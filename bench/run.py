"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` from the seed, warms it up,
measures the decentralized round for ``--seconds``, then checks the first
rounds against the plain reference. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window), ``device``, and with
``--trace 1`` ``breakdown``; ``checks`` comes last. It exits non-zero and
prints no result when JAX's first device is not a TPU, when there are
fewer chips than the cell asks for, or when the chip's kind is not in
``peaks.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    # the compile cache lives in the checkout at a fixed path (the path is
    # part of the cache key); nothing is shared with another checkout
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    import harness

    try:
        cell = harness.resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return fail(f"cannot resolve workload {args.workload!r}: {e}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX's first device is on "
                    f"{devices[0].platform!r}")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, found "
                    f"{len(devices)}")
    try:
        peaks = harness.peaks(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))

    out = harness.run(cell, args.seed, args.seconds, trace=bool(args.trace),
                      devices=devices[: cell.chips], t_start=T_START)
    line = harness.result_line(cell, out, peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
