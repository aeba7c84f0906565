"""Readings that set a cell's limits, on the chip at the cell's own size.

  python3 bench/control.py --workload <name> --seeds 11,12,13 [--program]

For each seed, against the plain reference over the same weights and
batches, it reads the numbers of ``correctness.py`` for:

* ``program``: the timed path's first rounds (``--program``), as a
  benchmark run reads them;
* ``control_e4m3`` and ``control_bf16``: the reference in the program's
  place, every matmul operand rounded to 3 (float8 e4m3) or 7 (bfloat16)
  mantissa bits;
* ``half_batch`` and ``no_exchange``: the reference in the program's place
  with half of each site's batch left out, or with the exchange between
  sites left out.

Each reading is one JSON line on standard output (and in ``--out``). The
benchmark's own runs never run this.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def round_mantissa(bits: int):
    """Round float32 values to ``bits`` mantissa bits, to nearest even,
    keeping float32's exponent range (a narrower format with ideal
    scaling). The cotangent flowing back through a rounded operand is
    rounded the same way, so the backward pass computes at that precision
    too."""
    import jax
    import jax.numpy as jnp

    shift = 23 - bits
    keep = jnp.uint32(~((1 << shift) - 1) & 0xFFFFFFFF)

    def r(a):
        u = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        u = (u + ((u >> shift) & 1) + ((1 << (shift - 1)) - 1)) & keep
        return jax.lax.bitcast_convert_type(u, jnp.float32)

    @jax.custom_vjp
    def rnd(a):
        return r(a)

    rnd.defvjp(lambda a: (r(a), None), lambda _, ct: (r(ct),))
    return rnd


VARIANTS = {
    "control_e4m3": {"rnd": 3},
    "control_bf16": {"rnd": 7},
    "half_batch": {"fault": "half_batch"},
    "no_exchange": {"fault": "no_exchange"},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(BENCH.parent / ".jax_cache"))
    import correctness
    import harness
    import reference

    cell = harness.resolve(args.workload, smoke=args.smoke)
    devices = jax.devices()[: cell.chips]
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prog = None
        if args.program:
            fed = harness.Federation(cell, seed, devices)
            prog = fed.first_rounds()
            w, pool = fed.w, fed.pool
            del fed
            gc.collect()
        else:
            w = harness.mixing_weights(cell.traffic["graph"])
            pool = harness.make_pool(cell.config, cell.traffic, seed)
        q = int(cell.traffic["q"])
        batches = [harness.round_batch(pool, q, r)
                   for r in range(harness.SETUP_ROUNDS)]

        def ref_run(**kw):
            return reference.run_reference(
                cell.model, cell.config, cell.traffic,
                harness.init_params(cell, seed, devices[0]), w, batches,
                devices=devices, **kw)

        ref = ref_run()
        if prog is not None:
            emit({"workload": cell.name, "seed": seed, "variant": "program",
                  **correctness.readings(prog, ref)})
        for name in args.variants.split(","):
            v = VARIANTS[name]
            kw = {"fault": v.get("fault")}
            if "rnd" in v:
                kw["rnd"] = round_mantissa(v["rnd"])
            try:
                got = ref_run(**kw)
                rec = correctness.readings(got, ref)
            except Exception as e:  # a control that crashes has failed
                rec = {"error": repr(e)[:300]}
            emit({"workload": cell.name, "seed": seed, "variant": name, **rec})
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
