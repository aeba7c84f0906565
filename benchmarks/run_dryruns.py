"""Drive the full dry-run sweep: every (arch x shape x mesh) pair.

Each pair runs in a fresh subprocess (jax locks the device count at init;
the dry-run needs 512 placeholder devices while everything else in the
repo must see 1). Results are cached as JSON under experiments/dryrun/ --
re-runs skip completed pairs. Exit code is nonzero if any pair fails.

Usage:
  PYTHONPATH=src python -m benchmarks.run_dryruns [--mesh single|multi|both]
      [--arch ARCH ...] [--shape SHAPE ...] [--q 4] [--force]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(REPO, "experiments", "dryrun")

ARCHS = [
    "phi3-medium-14b",
    "recurrentgemma-2b",
    "internvl2-26b",
    "smollm-360m",
    "rwkv6-7b",
    "qwen2.5-32b",
    "dbrx-132b",
    "whisper-medium",
    "llama4-scout-17b-a16e",
    "tinyllama-1.1b",
]
SHAPE_NAMES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def record_path(arch: str, shape: str, mesh: str) -> str:
    return os.path.join(OUT_DIR, f"{arch}_{shape}_{mesh}.json")


def run_one(arch: str, shape: str, mesh: str, q: int, timeout: int = 3600) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the dry run forces 512 host devices: a CPU rehearsal by design
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--mesh", mesh,
        "--q", str(q), "--out", OUT_DIR,
    ]
    t0 = time.time()
    proc = subprocess.run(
        cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout
    )
    dt = time.time() - t0
    if proc.returncode != 0:
        return {
            "arch": arch, "shape": shape, "mesh": mesh, "status": "error",
            "stderr_tail": proc.stderr[-3000:], "wall_s": round(dt, 1),
        }
    path = record_path(arch, shape, mesh)
    if os.path.exists(path):
        with open(path) as f:
            rec = json.load(f)
        rec["wall_s"] = round(dt, 1)
        return rec
    return {"arch": arch, "shape": shape, "mesh": mesh, "status": "error",
            "stderr_tail": "no record written", "wall_s": round(dt, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=("single", "multi", "both"))
    ap.add_argument("--arch", nargs="*", default=ARCHS)
    ap.add_argument("--shape", nargs="*", default=SHAPE_NAMES)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = []
    total = 0
    for mesh in meshes:
        for arch in args.arch:
            for shape in args.shape:
                total += 1
                path = record_path(arch, shape, mesh)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        rec = json.load(f)
                    if rec["status"] != "error":  # errors are always retried
                        print(f"[cached] {arch} x {shape} x {mesh}: {rec['status']}")
                        continue
                rec = run_one(arch, shape, mesh, args.q)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (
                        f" flops/dev={rec['flops']:.3e}"
                        f" coll={rec['collectives']['total_bytes']:.3e}B"
                        f" compile={rec.get('compile_s', 0)}s"
                    )
                elif status == "error":
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=2)
                    failures.append((arch, shape, mesh))
                    extra = " :: " + rec.get("stderr_tail", "")[-400:].replace("\n", " | ")
                print(f"[{status}] {arch} x {shape} x {mesh} ({rec.get('wall_s','?')}s){extra}")
                sys.stdout.flush()
    print(f"\n{total - len(failures)}/{total} pairs OK")
    if failures:
        print("FAILURES:")
        for f3 in failures:
            print("  ", f3)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
