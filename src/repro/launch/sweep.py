"""Run the full dry-run grid (arch × shape × mesh) in subprocesses.

One subprocess per cell keeps XLA's memory bounded and makes the sweep
resumable: cells with an existing JSON record are skipped (delete the file
to re-run).  Usage::

    PYTHONPATH=src python -m repro.launch.sweep [--only-singlepod] [--force]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[3]
OUT_DIR = ROOT / "experiments" / "dryrun"

ARCHS = [
    "minicpm-2b", "deepseek-7b", "mistral-nemo-12b", "qwen2-72b",
    "llava-next-mistral-7b", "jamba-1.5-large-398b", "seamless-m4t-large-v2",
    "kimi-k2-1t-a32b", "arctic-480b", "mamba2-1.3b",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def run_cell(arch: str, shape: str, multi_pod: bool, extra=(),
             out_dir=None, timeout: int = 3600) -> str:
    mesh_tag = "multipod" if multi_pod else "singlepod"
    out = (out_dir or OUT_DIR) / f"{arch}_{shape}_{mesh_tag}.json"
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", arch, "--shape", shape, "--save-hlo",
           "--out-dir", str(out_dir or OUT_DIR), *extra]
    if multi_pod:
        cmd.append("--multi-pod")
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=str(ROOT), timeout=timeout,
                          env={"PYTHONPATH": str(ROOT / "src"),
                               "PATH": "/usr/bin:/bin:/usr/local/bin",
                               # children never take a TPU on a chip host
                               "JAX_PLATFORMS": "cpu"})
    dt = time.time() - t0
    if proc.returncode != 0:
        err = proc.stderr.strip().splitlines()[-1] if proc.stderr else "?"
        out.write_text(json.dumps(
            {"status": "error", "error": err, "t_s": dt}, indent=2))
        return f"ERROR ({dt:.0f}s): {err[:120]}"
    try:
        rec = json.loads(out.read_text())
        if rec.get("status") == "skipped":
            return f"skipped: {rec['reason'][:60]}"
        r = rec["roofline"]
        return (f"ok ({dt:.0f}s) bottleneck={r['bottleneck']} "
                f"frac={r['roofline_fraction']:.4f}")
    except Exception as e:  # pragma: no cover
        return f"ok ({dt:.0f}s) [no record: {e}]"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only-singlepod", action="store_true")
    ap.add_argument("--only-multipod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default="")
    ap.add_argument("--extra", default="",
                    help="comma-separated extra dryrun flags")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out_dir) if args.out_dir else OUT_DIR
    extra = tuple(x for x in args.extra.split(",") if x)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = [False, True]
    if args.only_singlepod:
        meshes = [False]
    if args.only_multipod:
        meshes = [True]

    total = t0 = time.time()
    for multi_pod in meshes:
        mesh_tag = "multipod" if multi_pod else "singlepod"
        for arch in ARCHS:
            for shape in SHAPES:
                out = out_dir / f"{arch}_{shape}_{mesh_tag}.json"
                tag = f"{arch:24s} {shape:12s} {mesh_tag:10s}"
                if out.exists() and not args.force:
                    rec = json.loads(out.read_text())
                    if rec.get("status") in ("ok", "skipped"):
                        print(f"{tag} cached:{rec['status']}", flush=True)
                        continue
                msg = run_cell(arch, shape, multi_pod, extra=extra,
                               out_dir=out_dir)
                print(f"{tag} {msg}", flush=True)
    print(f"sweep done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
