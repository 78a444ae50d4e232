"""Benchmark orchestrator — one entry per paper table/figure + the
framework-level benches. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--only fig1,...]

The roofline section only reports if dry-run JSONs exist (run
``python -m repro.launch.dryrun --all --both-meshes`` first).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    # the launch-path check: benches started outside launch/run.sh run
    # under glibc malloc — valid numbers, noisier tails
    from repro.launch.env import enable_compile_cache, warn_if_no_tcmalloc
    warn_if_no_tcmalloc(lambda s: print(s, file=sys.stderr))
    enable_compile_cache()

    from benchmarks import (codec_json, compressed_allreduce,
                            fig1_decoder_latency, fig2_decoder_area,
                            fig3_encoder_latency, fig4_encoder_area,
                            quant_matmul)

    benches = {
        "fig1": fig1_decoder_latency.run,
        "fig2": fig2_decoder_area.run,
        "fig3": fig3_encoder_latency.run,
        "fig4": fig4_encoder_area.run,
        "quant_matmul": quant_matmul.run,
        "compressed_allreduce": compressed_allreduce.run,
        # machine-readable perf trajectory: writes BENCH_codec.json
        "codec_json": codec_json.run,
    }
    only = set(args.only.split(",")) if args.only else set(benches) | {
        "roofline"}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest
            print(f"{name}/FAILED,0,{type(e).__name__}:{e}")
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    # n=64 widths need x64 lanes: run in a subprocess so this process
    # keeps the default dtypes. It is a CPU dataflow figure, pinned to
    # the CPU: a child could not reach a chip this process holds
    if not args.only or "fig64" in only:
        import subprocess
        env = dict(os.environ)
        env["PYTHONPATH"] = "src:."
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.fig_n64"],
            capture_output=True, text=True, env=env, timeout=560)
        print(out.stdout, end="")
        if out.returncode != 0:
            print(f"fig64/FAILED,0,{out.stderr[-200:]}")
            failed.append("fig64")

    # roofline (from dry-run artifacts, if present)
    if "roofline" in only and os.path.isdir("experiments/dryrun") and \
            os.listdir("experiments/dryrun"):
        from benchmarks import roofline
        print("# --- roofline (single-pod baselines) ---")
        roofline.run()
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
