"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # CPU-sized defaults
    PYTHONPATH=src python -m benchmarks.run --only cur time
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI pass + JSON artifact
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

SUITES = ["spsd_error", "spsd_error_adaptive", "kpca", "spectral", "cur",
          "time", "landmark", "ablations", "kernels", "serve", "workloads"]

SMOKE_JSON = os.path.join("results", "BENCH_smoke.json")

# The per-PR tracked copy at the repo root: results/BENCH_smoke.json is
# gitignored (CI-artifact only), so every smoke run also refreshes a
# ``BENCH_<tag>.json`` file and commits carry the measured trajectory
# in-tree.  The tag defaults to the short git revision; PRs pass an explicit
# ``--tag prN`` when refreshing the tracked copy they commit.
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def default_tag() -> str:
    """Short git revision of the repo, or 'local' outside a checkout."""
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True, cwd=REPO_ROOT,
                             timeout=10)
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "local"


def tracked_json_path(tag: str) -> str:
    return os.path.join(REPO_ROOT, f"BENCH_{tag}.json")


def smoke(out: str = SMOKE_JSON, tag: str = None) -> int:
    """Tiny-shape pass over every perf entry point, CI-sized (~1 min CPU).

    Exercises the argument plumbing and the streaming code paths so the
    benchmark suite cannot bit-rot, and writes ``results/BENCH_smoke.json``
    (per-step wall time, the fused-vs-separate scaling rows, and the
    per-kernel registry rows) so CI can archive the perf trajectory per PR.
    A tracked ``BENCH_<tag>.json`` copy lands at the repo root (tag from
    ``--tag``, default the short git revision).  Absolute numbers at these
    shapes are noise; trends and the speedup ratio are the signal.
    """
    import jax
    t0 = time.time()
    from benchmarks import bench_cur, bench_kernels, bench_serve, \
        bench_spsd_error, bench_time, bench_workloads
    steps = {}

    def step(name, fn):
        t = time.time()
        out_val = fn()
        steps[name] = round(time.time() - t, 3)
        return out_val

    step("spsd_error_dense",
         lambda: bench_spsd_error.main(["--datasets", "letters", "--n", "400"]))
    step("spsd_error_streaming",
         lambda: bench_spsd_error.main(["--datasets", "letters", "--n", "400",
                                        "--streaming", "--probes", "32"]))
    scaling = step("spsd_error_scaling",
                   lambda: bench_spsd_error.run_scaling([3000]))
    step("time", lambda: bench_time.main(["--ns", "400", "800"]))
    step("time_streaming",
         lambda: bench_time.main(["--ns", "400", "800", "--streaming"]))
    step("cur", lambda: bench_cur.main([]))
    cur_selection = step(
        "cur_streaming_selection",
        lambda: bench_cur.run_streaming_selection(n=800, c=32, sc=64))
    kernels = step("kernels", lambda: bench_kernels.run())
    kernels_bf16 = step("kernels_bf16",
                        lambda: bench_kernels.run(precision="bf16_f32acc"))
    serve = step("serve", lambda: bench_serve.run(loads=(1, 2, 8),
                                                  requests_per_client=6))
    serve_append = step(
        "serve_append",
        lambda: bench_serve.run_append(n=800, batches=4, batch_rows=32))
    workloads = step("workloads", lambda: bench_workloads.run())

    # achieved-vs-roofline per launch, pulled out of the kernel rows so the
    # perf trajectory is one flat section (and one CI artifact) per PR
    roofline = [
        {"kernel": r["kernel"], "precision": r["precision"],
         **r["roofline"]}
        for r in kernels + kernels_bf16 if "roofline" in r]
    l1_routes = {r["precision"]: r["l1_route"]
                 for r in kernels + kernels_bf16
                 if r["kernel"] == "laplacian"}

    payload = {
        "total_seconds": round(time.time() - t0, 3),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "meta": {
            # which tile policies the sweep exercised and which l1dist form
            # the laplacian rows took (mxu_signsplit | vpu_loop)
            "precision_policies": sorted({r["precision"]
                                          for r in kernels + kernels_bf16}),
            "l1dist_route": l1_routes,
            "roofline_profile": roofline[0]["profile"] if roofline else None,
        },
        "steps_seconds": steps,
        "scaling": scaling,
        "kernels": kernels,
        "kernels_bf16": kernels_bf16,
        "roofline": roofline,
        "cur_streaming_selection": cur_selection,
        "serve": serve,
        "serve_append": serve_append,
        "workloads": workloads,
    }
    out_dir = os.path.dirname(out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
    # standalone roofline report next to the smoke JSON (CI uploads it as its
    # own artifact so launch-efficiency trends are greppable without the rest
    # of the payload)
    roofline_out = os.path.join(out_dir or ".", "ROOFLINE_smoke.json")
    with open(roofline_out, "w") as f:
        json.dump({"meta": payload["meta"], "roofline": roofline}, f, indent=2)
    tracked = tracked_json_path(tag or default_tag())
    with open(tracked, "w") as f:            # tracked copy at the repo root
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"\nsmoke benchmarks completed in {payload['total_seconds']:.1f}s "
          f"-> {out} (tracked copy: {tracked})")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--only", nargs="*", default=None,
                   help=f"subset of {SUITES}")
    p.add_argument("--smoke", action="store_true",
                   help="tiny-shape CI pass over the perf entry points")
    p.add_argument("--smoke-out", default=SMOKE_JSON,
                   help="where --smoke writes its JSON summary")
    p.add_argument("--tag", default=None,
                   help="tag for the tracked repo-root BENCH_<tag>.json copy "
                        "(default: short git revision)")
    args = p.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        return smoke(args.smoke_out, tag=args.tag)
    picked = args.only or SUITES

    t0 = time.time()
    if "spsd_error" in picked:
        from benchmarks import bench_spsd_error
        bench_spsd_error.main(["--datasets", "letters", "pendigit",
                               "mushrooms"])
        bench_spsd_error.main(["--datasets", "pendigit", "--eta", "0.99"])
    if "spsd_error_adaptive" in picked:
        from benchmarks import bench_spsd_error
        bench_spsd_error.main(["--datasets", "pendigit", "--adaptive"])
    if "kpca" in picked:
        from benchmarks import bench_kpca
        bench_kpca.main(["--datasets", "pendigit", "mushrooms", "--knn"])
    if "spectral" in picked:
        from benchmarks import bench_spectral
        bench_spectral.main(["--datasets", "pendigit"])
    if "cur" in picked:
        from benchmarks import bench_cur
        bench_cur.main([])
    if "time" in picked:
        from benchmarks import bench_time
        bench_time.main([])
    if "landmark" in picked:
        from benchmarks import bench_landmark_attention
        bench_landmark_attention.main([])
    if "ablations" in picked:
        from benchmarks import bench_ablations
        bench_ablations.main([])
    if "kernels" in picked:
        from benchmarks import bench_kernels
        bench_kernels.main([])
    if "serve" in picked:
        from benchmarks import bench_serve
        bench_serve.main([])
    if "workloads" in picked:
        from benchmarks import bench_workloads
        bench_workloads.main([])
    print(f"\nbenchmarks completed in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
