"""End-to-end smoke of the fast-SPSD main path on a TPU.

    python chip_smoke.py               # one chip: build, persist, warm boot, serve
    python chip_smoke.py --four-chips  # the sharded fused sweep over four chips

One process drives the chip through the entry points users call, at a size
users would call real, and checks every result against an independent
reference:

1. device: refuse to run (non-zero exit, no result line) without a TPU;
2. build: ``fast_model_with_error`` over a metered ``PairwiseKernel`` with
   Pallas on (one fused sweep for C and the Hutchinson probes, route
   ``pallas_fused``, interpret mode off) at n = 2^20, d = 16, c = 512, its
   error checked against the same build with Pallas off; the projection-
   sketch route (one fused sweep for C, K·S and the probes) at n = 2^16;
   then ``build_artifact`` for the same model;
3. persist and warm-boot: ``save_artifact`` to ``.smoke_ckpt/`` in the
   checkout, ``load_or_rebuild`` must report a warm boot;
4. serve: a ``KernelServer`` on the warm artifact answers mixed KRR / KPCA /
   feature requests of heterogeneous sizes, one cross launch per bucket,
   each answer at ≤1e-5 of a reference (float64 QR-based KRR; the non-Pallas
   route for KPCA and features);
5. the last line: ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only the sharded sweep (route ``pallas_fused_sharded``,
scalar-prefetch slabs) and the same call on one chip, compares them at
≤1e-5, and checks that every device held the sweep's working set.

Latencies and seconds printed on the way are bring-up readings, not
benchmark metrics.  Compile seconds and persistent-cache hits are printed at
the end, so a second run in the same checkout shows a warm compile.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N = 1 << 20            # corpus rows: C (n × c, f32) is 2 GiB on the chip
D = 16                 # letters / pendigits width
C = 512                # landmarks
S = 4 * C              # uniform column sketch
PROBES = 64            # Hutchinson error probes
GAUSS_N = 1 << 16      # the projection-sketch (gaussian) route
CALIB_N = 1 << 16      # rows the median heuristic quantiles over (× 128)
# the Pallas-off reference build's row panels: the panel engine's default
# (2^25 elements, 32 rows at n = 2^20) underfills the MXU and took 352 s on
# a v5e; 256-row panels (1 GiB) give the same products in fewer steps
REF_BLOCK = 256
FOUR_N = 1 << 18       # the sharded sweep over four chips
SEED = 0
PARITY_TOL = 1e-5      # serving and sharded-vs-one-chip parity
# Pallas vs non-Pallas Hutchinson error, relative: the two routes sum K·Z in
# different orders.  The estimator's own spread at 64 probes is ~18%, so
# this gates numerics only.
ERR_RTOL = 1e-3
CKPT_DIR = ROOT / ".smoke_ckpt"
REQUESTS = 48
QUERY_SIZES = (1, 5, 17, 33, 64, 200)


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def gap(a, b) -> float:
    """max |a − b| / max(1, max |b|), in float64 on the host."""
    from repro.serve import parity_gap
    return parity_gap(a, b)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, cache_dir):
        say(f"compile: {self.seconds:.3f} s of backend compiles over "
            f"{self.compiles} programs; persistent cache {cache_dir}: "
            f"{self.hits} hits, {self.misses} misses")


def dataset(n: int, seed: int):
    """Letters-shaped data (d = 16, 26 classes, standardized) and ±1
    one-hot KRR targets."""
    from benchmarks.common import make_dataset
    X, labels = make_dataset("letters", seed=seed, n=n)
    y = 2.0 * np.eye(26, dtype=np.float32)[labels] - 1.0
    return X, jnp.asarray(y)


def metered_model(spec, key, *, use_pallas, s_sketch, mesh=None,
                  block_size=None):
    """A jittable ``fast_model_with_error`` over a metered operator; the
    operator is kept so its route and counts can be read after tracing."""
    from repro.core import spsd
    from repro.core.instrument import CountingOperator
    from repro.core.kernelop import PairwiseKernel
    ops = []

    def run(X):
        op = CountingOperator(PairwiseKernel(X, spec, use_pallas=use_pallas))
        ops.append(op)
        ap, err = spsd.fast_model_with_error(
            op, key, c=C, s=S, s_sketch=s_sketch, probes=PROBES, mesh=mesh,
            block_size=block_size)
        return ap.C, ap.U, ap.P_indices, err

    return run, ops


def timed_build(name, run, X):
    """AOT-compile then run; returns outputs, compile and run seconds."""
    t0 = time.perf_counter()
    compiled = jax.jit(run).lower(X).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(X))
    t2 = time.perf_counter()
    say(f"{name}: compile {t1 - t0:.3f} s, build {t2 - t1:.3f} s "
        f"(host clock after block_until_ready)")
    return out


def build_phase(X, y, spec, key):
    from repro.kernels.pairwise import ops as pw_ops
    from repro.serve import build_artifact

    require(pw_ops._interpret_mode() is False,
            "Pallas would run in interpret mode on this backend")
    say("pallas interpret mode: False")

    run, ops = metered_model(spec, key, use_pallas=True, s_sketch="uniform")
    Cp, Up, idx, err = timed_build(f"fused build n={N} d={D} c={C} s={S}",
                                   run, X)
    op = ops[-1]
    say(f"route: {op.last_route}  sweeps={op.counts['sweeps']} "
        f"fused_sweeps={op.counts['fused_sweeps']} "
        f"entries={op.counts['entries']}")
    require(op.last_route == "pallas_fused", f"route {op.last_route}")
    require(op.counts["sweeps"] == 1 and op.counts["fused_sweeps"] == 1,
            f"expected one fused sweep, got {op.counts}")
    require(bool(jnp.isfinite(err)) and bool(jnp.all(jnp.isfinite(Up))),
            "non-finite model")

    run_ref, ops_ref = metered_model(spec, key, use_pallas=False,
                                     s_sketch="uniform", block_size=REF_BLOCK)
    C_ref, _, idx_ref, err_ref = timed_build("reference build (Pallas off)",
                                             run_ref, X)
    require(ops_ref[-1].last_route == "panel", ops_ref[-1].last_route)
    err, err_ref = float(err), float(err_ref)
    c_gap = float(jnp.max(jnp.abs(Cp - C_ref)) /
                  jnp.maximum(1.0, jnp.max(jnp.abs(C_ref))))
    say(f"hutchinson relative error: pallas {err!r}  reference {err_ref!r}  "
        f"|diff|/ref {abs(err - err_ref) / err_ref:.3e} (tol {ERR_RTOL})")
    say(f"C parity vs reference: {c_gap:.3e} (tol {PARITY_TOL})")
    require(bool(jnp.all(idx == idx_ref)), "landmarks differ across routes")
    require(abs(err - err_ref) <= ERR_RTOL * err_ref, "error mismatch")
    require(c_gap <= PARITY_TOL, "C mismatch")
    # build_artifact's SVD of C needs ~9 GiB of scratch: keep only host
    # copies of what the artifact is compared with
    del C_ref
    Cp, idx = np.asarray(Cp), np.asarray(idx)

    # the projection-sketch route: C, K·S and the probes in one fused sweep
    Xg = X[:GAUSS_N]
    outs = {}
    for use_pallas in (True, False):
        run_g, ops_g = metered_model(spec, key, use_pallas=use_pallas,
                                     s_sketch="gaussian")
        outs[use_pallas] = timed_build(
            f"gaussian-sketch build n={GAUSS_N} pallas={use_pallas}",
            run_g, Xg)
        if use_pallas:
            require(ops_g[-1].last_route == "pallas_fused"
                    and ops_g[-1].counts["sweeps"] == 1,
                    f"gaussian route {ops_g[-1].last_route} "
                    f"{ops_g[-1].counts}")
    eg, eg_ref = float(outs[True][3]), float(outs[False][3])
    say(f"gaussian-sketch error: pallas {eg!r}  reference {eg_ref!r}  "
        f"|diff|/ref {abs(eg - eg_ref) / eg_ref:.3e}")
    require(abs(eg - eg_ref) <= ERR_RTOL * eg_ref, "gaussian error mismatch")
    require(gap(outs[True][0], outs[False][0]) <= PARITY_TOL,
            "gaussian C mismatch")
    del outs

    t0 = time.perf_counter()
    art = build_artifact(X, y, spec, c=C, s=S, s_sketch="uniform", key=key,
                         use_pallas=True)
    jax.block_until_ready(art.heads)
    say(f"build_artifact: {time.perf_counter() - t0:.3f} s "
        f"(host clock, compiles included)")
    require(np.array_equal(np.asarray(art.landmark_indices), idx),
            "artifact landmarks differ from the metered build")
    Cp = jnp.asarray(Cp)
    a_gap = float(jnp.max(jnp.abs(art.C - Cp)) /
                  jnp.maximum(1.0, jnp.max(jnp.abs(Cp))))
    say(f"artifact C vs metered build: {a_gap:.3e}")
    require(a_gap <= PARITY_TOL, "artifact C mismatch")
    return art


def persist_phase(art):
    from repro.serve import load_or_rebuild, save_artifact

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    t0 = time.perf_counter()
    save_artifact(str(CKPT_DIR), art)
    t1 = time.perf_counter()

    def no_rebuild():
        raise SmokeFailure("warm boot fell back to a rebuild")

    loaded, recovery = load_or_rebuild(str(CKPT_DIR), no_rebuild)
    jax.block_until_ready(loaded.C)
    t2 = time.perf_counter()
    boot = "warm" if recovery.warm else "cold"
    say(f"persist: save {t1 - t0:.3f} s, boot {t2 - t1:.3f} s ({boot})")
    require(recovery.warm, "boot was not warm")
    require(np.array_equal(np.asarray(loaded.C), np.asarray(art.C)),
            "restored C differs from the saved one")
    return loaded


def serve_phase(art, y):
    from repro.core.instrument import CountingOperator
    from repro.launch.serve_kernel import BatchPolicy, KernelServer, \
        percentile_ms
    from repro.serve import dense_oracle, krr_reference

    rng = np.random.default_rng(SEED + 1)
    Xq_all, _ = dataset(sum(QUERY_SIZES) * REQUESTS, SEED + 1)
    Xq_all = np.asarray(Xq_all)
    sizes = [int(rng.choice(QUERY_SIZES)) for _ in range(REQUESTS)]
    tasks = [("krr", "kpca", "features")[i % 3] for i in range(REQUESTS)]
    starts = np.cumsum([0] + sizes)
    queries = [Xq_all[a:b] for a, b in zip(starts[:-1], starts[1:])]

    t0 = time.perf_counter()
    krr = [i for i, t in enumerate(tasks) if t == "krr"]
    stacked = np.asarray(krr_reference(
        art, np.concatenate([queries[i] for i in krr]), y))
    expected = {}
    edges = np.cumsum([0] + [sizes[i] for i in krr])
    for i, a, b in zip(krr, edges[:-1], edges[1:]):
        expected[i] = stacked[a:b]
    for i, t in enumerate(tasks):
        if t != "krr":
            expected[i] = np.asarray(dense_oracle(art, queries[i], t))
    say(f"references: {time.perf_counter() - t0:.3f} s")

    op = CountingOperator(art.landmark_operator())
    server = KernelServer(art, BatchPolicy(max_batch=16, max_wait_s=0.005),
                          op=op)
    try:
        passes = []
        for _ in range(2):              # the first pass compiles the buckets
            s0, b0 = op.counts["cross_sweeps"], server.buckets_served
            pending = [server.submit(q, t) for q, t in zip(queries, tasks)]
            res = [p.wait(timeout=600.0) for p in pending]
            worst = {t: max(gap(r.out, expected[i])
                            for i, r in enumerate(res) if tasks[i] == t)
                     for t in ("krr", "kpca", "features")}
            passes.append((op.counts["cross_sweeps"] - s0,
                           server.buckets_served - b0, worst,
                           [p.latency_s for p in pending]))
    finally:
        server.stop()
    for k, (sweeps, buckets, worst, lats) in enumerate(passes):
        say(f"serve pass {k}: {REQUESTS} requests, sizes {sorted(set(sizes))}"
            f"; cross_sweeps {sweeps} == buckets_served {buckets}: "
            f"{sweeps == buckets}; route {op.last_route}; parity " +
            ", ".join(f"{t} {v:.3e}" for t, v in worst.items()) +
            f" (tol {PARITY_TOL})")
        require(sweeps == buckets, "cross launches != buckets")
        require(max(worst.values()) <= PARITY_TOL, "serving parity")
    lats = passes[-1][3]
    say(f"latency (informational, not a metric): p50 "
        f"{percentile_ms(lats, 50):.3f} ms  p99 {percentile_ms(lats, 99):.3f}"
        f" ms")


def one_chip():
    from repro.kernels.pairwise import calibrate_sigma

    t0 = time.perf_counter()
    X, y = dataset(N, SEED)
    # the rows are i.i.d., so the first CALIB_N are a uniform sample
    spec = calibrate_sigma(X[:CALIB_N], "rbf")
    key = jax.random.PRNGKey(SEED)
    say(f"data n={N} d={D}: {time.perf_counter() - t0:.3f} s; {spec}")
    art = build_phase(X, y, spec, key)
    loaded = persist_phase(art)
    del art
    serve_phase(loaded, y)


def four_chips():
    from repro.distributed.sharding import data_parallel_mesh
    from repro.kernels.pairwise import calibrate_sigma

    devices = jax.devices()
    require(len(devices) == 4, f"--four-chips needs 4 devices, "
                               f"found {len(devices)}")
    X, _ = dataset(FOUR_N, SEED)
    spec = calibrate_sigma(X[:CALIB_N], "rbf")
    key = jax.random.PRNGKey(SEED)

    def build(name, mesh):
        # eager, as users call it: under one jit spanning the mesh, the
        # Pallas launches outside the sweep's shard_map (the s×s block)
        # would be handed to the SPMD partitioner, which refuses them
        from repro.core import spsd
        from repro.core.instrument import CountingOperator
        from repro.core.kernelop import PairwiseKernel
        op = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
        t0 = time.perf_counter()
        ap, err = spsd.fast_model_with_error(
            op, key, c=C, s=S, s_sketch="uniform", probes=PROBES, mesh=mesh)
        jax.block_until_ready((ap.C, ap.U, err))
        say(f"{name}: {time.perf_counter() - t0:.3f} s (compile included; "
            f"host clock after block_until_ready)")
        return op, ap, err

    op4, ap4, e4 = build(f"sharded build n={FOUR_N} over 4 chips",
                         data_parallel_mesh())
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    say(f"route: {op4.last_route}  slab mode: {op4.last_slab_mode}  "
        f"sweeps={op4.counts['sweeps']}")
    say("peak bytes in use per device: " + ", ".join(
        f"{d.id}: {p}" for d, p in zip(devices, peaks)))
    require(op4.last_route == "pallas_fused_sharded", op4.last_route)
    require(op4.last_slab_mode == "prefetch", op4.last_slab_mode)
    # every device held its own full n × c carry of the C gather before the
    # psum (n·c f32), so a device that sat idle shows a smaller peak
    require(all(p >= FOUR_N * C * 4 for p in peaks),
            "a device did not hold the sweep's working set")

    op1, ap1, e1 = build("same build on one chip", None)
    require(op1.last_route == "pallas_fused", op1.last_route)
    gaps = {"C": gap(ap4.C, ap1.C), "U": gap(ap4.U, ap1.U),
            "error": abs(float(e4) - float(e1))}
    say("sharded vs one chip: " + ", ".join(
        f"{k} {v:.3e}" for k, v in gaps.items()) + f" (tol {PARITY_TOL})")
    say(f"hutchinson relative error: {float(e4)!r}")
    require(all(v <= PARITY_TOL for v in gaps.values()),
            "sharded sweep disagrees with one chip")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded sweep over four chips and "
                        "its one-chip comparison")
    args = p.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("FAIL: no TPU found; this smoke runs only on the chip",
              file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    say(f"total: {time.perf_counter() - t0:.3f} s")
    meter.report(cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
