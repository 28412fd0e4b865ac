"""Continuous-batching kernel-inference server over a KernelModelArtifact.

The production shape of ``repro.serve``: replicas precompute the factor
store once (``--build``), then any number of serving processes warm-boot
from the checkpoint (``--serve``) and answer KRR / KPCA / feature-map
queries with one rectangular fused cross-kernel launch per size bucket.

    # precompute + persist the artifact and a canned query trace
    PYTHONPATH=src python -m repro.launch.serve_kernel --build \
        --dir /tmp/serve_ckpt --n 240 --c 48 --s 96 --queries 12

    # fresh process: warm boot, replay the trace, assert parity + latency
    PYTHONPATH=src python -m repro.launch.serve_kernel --serve \
        --dir /tmp/serve_ckpt --require-warm --parity-tol 1e-5

``KernelServer`` runs the continuous-batching loop: callers ``submit``
requests from any thread; a background worker collects until ``max_batch``
requests are queued or the oldest has waited ``max_wait_s``, then flushes —
``plan_buckets`` groups the batch by query count (padding bounded by
``waste``) and each bucket is answered by ONE ``op.cross`` launch.  Every
request records its enqueue→complete latency; the CI serve-smoke job
asserts the replayed trace matches the f64 references to ≤1e-5 and that
``cross_sweeps`` (via ``CountingOperator``) equals ``buckets_served``.

Corpus growth rides the same loop: ``submit_append`` enqueues a training
batch next to the queries; the worker absorbs it IN ARRIVAL ORDER through
an ``IncrementalMaintainer`` (one thin ``append_sweeps``-metered launch +
delta checkpoint per batch, see ``repro.serve.incremental``) and swaps the
refreshed artifact in for every later query — no rebuild, no restart.  The
``--append`` CLI leg replays that path and asserts the absorb was O(b·c):
exactly one append sweep per batch, zero panel/full sweeps, and ≤1e-5
parity against the f64 KRR reference on the GROWN corpus.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.core.instrument import CountingOperator
from repro.kernels.pairwise import specs as pw_specs
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import (
    GenerationStats,
    IncrementalMaintainer,
    KernelModelArtifact,
    QueryRequest,
    StalenessPolicy,
    answer_batch,
    build_artifact,
    dense_oracle,
    is_delta_step,
    krr_reference,
    load_artifact,
    load_or_rebuild,
    parity_gap,
    plan_buckets,
    save_artifact,
)

TRACE_FILE = "trace.npz"
BUILD_FILE = "build.json"


# ---------------------------------------------------------------------------
# batching policy + server
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    """When the collector flushes: at ``max_batch`` queued requests, or when
    the OLDEST queued request has waited ``max_wait_s`` (so a lone request's
    latency is bounded by max_wait_s + one launch, never unbounded).
    ``waste`` is the per-request padding bound ``plan_buckets`` enforces."""

    max_batch: int = 32
    max_wait_s: float = 0.01
    waste: float = 0.25


class _Pending:
    """Shared completion handle: ``wait()`` blocks until the batching loop
    fills ``result`` (or re-raises the flush error)."""

    __slots__ = ("t_enqueue", "result", "latency_s", "error", "_done")

    def __init__(self):
        self.t_enqueue = time.perf_counter()
        self.result = None
        self.latency_s: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def wait(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError("request not answered within timeout")
        if self.error is not None:
            raise self.error
        return self.result


class PendingQuery(_Pending):
    """Handle returned by ``KernelServer.submit``; ``wait()`` returns the
    ``QueryResult``."""

    __slots__ = ("request",)

    def __init__(self, request: QueryRequest):
        super().__init__()
        self.request = request


class PendingAppend(_Pending):
    """Handle returned by ``KernelServer.submit_append``; ``wait()`` returns
    the ``GenerationStats`` of the absorbed batch.  Appends are absorbed in
    ARRIVAL ORDER relative to each other and to queries in the same flush,
    so a query submitted after an append is answered by the refreshed
    artifact."""

    __slots__ = ("X_new", "y_new")

    def __init__(self, X_new, y_new):
        super().__init__()
        self.X_new = np.asarray(X_new, np.float32)
        self.y_new = np.asarray(y_new, np.float32)


class KernelServer:
    """Threaded continuous-batching loop over ``answer_batch``.

    One background worker owns the launch path; ``submit`` is safe from any
    number of client threads.  Counters (``buckets_served``,
    ``requests_served``) and the per-request ``latencies_s`` log are the
    ground truth the bench and the serve-smoke assertions read.
    """

    def __init__(self, artifact: KernelModelArtifact,
                 policy: BatchPolicy = BatchPolicy(), op=None,
                 maintainer: Optional[IncrementalMaintainer] = None):
        self.artifact = artifact
        self.policy = policy
        self.op = artifact.landmark_operator() if op is None else op
        self.maintainer = maintainer
        self._cv = threading.Condition()
        self._queue: List[_Pending] = []
        self._stopping = False
        self.buckets_served = 0
        self.batches_served = 0
        self.requests_served = 0
        self.appends_served = 0
        self.latencies_s: List[float] = []
        self.append_latencies_s: List[float] = []
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    # -- client side --------------------------------------------------------

    def submit(self, X, task: str = "krr") -> PendingQuery:
        req = X if isinstance(X, QueryRequest) else QueryRequest(X, task)
        return self._enqueue(PendingQuery(req))

    def submit_append(self, X_new, y_new) -> PendingAppend:
        """Enqueue a training batch for incremental absorption (requires a
        ``maintainer``).  Absorbed in arrival order within the batching
        loop; ``wait()`` returns the batch's ``GenerationStats``."""
        if self.maintainer is None:
            raise RuntimeError(
                "KernelServer has no IncrementalMaintainer; construct with "
                "maintainer= to accept appends")
        return self._enqueue(PendingAppend(X_new, y_new))

    def _enqueue(self, pending):
        with self._cv:
            if self._stopping:
                raise RuntimeError("server is stopped")
            self._queue.append(pending)
            self._cv.notify_all()
        return pending

    def stop(self):
        """Drain the queue, then join the worker (idempotent)."""
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        self._worker.join()

    # -- worker side --------------------------------------------------------

    def _take_batch(self) -> List[PendingQuery]:
        """Block until a flush is due; return the batch (empty = shut down)."""
        with self._cv:
            while not self._queue and not self._stopping:
                self._cv.wait()
            if not self._queue:
                return []                                 # stopping + drained
            deadline = self._queue[0].t_enqueue + self.policy.max_wait_s
            while (len(self._queue) < self.policy.max_batch
                   and not self._stopping):
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cv.wait(timeout=remaining)
            batch = self._queue[: self.policy.max_batch]
            del self._queue[: len(batch)]
            return batch

    def _loop(self):
        while True:
            batch = self._take_batch()
            if not batch:
                return
            try:
                self._flush(batch)
            except BaseException as e:                    # propagate to waiters
                for p in batch:
                    if not p._done.is_set():
                        p.error = e
                        p._done.set()

    def _flush(self, batch: List[_Pending]):
        """Process one collected batch IN ARRIVAL ORDER: maximal runs of
        queries are bucketed and launched together; each append between
        them is absorbed before the next run, so later queries see the
        refreshed artifact."""
        i = 0
        while i < len(batch):
            if isinstance(batch[i], PendingAppend):
                self._absorb(batch[i])
                i += 1
                continue
            j = i
            while j < len(batch) and not isinstance(batch[j], PendingAppend):
                j += 1
            self._answer(batch[i:j])
            i = j
        self.batches_served += 1

    def _answer(self, run: List[PendingQuery]):
        requests = [p.request for p in run]
        results = [None] * len(run)
        for bucket in plan_buckets(requests, waste=self.policy.waste):
            answers = answer_batch(
                self.artifact, [requests[i] for i in bucket], op=self.op,
                bucket=self.buckets_served)
            jax.block_until_ready([a.out for a in answers])
            self.buckets_served += 1
            for i, res in zip(bucket, answers):
                results[i] = res
        now = time.perf_counter()
        for p, res in zip(run, results):
            p.result = res
            p.latency_s = now - p.t_enqueue
            self.latencies_s.append(p.latency_s)
            self.requests_served += 1
            p._done.set()

    def _absorb(self, p: PendingAppend):
        old = self.artifact
        stats: GenerationStats = self.maintainer.append(p.X_new, p.y_new)
        art = self.maintainer.artifact
        if art is not old:
            # a re-sketch replaces the landmarks; the query op must follow
            # (rebind keeps the meters running across the swap)
            if art.X_landmarks is not old.X_landmarks and \
                    hasattr(self.op, "rebind"):
                self.op.rebind(art.landmark_operator())
            self.artifact = art
        p.result = stats
        p.latency_s = time.perf_counter() - p.t_enqueue
        self.append_latencies_s.append(p.latency_s)
        self.appends_served += 1
        p._done.set()


def percentile_ms(latencies_s: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(latencies_s, np.float64), q) * 1e3)


# ---------------------------------------------------------------------------
# canned trace: build-time oracle answers, replayed by fresh serving processes
# ---------------------------------------------------------------------------

def synth_problem(n: int, d: int, seed: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Deterministic synthetic regression problem (shared by --build and the
    --serve rebuild hook, so a cold boot recreates the identical artifact)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    w = rng.standard_normal((d,)).astype(np.float32)
    y = np.tanh(X @ w) + 0.1 * rng.standard_normal(n).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y, jnp.float32)


def synth_batches(params: dict, batches: int, rows: int
                  ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Append batches drawn from the SAME generative process as
    ``synth_problem`` (same seed stream prefix, so the grown corpus is the
    deterministic continuation of the base one)."""
    n, d, seed = params["n"], params["d"], params["seed"]
    rng = np.random.default_rng(seed)
    rng.standard_normal((n, d))                      # replay the base X draw
    w = rng.standard_normal((d,)).astype(np.float32)
    rng.standard_normal(n)                           # ... and the base noise
    out = []
    for _ in range(batches):
        Xb = rng.standard_normal((rows, d)).astype(np.float32)
        yb = np.tanh(Xb @ w) + 0.1 * rng.standard_normal(rows).astype(
            np.float32)
        out.append((Xb, yb))
    return out


def build_from_params(params: dict) -> KernelModelArtifact:
    X, y = synth_problem(params["n"], params["d"], params["seed"])
    spec = pw_specs.get_spec(params["kernel"], **params["spec_params"])
    return build_artifact(
        X, y, spec, c=params["c"], s=params["s"], alpha=params["alpha"],
        n_components=params["n_components"],
        key=jax.random.PRNGKey(params["seed"]),
        use_pallas=params["use_pallas"])


def write_trace(directory: str, artifact: KernelModelArtifact, params: dict,
                n_queries: int, seed: int) -> str:
    """Canned heterogeneous query trace + oracle-expected outputs.

    KRR expectations come from ``krr_reference`` (an independent f64 solve
    of the approximated kernel through a thin QR of C, never n×n, one call
    for every KRR query); KPCA/feature expectations from the dense-route
    ``dense_oracle``.  A serving process that matches this file to ≤1e-5
    has verified the Woodbury identity, the head algebra, the fused Pallas
    cross launch, and checkpoint persistence at once.
    """
    rng = np.random.default_rng(seed + 1)
    _, y = synth_problem(params["n"], params["d"], params["seed"])
    sizes = [int(rng.choice([5, 17, 33, 64])) for _ in range(n_queries)]
    tasks = [("krr", "kpca", "features")[i % 3] for i in range(n_queries)]
    payload = {"tasks": np.array(tasks), "sizes": np.array(sizes)}
    d = params["d"]
    queries = [rng.standard_normal((nq, d)).astype(np.float32)
               for nq in sizes]
    krr = [i for i, task in enumerate(tasks) if task == "krr"]
    if krr:
        stacked = np.asarray(krr_reference(
            artifact, np.concatenate([queries[i] for i in krr]), y))
        bounds = np.cumsum([0] + [sizes[i] for i in krr])
        for i, lo, hi in zip(krr, bounds[:-1], bounds[1:]):
            payload[f"e{i}"] = stacked[lo:hi]
    for i, (Xq, task) in enumerate(zip(queries, tasks)):
        payload[f"q{i}"] = Xq
        if task != "krr":
            payload[f"e{i}"] = np.asarray(dense_oracle(artifact, Xq, task),
                                          np.float32)
    path = os.path.join(directory, TRACE_FILE)
    np.savez(path, **payload)
    return path


def load_trace(directory: str) -> List[Tuple[np.ndarray, str, np.ndarray]]:
    with np.load(os.path.join(directory, TRACE_FILE)) as z:
        tasks = [str(t) for t in z["tasks"]]
        return [(z[f"q{i}"], task, z[f"e{i}"])
                for i, task in enumerate(tasks)]


def replay_trace(server: KernelServer,
                 trace: Sequence[Tuple[np.ndarray, str, np.ndarray]]
                 ) -> Tuple[float, List[float]]:
    """Submit the whole trace (as concurrent clients would), wait for every
    answer, and return (worst parity gap vs expected, per-request latencies)."""
    pending = [server.submit(Xq, task) for Xq, task, _ in trace]
    gaps, lats = [], []
    for p, (_, _, expected) in zip(pending, trace):
        res = p.wait(timeout=60.0)
        gaps.append(parity_gap(res.out, expected))
        lats.append(p.latency_s)
    return max(gaps), lats


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _build(args) -> int:
    params = {
        "n": args.n, "d": args.d, "c": args.c, "s": args.s,
        "alpha": args.alpha, "n_components": args.n_components,
        "kernel": args.kernel, "spec_params": {"sigma": args.sigma},
        "seed": args.seed, "use_pallas": not args.no_pallas,
    }
    os.makedirs(args.dir, exist_ok=True)
    artifact = build_from_params(params)
    path = save_artifact(args.dir, artifact, step=0)
    with open(os.path.join(args.dir, BUILD_FILE), "w") as f:
        json.dump(params, f, indent=1)
    trace_path = write_trace(args.dir, artifact, params,
                             n_queries=args.queries, seed=args.seed)
    print(f"artifact (c={artifact.c}) committed at {path}")
    print(f"trace with {args.queries} queries at {trace_path}")
    return 0


def _serve(args) -> int:
    with open(os.path.join(args.dir, BUILD_FILE)) as f:
        params = json.load(f)

    artifact, recovery = load_or_rebuild(
        args.dir, lambda: build_from_params(params))
    boot = "warm" if recovery.warm else "cold"
    print(f"boot: {boot} "
          f"(events: {[e.kind for e in recovery.events]})")
    if args.require_warm and not recovery.warm:
        print("FAIL: --require-warm but boot was cold")
        return 1

    if args.append_batches > 0 and int(artifact.C.shape[0]) != params["n"]:
        # A previous append run left a delta chain on the store, so the
        # warm boot restored the grown chain tip — but the canned trace and
        # the synth base (X, y) describe the BASE corpus.  Restart the leg
        # from the latest FULL snapshot and drop the prior run's deltas:
        # the leg replays a deterministic append stream, so reruns are
        # idempotent instead of chaining deltas onto a stale tip.
        steps = ckpt.committed_steps(args.dir)
        fulls = [s for s in steps if not is_delta_step(args.dir, s)]
        if fulls:
            artifact = load_artifact(args.dir, step=max(fulls))
            for s in steps:
                if s > max(fulls):
                    ckpt.remove_step(args.dir, s)
            print(f"append leg: rebased on full step {max(fulls)} "
                  f"(dropped {len(steps) - len(fulls)} prior delta step(s))")

    op = CountingOperator(artifact.landmark_operator())
    policy = BatchPolicy(max_batch=args.max_batch,
                         max_wait_s=args.max_wait_ms / 1e3)
    maintainer = None
    if args.append_batches > 0:
        X_base, y_base = synth_problem(params["n"], params["d"],
                                       params["seed"])
        maintainer = IncrementalMaintainer(
            artifact, np.asarray(y_base), directory=args.dir,
            X=np.asarray(X_base),
            staleness=StalenessPolicy(
                drift_threshold=args.drift_threshold,
                error_budget=float("inf"), max_generations=0),
            op=op)
    server = KernelServer(artifact, policy, op=op, maintainer=maintainer)
    trace = load_trace(args.dir)
    try:
        gap_warmup, _ = replay_trace(server, trace)       # compile caches
        sweeps0, buckets0 = op.counts["cross_sweeps"], server.buckets_served
        gap, lats = replay_trace(server, trace)
        append_ok = True
        if args.append_batches > 0:
            append_ok = _append_leg(args, params, server, op)
    finally:
        server.stop()

    sweeps = op.counts["cross_sweeps"] - sweeps0
    buckets = server.buckets_served - buckets0
    p50, p99 = percentile_ms(lats, 50), percentile_ms(lats, 99)
    print(f"replayed {len(trace)} queries: parity {gap:.3e} "
          f"(warmup pass {gap_warmup:.3e})")
    print(f"launches: {sweeps} cross sweeps over {buckets} buckets "
          f"(route: {op.last_route})")
    print(f"latency: p50 {p50:.2f} ms  p99 {p99:.2f} ms")

    ok = append_ok
    if gap > args.parity_tol or gap_warmup > args.parity_tol:
        print(f"FAIL: parity {max(gap, gap_warmup):.3e} > "
              f"tol {args.parity_tol:.1e}")
        ok = False
    if sweeps != buckets:
        print(f"FAIL: {sweeps} cross sweeps != {buckets} buckets "
              f"(serving must launch exactly once per bucket)")
        ok = False
    if args.max_p50_ms is not None and p50 > args.max_p50_ms:
        print(f"FAIL: p50 {p50:.2f} ms > budget {args.max_p50_ms} ms")
        ok = False
    print("serve ok" if ok else "serve FAILED")
    return 0 if ok else 1


def _append_leg(args, params: dict, server: KernelServer,
                op: CountingOperator) -> bool:
    """The append-refresh replay: absorb batches through the live server,
    then hold the absorb to the O(b·c) meter contract and the grown-corpus
    parity contract."""
    batches = synth_batches(params, args.append_batches, args.append_rows)
    before = dict(op.counts)
    n_before = int(server.artifact.C.shape[0])

    pending = [server.submit_append(Xb, yb) for Xb, yb in batches]
    stats = [p.wait(timeout=60.0) for p in pending]
    gens = [s.generation for s in stats]
    app_p50 = percentile_ms([p.latency_s for p in pending], 50)
    print(f"append: absorbed {len(batches)} x {args.append_rows} rows "
          f"(n {n_before} -> {stats[-1].n_after}), p50 {app_p50:.2f} ms, "
          f"drift {stats[-1].drift:.3f}")

    ok = True
    # the O(b·c) contract: ONE thin metered launch per batch, nothing else
    deltas = {k: op.counts[k] - before.get(k, 0)
              for k in ("append_sweeps", "sweeps", "fulls", "cross_sweeps")}
    if deltas["append_sweeps"] != len(batches):
        print(f"FAIL: {deltas['append_sweeps']} append sweeps for "
              f"{len(batches)} batches (must be exactly one per batch)")
        ok = False
    if deltas["sweeps"] or deltas["fulls"] or deltas["cross_sweeps"]:
        print(f"FAIL: absorb touched the kernel beyond the thin launch "
              f"(sweeps={deltas['sweeps']} fulls={deltas['fulls']} "
              f"cross={deltas['cross_sweeps']})")
        ok = False
    if gens != list(range(gens[0], gens[0] + len(batches))):
        print(f"FAIL: generations {gens} not consecutive in arrival order")
        ok = False

    # grown-corpus parity: fresh queries vs the f64 KRR reference over the
    # artifact as it NOW stands (base + every appended row)
    rng = np.random.default_rng(params["seed"] + 2)
    _, y_base = synth_problem(params["n"], params["d"], params["seed"])
    y_full = np.concatenate([np.asarray(y_base)[:, None]]
                            + [yb[:, None] for _, yb in batches], axis=0)
    art = server.artifact
    gaps = []
    for nq in (5, 17, 33):
        Xq = rng.standard_normal((nq, params["d"])).astype(np.float32)
        expected = krr_reference(art, jnp.asarray(Xq),
                                 jnp.asarray(y_full, jnp.float32))
        res = server.submit(Xq, "krr").wait(timeout=60.0)
        gaps.append(float(parity_gap(res.out, expected)))
        for task in ("kpca", "features"):
            expected = dense_oracle(art, jnp.asarray(Xq), task)
            res = server.submit(Xq, task).wait(timeout=60.0)
            gaps.append(float(parity_gap(res.out, expected)))
    gap = max(gaps)
    print(f"append: grown-corpus parity {gap:.3e} over {len(gaps)} probes")
    if gap > args.parity_tol:
        print(f"FAIL: grown-corpus parity {gap:.3e} > "
              f"tol {args.parity_tol:.1e}")
        ok = False

    # persistence: every generation is a committed delta step, and a fresh
    # chain restore reproduces the LIVE artifact bitwise
    steps = ckpt.committed_steps(args.dir)
    if len(steps) < 1 + len(batches):
        print(f"FAIL: expected >= {1 + len(batches)} committed steps "
              f"(base + one delta per batch), found {steps}")
        ok = False
    restored = load_artifact(args.dir)
    if restored is None or \
            not np.array_equal(np.asarray(restored.C), np.asarray(art.C)) or \
            not np.array_equal(np.asarray(restored.heads["krr"]),
                               np.asarray(art.heads["krr"])):
        print("FAIL: delta-chain restore does not reproduce the live "
              "artifact bitwise")
        ok = False
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="kernel-inference serving: precompute (--build) and "
                    "warm-boot replay (--serve)")
    p.add_argument("--build", action="store_true")
    p.add_argument("--serve", action="store_true")
    p.add_argument("--dir", required=True,
                   help="checkpoint directory (the factor store)")
    # build-side knobs (persisted to build.json for the rebuild hook)
    p.add_argument("--n", type=int, default=240)
    p.add_argument("--d", type=int, default=24)
    p.add_argument("--c", type=int, default=48)
    p.add_argument("--s", type=int, default=96)
    p.add_argument("--alpha", type=float, default=1.0)
    p.add_argument("--n-components", type=int, default=8)
    p.add_argument("--kernel", default="rbf")
    p.add_argument("--sigma", type=float, default=1.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--queries", type=int, default=12)
    p.add_argument("--no-pallas", action="store_true")
    # serve-side knobs
    p.add_argument("--require-warm", action="store_true",
                   help="fail unless the artifact restored from checkpoint")
    p.add_argument("--parity-tol", type=float, default=1e-5)
    p.add_argument("--max-p50-ms", type=float, default=None)
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    # incremental-append leg (serve side)
    p.add_argument("--append-batches", type=int, default=0,
                   help="absorb this many appended-row batches through the "
                        "live server and assert the O(b*c) meter + "
                        "grown-corpus parity contracts")
    p.add_argument("--append-rows", type=int, default=16,
                   help="rows per appended batch")
    p.add_argument("--drift-threshold", type=float, default=float("inf"),
                   help="staleness drift threshold for the append leg "
                        "(default: never re-sketch)")
    args = p.parse_args(argv)

    if args.build == args.serve:
        p.error("exactly one of --build / --serve is required")
    enable_compile_cache()
    return _build(args) if args.build else _serve(args)


if __name__ == "__main__":
    raise SystemExit(main())
