"""Serving path: artifact heads vs dense oracles, bucketed fused launches,
warm-boot persistence through checkpoint/ + fault-tolerance recompute, and
the continuous-batching KernelServer."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.instrument import CountingOperator
from repro.kernels.pairwise import specs as pw_specs
from repro.launch.serve_kernel import (
    BatchPolicy,
    KernelServer,
    build_from_params,
    load_trace,
    replay_trace,
    synth_problem,
    write_trace,
)
from repro.serve import (
    QueryRequest,
    answer_batch,
    build_artifact,
    dense_krr_oracle,
    dense_oracle,
    krr_reference,
    load_artifact,
    load_or_rebuild,
    parity_gap,
    plan_buckets,
    save_artifact,
    serve_kernel_model,
)

N, D, C, S = 240, 24, 48, 96


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((N, D)).astype(np.float32)
    y = rng.standard_normal((N,)).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


@pytest.fixture(scope="module")
def artifact(problem):
    X, y = problem
    spec = pw_specs.get_spec("rbf", sigma=1.0)
    return build_artifact(X, y, spec, c=C, s=S, alpha=1.0, n_components=8,
                          key=jax.random.PRNGKey(0), use_pallas=True)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(7)
    return jnp.asarray(rng.standard_normal((37, D)).astype(np.float32))


# ---------------------------------------------------------------------------
# parity vs the dense oracles
# ---------------------------------------------------------------------------

def test_krr_parity_vs_dense_solve_oracle(artifact, problem, queries):
    """The acceptance gate: the served prediction must match an INDEPENDENT
    dense KRR solve on the approximated kernel (no Woodbury identity, no
    artifact head) to <=1e-5."""
    _, y = problem
    res = serve_kernel_model(artifact, [QueryRequest(queries, "krr")])
    expected = dense_krr_oracle(artifact, queries, y)
    assert parity_gap(res[0].out, expected) <= 1e-5


@pytest.mark.parametrize("targets", [1, 3], ids=["vector", "matrix"])
def test_krr_reference_matches_dense_solve_in_float64(artifact, queries,
                                                      targets):
    """The scalable QR/eigen KRR reference (never n×n) agrees with the
    direct dense solve at small n, to float64 accuracy before both round to
    their f32 outputs."""
    y = jnp.asarray(np.random.default_rng(11).standard_normal((N, targets)),
                    jnp.float32)
    y = y[:, 0] if targets == 1 else y
    got = np.asarray(krr_reference(artifact, queries, y), np.float64)
    ref = np.asarray(dense_krr_oracle(artifact, queries, y), np.float64)
    assert got.shape == (queries.shape[0], targets)
    assert parity_gap(got, ref) <= 1e-6


def test_krr_head_stays_accurate_when_the_spectrum_dwarfs_the_ridge():
    """Clustered data and a ridge far below the top of C U Cᵀ's spectrum
    (the regime of a large corpus): the f32 head must still reproduce the
    f64 reference at the serving gate.  Forming Cᵀw = Cᵀ(y − C M Cᵀ y)/α
    first loses ~1e-3 here to cancellation; ``krr_head``'s M Cᵀ y does not."""
    rng = np.random.default_rng(5)
    centers = rng.normal(size=(26, 16)) * 2.0
    X = centers[rng.integers(0, 26, 2048)] + rng.normal(size=(2048, 16)) * .7
    X = jnp.asarray((X - X.mean(0)) / X.std(0), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=(2048, 3)), jnp.float32)
    spec = pw_specs.get_spec("rbf", sigma=3.9)
    art = build_artifact(X, y, spec, c=256, s=1024, alpha=1e-3,
                         s_sketch="uniform", key=jax.random.PRNGKey(0),
                         use_pallas=False)
    Xq = X[:50] + 0.1
    G = np.asarray(pw_specs.apply(spec, Xq, art.X_landmarks), np.float64)
    served = G @ np.asarray(art.heads["krr"], np.float64)
    assert parity_gap(served, krr_reference(art, Xq, y)) <= 1e-5


def test_kpca_and_feature_parity_vs_dense_route(artifact, queries):
    res = serve_kernel_model(artifact, [QueryRequest(queries, "kpca"),
                                        QueryRequest(queries, "features")])
    assert parity_gap(res[0].out, dense_oracle(artifact, queries,
                                               "kpca")) <= 1e-5
    assert parity_gap(res[1].out, dense_oracle(artifact, queries,
                                               "features")) <= 1e-5


def test_feature_map_gram_matches_fast_model(artifact, queries):
    """phi(x)^T phi(y) must reproduce the Nystrom extension
    k_hat(x, y) = K(x, X_S) U K(y, X_S)^T."""
    res = serve_kernel_model(artifact, [QueryRequest(queries, "features")])
    phi = np.asarray(res[0].out, np.float64)
    G = np.asarray(pw_specs.apply(artifact.spec, queries,
                                  artifact.X_landmarks), np.float64)
    khat = G @ np.asarray(artifact.U, np.float64) @ G.T
    assert np.max(np.abs(phi @ phi.T - khat)) <= 1e-4


def test_train_points_round_trip(artifact, problem):
    """Rows of C are K(x_i, X_S), so serving the TRAIN points reproduces the
    fast model's fitted values exactly (same algebra, same precision)."""
    X, _ = problem
    res = serve_kernel_model(artifact, [QueryRequest(X[:50], "krr")])
    fitted = artifact.C[:50].astype(jnp.float32) @ artifact.heads["krr"]
    assert parity_gap(res[0].out, fitted) <= 1e-5


# ---------------------------------------------------------------------------
# bucketed batching: one fused launch per bucket
# ---------------------------------------------------------------------------

def test_one_cross_sweep_per_bucket(artifact):
    rng = np.random.default_rng(3)
    sizes = [100, 90, 20]
    reqs = [QueryRequest(rng.standard_normal((nq, D)).astype(np.float32),
                         task)
            for nq, task in zip(sizes, ("krr", "kpca", "features"))]
    buckets = plan_buckets(reqs, waste=0.25)
    assert len(buckets) == 2          # [100, 90] bucket + [20] bucket

    op = CountingOperator(artifact.landmark_operator())
    results = serve_kernel_model(artifact, reqs, waste=0.25, op=op)
    assert op.counts["cross_sweeps"] == len(buckets)
    assert op.last_route == "pallas_fused_rows"
    # results come back in input order with the right shapes/tasks
    for r, req in zip(results, reqs):
        assert r.task == req.task
        assert r.out.shape[0] == req.n_q


def test_heterogeneous_batch_matches_per_request_answers(artifact):
    rng = np.random.default_rng(4)
    reqs = [QueryRequest(rng.standard_normal((nq, D)).astype(np.float32),
                         task)
            for nq, task in [(5, "krr"), (33, "kpca"), (5, "features"),
                             (17, "krr")]]
    batched = serve_kernel_model(artifact, reqs)
    for req, got in zip(reqs, batched):
        solo = answer_batch(artifact, [req])[0]
        assert parity_gap(got.out, solo.out) <= 1e-6


def test_padding_rows_never_leak(artifact):
    """A size-1 request bucketed with a big one gets exactly its own row."""
    rng = np.random.default_rng(5)
    small = QueryRequest(rng.standard_normal((1, D)).astype(np.float32))
    big = QueryRequest(rng.standard_normal((4, D)).astype(np.float32))
    out = answer_batch(artifact, [big, small])
    assert out[1].out.shape[0] == 1
    assert parity_gap(out[1].out,
                      answer_batch(artifact, [small])[0].out) <= 1e-6


def test_unknown_task_rejected():
    with pytest.raises(ValueError, match="unknown task"):
        QueryRequest(np.zeros((3, D), np.float32), task="cluster")


# ---------------------------------------------------------------------------
# refit: new targets through the cached Woodbury workspace
# ---------------------------------------------------------------------------

def test_refit_matches_fresh_build(artifact, problem, queries):
    X, _ = problem
    rng = np.random.default_rng(11)
    y_new = jnp.asarray(rng.standard_normal((N,)).astype(np.float32))
    refitted = artifact.refit(y_new)
    served = serve_kernel_model(refitted, [QueryRequest(queries, "krr")])
    expected = dense_krr_oracle(artifact, queries, y_new)
    assert parity_gap(served[0].out, expected) <= 1e-4   # f32 workspace


# ---------------------------------------------------------------------------
# persistence: checkpoint roundtrip + recompute-on-corruption
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bitwise_predictions(artifact, queries,
                                                  tmp_path):
    save_artifact(str(tmp_path), artifact, step=0)
    restored = load_artifact(str(tmp_path))
    assert restored is not None
    assert restored.spec.name == artifact.spec.name
    assert restored.alpha == artifact.alpha
    a = serve_kernel_model(artifact, [QueryRequest(queries, "krr")])
    b = serve_kernel_model(restored, [QueryRequest(queries, "krr")])
    assert np.array_equal(np.asarray(a[0].out), np.asarray(b[0].out))


def test_load_or_rebuild_warm_then_corrupt_then_rebuilt(artifact, queries,
                                                        tmp_path):
    d = str(tmp_path)
    save_artifact(d, artifact, step=0)
    builds = []

    def build_fn():
        builds.append(1)
        return artifact

    got, rec = load_or_rebuild(d, build_fn)
    assert rec.warm and not builds
    assert [e.kind for e in rec.events] == ["restored"]

    # truncate the manifest: corruption must rebuild + re-persist, not crash
    (tmp_path / "step_000000000" / "manifest.json").write_text('{"leaf')
    got, rec = load_or_rebuild(d, build_fn)
    assert [e.kind for e in rec.events] == ["corrupt", "rebuilt"]
    assert len(builds) == 1
    a = serve_kernel_model(got, [QueryRequest(queries, "kpca")])
    assert parity_gap(a[0].out, dense_oracle(got, queries, "kpca")) <= 1e-5

    # the rebuild re-persisted: next boot is warm again
    got, rec = load_or_rebuild(d, build_fn)
    assert rec.warm and len(builds) == 1


def test_load_or_rebuild_missing_store_builds_fresh(artifact, tmp_path):
    builds = []

    def build_fn():
        builds.append(1)
        return artifact

    got, rec = load_or_rebuild(str(tmp_path / "nowhere"), build_fn)
    assert [e.kind for e in rec.events] == ["missing", "rebuilt"]
    assert len(builds) == 1 and got is artifact


# ---------------------------------------------------------------------------
# continuous batching (KernelServer) + the canned trace
# ---------------------------------------------------------------------------

def test_kernel_server_batches_concurrent_clients(artifact):
    op = CountingOperator(artifact.landmark_operator())
    server = KernelServer(
        artifact, BatchPolicy(max_batch=16, max_wait_s=0.05), op=op)
    rng = np.random.default_rng(13)
    queries = [(rng.standard_normal((nq, D)).astype(np.float32), task)
               for nq, task in [(5, "krr"), (17, "kpca"), (5, "features"),
                                (33, "krr"), (17, "krr"), (5, "kpca")]]
    try:
        results = [None] * len(queries)

        def client(i):
            Xq, task = queries[i]
            results[i] = server.submit(Xq, task).wait(timeout=60.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.stop()

    assert server.requests_served == len(queries)
    assert op.counts["cross_sweeps"] == server.buckets_served > 0
    assert len(server.latencies_s) == len(queries)
    assert all(lat > 0 for lat in server.latencies_s)
    for (Xq, task), res in zip(queries, results):
        assert res.task == task
        direct = answer_batch(artifact, [QueryRequest(Xq, task)])[0]
        assert parity_gap(res.out, direct.out) <= 1e-6


def test_kernel_server_submit_after_stop_raises(artifact):
    server = KernelServer(artifact)
    server.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        server.submit(np.zeros((2, D), np.float32))


def test_trace_write_replay_roundtrip(tmp_path):
    """The serve-smoke mechanics in-process: build params -> artifact ->
    trace with oracle expectations -> fresh server replays to <=1e-5."""
    params = {"n": 160, "d": 12, "c": 32, "s": 64, "alpha": 1.0,
              "n_components": 6, "kernel": "rbf",
              "spec_params": {"sigma": 1.0}, "seed": 3, "use_pallas": True}
    art = build_from_params(params)
    write_trace(str(tmp_path), art, params, n_queries=6, seed=3)
    trace = load_trace(str(tmp_path))
    assert len(trace) == 6

    op = CountingOperator(art.landmark_operator())
    server = KernelServer(art, BatchPolicy(max_wait_s=0.02), op=op)
    try:
        gap, lats = replay_trace(server, trace)
    finally:
        server.stop()
    assert gap <= 1e-5
    assert len(lats) == 6
    assert op.counts["cross_sweeps"] == server.buckets_served


def test_build_from_params_deterministic():
    params = {"n": 120, "d": 8, "c": 24, "s": 48, "alpha": 1.0,
              "n_components": 4, "kernel": "rbf",
              "spec_params": {"sigma": 1.0}, "seed": 5, "use_pallas": True}
    a = build_from_params(params)
    b = build_from_params(params)
    assert np.array_equal(np.asarray(a.heads["krr"]),
                          np.asarray(b.heads["krr"]))
    X, _ = synth_problem(params["n"], params["d"], params["seed"])
    assert np.array_equal(
        np.asarray(a.X_landmarks),
        np.asarray(jnp.take(X, a.landmark_indices, axis=0)))


# ---------------------------------------------------------------------------
# mixed-precision serving
# ---------------------------------------------------------------------------

def test_serve_bf16_cross_launches_within_budget(artifact, queries):
    """serve_kernel_model(precision='bf16_f32acc'): an f32-built artifact
    served with bf16 cross tiles must stay within the quantization budget of
    the f32 serving answers (scale-normalized), for every task head."""
    reqs = [QueryRequest(queries, t) for t in ("krr", "kpca", "features")]
    f32 = serve_kernel_model(artifact, reqs)
    bf16 = serve_kernel_model(artifact, reqs, precision="bf16_f32acc")
    for a, b in zip(bf16, f32):
        assert parity_gap(a.out, b.out) <= 5e-2


def test_serve_bf16_route_and_metering(artifact, queries):
    """The bf16 cross launch is attributed: route suffix + last_precision on
    the CountingOperator, one cross sweep per bucket as ever."""
    op = CountingOperator(
        artifact.landmark_operator(precision="bf16_f32acc"))
    serve_kernel_model(artifact, [QueryRequest(queries, "krr")], op=op)
    assert op.counts["cross_sweeps"] == 1
    assert op.last_route == "pallas_fused_rows+bf16_f32acc"
    assert op.last_precision == "bf16_f32acc"


def test_artifact_spec_precision_round_trips_through_checkpoint(
        artifact, tmp_path):
    """A bf16-spec'd artifact persists its tile policy: load_artifact hands
    back an operator that launches bf16 crosses without being asked."""
    import dataclasses as dc
    bf_art = dc.replace(
        artifact, spec=artifact.spec.with_precision("bf16_f32acc"))
    save_artifact(str(tmp_path / "ckpt"), bf_art)
    loaded = load_artifact(str(tmp_path / "ckpt"))
    assert loaded.spec is bf_art.spec          # registry-cached identity
    assert loaded.landmark_operator().precision == "bf16_f32acc"


def test_l1_signsplit_plan_cached_on_artifact_and_warm_boot(tmp_path):
    """An l1dist artifact persists its sign-split plan: every operator the
    artifact hands out shares the SAME edges array (no per-instance
    rebuilds), and a warm boot restores plan identity from the checkpoint."""
    rng = np.random.default_rng(11)
    X = jnp.asarray(rng.integers(0, 5, size=(120, 6)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(120), jnp.float32)
    spec = pw_specs.get_spec("laplacian", gamma=0.3)
    art = build_artifact(X, y, spec, c=24, s=48, alpha=1.0, n_components=4,
                         key=jax.random.PRNGKey(3), use_pallas=True)

    assert art.l1_route == "mxu_signsplit"
    assert art.l1_edges is not None
    op_a, op_b = art.landmark_operator(), art.landmark_operator()
    assert op_a.l1_edges() is art.l1_edges
    assert op_b.l1_edges() is art.l1_edges      # shared, not rebuilt

    save_artifact(str(tmp_path), art, step=0)

    def build_fn():  # warm boot must never fall back to a rebuild
        raise AssertionError("rebuild called on a warm store")

    loaded, rec = load_or_rebuild(str(tmp_path), build_fn)
    assert rec.warm
    assert loaded.l1_route == "mxu_signsplit"
    assert np.array_equal(np.asarray(loaded.l1_edges),
                          np.asarray(art.l1_edges))
    assert loaded.landmark_operator().l1_edges() is loaded.l1_edges

    # the restored plan serves: answers match the dense oracle
    q = jnp.asarray(rng.integers(0, 5, size=(17, 6)), jnp.float32)
    a = serve_kernel_model(loaded, [QueryRequest(q, "krr")])
    assert parity_gap(a[0].out, dense_oracle(loaded, q, "krr")) <= 1e-4


def test_rbf_artifact_has_no_l1_plan():
    """Non-l1dist specs carry no plan: route and edges stay None and the
    operator's lazy path is untouched."""
    rng = np.random.default_rng(12)
    X = jnp.asarray(rng.standard_normal((90, 5)), jnp.float32)
    y = jnp.asarray(rng.standard_normal(90), jnp.float32)
    art = build_artifact(X, y, pw_specs.get_spec("rbf", sigma=1.0),
                         c=18, s=36, alpha=1.0, n_components=4,
                         key=jax.random.PRNGKey(4), use_pallas=True)
    assert art.l1_route is None and art.l1_edges is None
