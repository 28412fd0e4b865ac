"""The paper's applications over the library: streaming guarantees,
approx_eigh edge cases, calibration parity, and accuracy against dense.

The tentpole invariant: KPCA and spectral clustering run with ZERO
``full()`` calls on the kernel operator (booby-trapped over the whole
pipeline here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eig, spsd
from repro.core.kernelop import PairwiseKernel, SPSDOperator
from repro.core.sketched_attention import sketched_attention
from repro.kernels.pairwise import calibrate as pw_cal
from repro.kernels.pairwise import specs as pw_specs
from repro.launch.serve_kernel import synth_problem
from repro.serve.artifact import build_artifact
from repro.serve.engine import (QueryRequest, dense_krr_oracle, parity_gap,
                                serve_kernel_model)

# ---------------------------------------------------------------------------
# data and metrics of the paper's applications
# ---------------------------------------------------------------------------

#: name: (n, d, classes) — sized after the paper's Table 6/7, CPU-friendly
DATASETS = {"letters": (1500, 16, 26), "pendigit": (1500, 16, 10)}


def make_dataset(name: str, seed: int = 0, n=None):
    """Gaussian mixture with the dataset's (n, d, classes), standardized."""
    n_, d, k = DATASETS[name]
    n = n or n_
    rng = np.random.default_rng(seed)
    k_eff = max(k, 8)
    centers = rng.normal(size=(k_eff, d)) * 2.0
    labels = rng.integers(0, k_eff, size=n)
    X = centers[labels] + rng.normal(size=(n, d)) * 0.7
    # per-feature scaling like libsvm preprocessing
    X = (X - X.mean(0)) / (X.std(0) + 1e-9)
    return jnp.asarray(X, jnp.float32), labels % max(k, 2)


def make_operator(X) -> PairwiseKernel:
    """RBF operator with the registry-calibrated bandwidth (no full())."""
    return PairwiseKernel(X, pw_cal.calibrate_sigma(X, "rbf"))


def nmi(labels_a, labels_b) -> float:
    """Normalized mutual information (paper §6.4 metric)."""
    a = np.asarray(labels_a)
    b = np.asarray(labels_b)
    n = a.shape[0]
    ua, ub = np.unique(a), np.unique(b)
    cont = np.zeros((len(ua), len(ub)))
    for i, x in enumerate(ua):
        for j, y in enumerate(ub):
            cont[i, j] = np.sum((a == x) & (b == y))
    pij = cont / n
    pi = pij.sum(1, keepdims=True)
    pj = pij.sum(0, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        mi = np.nansum(pij * np.log(pij / (pi @ pj)))
        ha = -np.nansum(pi * np.log(pi))
        hb = -np.nansum(pj * np.log(pj))
    return float(mi / max(np.sqrt(ha * hb), 1e-12))


def kmeans(X, k, seed=0, iters=50):
    rng = np.random.default_rng(seed)
    X = np.asarray(X)
    idx = rng.choice(X.shape[0], k, replace=False)
    C = X[idx]
    for _ in range(iters):
        d = ((X[:, None] - C[None]) ** 2).sum(-1)
        lab = d.argmin(1)
        for j in range(k):
            pts = X[lab == j]
            if len(pts):
                C[j] = pts.mean(0)
    return lab


def knn_classify(train_x, train_y, test_x, k=10):
    d = ((np.asarray(test_x)[:, None] - np.asarray(train_x)[None]) ** 2
         ).sum(-1)
    nn = np.argsort(d, axis=1)[:, :k]
    votes = np.asarray(train_y)[nn]
    out = []
    for row in votes:
        vals, cnt = np.unique(row, return_counts=True)
        out.append(vals[cnt.argmax()])
    return np.asarray(out)


class NormalizedAffinity(SPSDOperator):
    """D^-1/2 K D^-1/2 as a matmat-only operator view for subspace
    iteration — every application streams through the inner operator."""

    def __init__(self, inner, dinv):
        self.inner = inner
        self.dinv = dinv

    @property
    def n(self):
        return self.inner.n

    def matmat(self, V, block_size=None, mesh=None):
        W = self.inner.matmat(self.dinv[:, None] * V,
                              block_size=block_size, mesh=mesh)
        return self.dinv[:, None] * W


def fast_model(Kop, c, selection, seed):
    """Algorithm 1 with ``selection`` choosing C and a uniform s = 4c
    sketch for the fast U."""
    return spsd.fast_model(Kop, jax.random.fold_in(jax.random.PRNGKey(seed), 0),
                           c=c, s=4 * c, s_sketch="uniform", selection=selection)


def kpca_misalignment(n, k, c, selection, seed=0) -> float:
    """Misalignment (Eq. 10) of the fast model's top-k eigenvectors against
    the streamed-exact ones from subspace iteration."""
    X, _ = make_dataset("pendigit", seed=seed, n=n)
    Kop = make_operator(X)
    U_true = eig.streaming_subspace_eigh(
        Kop, k, key=jax.random.PRNGKey(seed), power_iters=8).eigenvectors
    ap = fast_model(Kop, c, selection, seed)
    return float(eig.misalignment(U_true, eig.approx_eigh(ap.C, ap.U,
                                                          k).eigenvectors))


def kpca_knn_error(n, k, c, selection, seed=0) -> float:
    """10-NN test error on KPCA features; test-point kernel columns go
    through the serving-path ``cross`` launch (no dense distance matrix)."""
    X, y = make_dataset("pendigit", seed=seed, n=n)
    ntr = X.shape[0] // 2
    Xtr, ytr, Xte, yte = X[:ntr], y[:ntr], X[ntr:], y[ntr:]
    Kop = make_operator(Xtr)
    ap = fast_model(Kop, c, selection, seed)
    feats, eres = eig.kpca_features(ap.C, ap.U, k)
    te_proj = Kop.cross(Xte, (eres.eigenvectors,))[0]            # (nte, k)
    lam = np.maximum(np.asarray(eres.eigenvalues), 1e-12)
    pred = knn_classify(np.asarray(feats), ytr,
                        np.asarray(te_proj) / np.sqrt(lam)[None, :])
    return float(np.mean(pred != np.asarray(yte)))


def spectral_nmi(n, k, c, selection, seed=0):
    """(NMI vs labels, NMI vs the dense-route clustering) of the
    degree-normalized Lemma-10 embedding on streamed-exact degrees d = K1;
    the dense route clusters the top-k eigenvectors of D^-1/2 K D^-1/2
    from streamed subspace iteration."""
    X, y = make_dataset("pendigit", seed=seed, n=n)
    Kop = make_operator(X)
    deg = Kop.matmat(jnp.ones((Kop.n, 1), jnp.float32))[:, 0]
    dinv = 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-9))
    ref = eig.streaming_subspace_eigh(
        NormalizedAffinity(Kop, dinv), k, key=jax.random.PRNGKey(seed),
        power_iters=8)
    Vr = np.asarray(ref.eigenvectors)
    Vr = Vr / np.maximum(np.linalg.norm(Vr, axis=1, keepdims=True), 1e-9)
    ref_lab = kmeans(Vr, k, seed=seed)
    ap = fast_model(Kop, c, selection, seed)
    V = eig.spectral_embedding(ap.C, ap.U, k, degrees=deg)
    lab = kmeans(np.asarray(V), k, seed=seed)
    return nmi(lab, y), nmi(lab, ref_lab)


# ---------------------------------------------------------------------------
# zero-full() booby traps over the whole application pipelines
# ---------------------------------------------------------------------------


def _boom(self):
    raise AssertionError("workload materialized the n×n kernel")


def test_kpca_never_calls_full(monkeypatch):
    monkeypatch.setattr(PairwiseKernel, "full", _boom)
    mis = kpca_misalignment(n=160, k=3, c=16, selection="uniform")
    assert np.isfinite(mis)
    err = kpca_knn_error(n=160, k=3, c=16, selection="uniform")
    assert np.isfinite(err)


def test_spectral_never_calls_full(monkeypatch):
    monkeypatch.setattr(PairwiseKernel, "full", _boom)
    nmi_labels, nmi_dense = spectral_nmi(n=160, k=4, c=16,
                                         selection="uniform")
    assert np.isfinite(nmi_labels) and np.isfinite(nmi_dense)


def test_streaming_subspace_eigh_matches_dense():
    X = jax.random.normal(jax.random.PRNGKey(0), (220, 8))
    Kop = PairwiseKernel(X, pw_specs.get_spec("rbf", sigma=2.0))
    ref = eig.streaming_subspace_eigh(Kop, 4, power_iters=8)
    lam, V = jnp.linalg.eigh(Kop.full())
    np.testing.assert_allclose(np.asarray(ref.eigenvalues),
                               np.asarray(lam[::-1][:4]), rtol=1e-4)
    mis = float(eig.misalignment(V[:, ::-1][:, :4], ref.eigenvectors))
    assert mis < 1e-6, mis


# ---------------------------------------------------------------------------
# approx_eigh edge cases the workloads hit
# ---------------------------------------------------------------------------


def test_approx_eigh_rank_deficient_C():
    """c greater than the numerical rank of C: eigenvectors must stay
    finite and the sqrt(lam) feature map NaN-free."""
    key = jax.random.PRNGKey(1)
    n, r, c = 120, 5, 24                       # C has rank 5 << c = 24
    A = jax.random.normal(key, (n, r))
    B = jax.random.normal(jax.random.fold_in(key, 1), (r, c))
    C = A @ B
    U = jnp.eye(c)
    res = eig.approx_eigh(C, U, k=8)
    assert np.all(np.isfinite(np.asarray(res.eigenvalues)))
    assert np.all(np.isfinite(np.asarray(res.eigenvectors)))
    feats, _ = eig.kpca_features(C, U, k=8)
    assert np.all(np.isfinite(np.asarray(feats))), "sqrt(lam) features NaN"


def test_approx_eigh_negative_trailing_eigenvalues():
    """Indefinite U (the fast-CUR U can be): trailing eigenvalues of M go
    negative; downstream feature maps must clamp, not NaN."""
    key = jax.random.PRNGKey(2)
    n, c = 100, 12
    C = jax.random.normal(key, (n, c))
    neg = jnp.concatenate([jnp.ones(6), -0.5 * jnp.ones(6)])
    U = jnp.diag(neg)                          # explicitly indefinite
    res = eig.approx_eigh(C, U, k=c)
    assert float(res.eigenvalues[-1]) < 0.0, "test premise: M is indefinite"
    assert np.all(np.isfinite(np.asarray(res.eigenvectors)))
    feats, eres = eig.kpca_features(C, U, k=c)
    assert np.all(np.isfinite(np.asarray(feats))), "sqrt(-lam) leaked a NaN"
    # transform path (Λ^{-1/2}) must also stay finite on a test column
    k_x = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (n, 2)))
    te = eig.kpca_transform(eres, k_x)
    assert np.all(np.isfinite(np.asarray(te)))


def test_spectral_embedding_streamed_degrees():
    """degrees= must override the model-implied degree vector (exact
    streamed d = K1) and produce unit row norms."""
    X = jax.random.normal(jax.random.PRNGKey(3), (150, 6))
    Kop = PairwiseKernel(X, pw_specs.get_spec("rbf", sigma=1.5))
    ap = spsd.fast_model(Kop, jax.random.PRNGKey(4), c=24, s=48,
                         s_sketch="uniform")
    deg = Kop.matmat(jnp.ones((150, 1), jnp.float32))[:, 0]
    V = eig.spectral_embedding(ap.C, ap.U, 4, degrees=deg)
    assert np.all(np.isfinite(np.asarray(V)))
    np.testing.assert_allclose(np.linalg.norm(np.asarray(V), axis=1), 1.0,
                               atol=1e-4)
    # and it differs from the model-implied-degree route in general
    V0 = eig.spectral_embedding(ap.C, ap.U, 4)
    assert not np.allclose(np.asarray(V), np.asarray(V0), atol=1e-7)


# ---------------------------------------------------------------------------
# calibration: the registry's quantile rule against the paper's eta rule
# ---------------------------------------------------------------------------


def eta_of(K: jnp.ndarray, k: int) -> float:
    ev = jnp.linalg.eigvalsh(K)
    ev2 = jnp.sort(ev ** 2)[::-1]
    return float(jnp.sum(ev2[:k]) / jnp.sum(ev2))


def calibrate_sigma_eta(X: jnp.ndarray, eta_target: float, k: int,
                        lo=0.05, hi=20.0, iters=18) -> float:
    """Binary search sigma so eta(K_sigma) ~ eta_target (paper §6.1).

    The pre-registry rule, kept as the oracle for the calibration parity
    test; it densifies an 800-point sub-kernel.
    """
    Xs = X[: min(X.shape[0], 800)]
    for _ in range(iters):
        mid = (lo + hi) / 2
        K = PairwiseKernel(Xs, pw_specs.get_spec("rbf", sigma=mid)).full()
        e = eta_of(K, max(int(np.ceil(Xs.shape[0] / 100)), k))
        if e > eta_target:
            hi = mid          # kernel too smooth -> lower sigma
        else:
            lo = mid
    return (lo + hi) / 2


def test_calibrate_sigma_parity_with_eta_rule():
    """The registry quantile rule lands in the same bandwidth regime as the
    old spectral-mass binary search at the smoke shape (same order of
    magnitude)."""
    X, _ = make_dataset("letters", seed=0, n=400)
    s_new = float(pw_cal.calibrate_sigma(X, "rbf").param("sigma"))
    s_old = calibrate_sigma_eta(X, 0.9, 3)
    assert 0.4 < s_new / s_old < 2.5, (s_new, s_old)


# ---------------------------------------------------------------------------
# accuracy against dense, one case per application, at smoke shapes
# ---------------------------------------------------------------------------

#: the selection policy the application checks use (the accuracy frontier)
SELECTION = "uniform_adaptive2"


def krr_parity(n=400, d=16, c=48, s=96, nq=64, alpha=1e-2, seed=0) -> float:
    """Streamed build → cached-Woodbury KRR heads → fused cross serving,
    against the dense f64 oracle on held-out queries."""
    X_all, y_all = synth_problem(n + nq, d, seed)
    X, y = X_all[:n], y_all[:n]
    Xq = X_all[n:]
    art = build_artifact(X, y, pw_cal.calibrate_sigma(X, "rbf"), c=c, s=s,
                         alpha=alpha, selection=SELECTION,
                         key=jax.random.PRNGKey(seed))
    res = serve_kernel_model(art, [QueryRequest(Xq, "krr")])
    pred = np.asarray(res[0].out)[:, 0]
    dense = np.asarray(dense_krr_oracle(art, Xq, y))[:, 0]
    return parity_gap(pred, dense)


def attention_rel_err(S=256, D=32, c=32, theta=4, seed=0) -> float:
    """Fast-SPSD attention against exact softmax attention."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (S, D)) * 0.4
    k = jax.random.normal(ks[1], (S, D)) * 0.4
    v = jax.random.normal(ks[2], (S, D))
    exact = jax.nn.softmax((q @ k.T) / np.sqrt(D), axis=-1) @ v
    out = sketched_attention(q, k, v, jax.random.PRNGKey(seed + 1), c=c,
                             theta=theta, mode="fast", selection=SELECTION)
    return float(jnp.linalg.norm(out - exact)) / float(jnp.linalg.norm(exact))


@pytest.mark.slow
@pytest.mark.parametrize("workload", ["kpca", "spectral", "krr",
                                      "attention"])
def test_application_accuracy(workload):
    if workload == "kpca":
        mis = kpca_misalignment(n=400, k=3, c=32, selection=SELECTION)
        assert mis < 0.5, mis
    elif workload == "spectral":
        _, nmi_dense = spectral_nmi(n=400, k=4, c=32, selection=SELECTION)
        assert nmi_dense > 0.2, nmi_dense
    elif workload == "krr":
        gap = krr_parity()
        assert gap < 1e-4, gap
    else:
        err = attention_rel_err()
        assert err < 0.35, err
