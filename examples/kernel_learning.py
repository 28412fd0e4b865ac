"""End-to-end kernel learning with the fast model (paper §6 pipeline).

    PYTHONPATH=src python examples/kernel_learning.py

Train/test split -> fast SPSD approximation of the train kernel -> KPCA
features -> 10-NN classification of held-out points, plus approximate
spectral clustering — the paper's two applications, on one synthetic
dataset, all through the public API.
"""
import jax
import jax.numpy as jnp
import numpy as np
from numpy.random import default_rng

from repro.core import eig, spsd
from repro.core.kernelop import RBFKernel


def make_data(n=1200, d=12, k=6, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)) * 2.0
    labels = rng.integers(0, k, size=n)
    X = centers[labels] + rng.normal(size=(n, d)) * 0.6
    return jnp.asarray(X, jnp.float32), labels


def knn(train_x, train_y, test_x, k=10):
    d = ((np.asarray(test_x)[:, None] - np.asarray(train_x)[None]) ** 2
         ).sum(-1)
    nn = np.argsort(d, 1)[:, :k]
    votes = np.asarray(train_y)[nn]
    return np.asarray([np.bincount(r).argmax() for r in votes])


X, y = make_data()
ntr = X.shape[0] // 2
Xtr, ytr, Xte, yte = X[:ntr], y[:ntr], X[ntr:], y[ntr:]
sigma = 2.0
K = RBFKernel(Xtr, sigma=sigma)

# fast model on the train kernel
c, s, k_feat = 48, 384, 8
ap = spsd.fast_model(K, jax.random.PRNGKey(0), c=c, s=s, s_sketch="uniform")
print(f"fast model err: {float(spsd.relative_error(K, ap)):.4f} "
      f"(c={c}, s={s}, n={ntr})")

# KPCA features + classification
feats, eres = eig.kpca_features(ap.C, ap.U, k_feat)
d2 = (jnp.sum(Xte ** 2, 1)[None] + jnp.sum(Xtr ** 2, 1)[:, None]
      - 2 * Xtr @ Xte.T)
k_test = jnp.exp(-jnp.maximum(d2, 0) / (2 * sigma ** 2))
te_feats = eig.kpca_transform(eres, k_test).T
pred = knn(np.asarray(feats), ytr, np.asarray(te_feats))
print(f"KPCA(+fast) 10-NN test error: {float(np.mean(pred != yte)):.4f}")

# approximate spectral clustering on the full set
Kf = RBFKernel(X, sigma=sigma)
apf = spsd.fast_model(Kf, jax.random.PRNGKey(1), c=c, s=s)
V = eig.spectral_embedding(apf.C, apf.U, 6)
rngk = default_rng(0)
C0 = np.asarray(V)[rngk.choice(len(V), 6, replace=False)]
lab = None
Vn = np.asarray(V)
for _ in range(30):
    dist = ((Vn[:, None] - C0[None]) ** 2).sum(-1)
    lab = dist.argmin(1)
    for j in range(6):
        pts = Vn[lab == j]
        if len(pts):
            C0[j] = pts.mean(0)
def nmi(a, b):
    a, b = np.asarray(a), np.asarray(b)
    n = len(a)
    cont = np.array([[np.sum((a == x) & (b == y)) for y in np.unique(b)]
                     for x in np.unique(a)]) / n
    pi, pj = cont.sum(1, keepdims=True), cont.sum(0, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        mi = np.nansum(cont * np.log(cont / (pi @ pj)))
        ha, hb = -np.nansum(pi * np.log(pi)), -np.nansum(pj * np.log(pj))
    return float(mi / max(np.sqrt(ha * hb), 1e-12))


print(f"spectral clustering (fast model, c={c}): "
      f"NMI vs true labels = {nmi(lab, y):.4f}")
