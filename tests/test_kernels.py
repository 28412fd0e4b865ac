"""Per-Pallas-kernel shape/dtype sweeps against the pure-jnp ref oracles
(interpret mode on CPU; the kernels themselves target TPU BlockSpecs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.landmark_attention import ops as lm_ops, ref as lm_ref
from repro.kernels.rbf_sketch import ops as rbf_ops, ref as rbf_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D", [
    (1, 4, 4, 128, 128, 64),      # MHA square
    (2, 8, 2, 128, 128, 32),      # GQA 4:1
    (1, 4, 1, 256, 256, 64),      # MQA
    (2, 4, 2, 100, 100, 32),      # non-multiple seq (padding path)
    (1, 2, 2, 1, 256, 64),        # decode: Sq=1 right-aligned
    (1, 4, 2, 64, 256, 32),       # chunked prefill continuation
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_ref(B, Hq, Hkv, Sq, Sk, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = (jax.random.normal(ks[0], (B, Hq, Sq, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, Hkv, Sk, D)) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D)).astype(dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True)
    ref = fa_ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 64, 200])
def test_flash_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 32)) * 0.5
    k = jax.random.normal(ks[1], (1, 2, 256, 32)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    ref = fa_ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


def test_flash_block_shapes():
    """block sizes sweep (VMEM tiling knobs)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 2, 512, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    ref = fa_ref.attention(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 256), (256, 128)]:
        out = fa_ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                     block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# landmark (fast-SPSD) read
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,c,d,dv", [
    (128, 16, 64, 64), (200, 32, 32, 16), (64, 8, 128, 128), (1, 16, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_landmark_read_vs_ref(m, c, d, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    Q = (jax.random.normal(ks[0], (m, d)) * 0.5).astype(dtype)
    kl = (jax.random.normal(ks[1], (c, d)) * 0.5).astype(dtype)
    UV = jax.random.normal(ks[2], (c, dv)).astype(dtype)
    U1 = jnp.abs(jax.random.normal(ks[3], (c,))) + 0.5
    off = jnp.asarray(0.3)
    out = lm_ops.landmark_read(Q, kl, UV, U1, off)
    ref = lm_ref.landmark_read(Q, kl, UV, U1, off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("N,G,c,d", [(3, 8, 16, 32), (2, 4, 32, 64),
                                     (1, 1, 8, 128)])
def test_landmark_step_vs_ref(N, G, c, d):
    """The fold-and-read launch over N factor sets against the per-set
    oracle: factors updated in place, the offset raised by a large key."""
    ks = jax.random.split(jax.random.PRNGKey(8), 8)
    q = jax.random.normal(ks[0], (N, G, d)) * 0.5
    kl = jax.random.normal(ks[1], (N, c, d)) * 0.5
    U = jax.random.normal(ks[2], (N, c, c)) / c
    UV = jax.random.normal(ks[3], (N, c, d))
    U1 = jnp.abs(jax.random.normal(ks[4], (N, c))) + 0.5
    off = jnp.full((N,), 0.3)
    kn = jax.random.normal(ks[5], (N, d)) * jnp.array([0.5, 8.0, 0.5])[:N, None]
    vn = jax.random.normal(ks[6], (N, d))
    out, (uv, u1, o) = lm_ops.landmark_step(q, kl, U, UV, U1, off, kn, vn)
    for i in range(N):
        w_out, (w_uv, w_u1, w_o) = lm_ref.landmark_step(
            q[i], kl[i], U[i], UV[i], U1[i], off[i], kn[i], vn[i])
        for a, b in ((out[i], w_out), (uv[i], w_uv), (u1[i], w_u1),
                     (o[i], w_o)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused RBF sketch blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nr,nc,d", [(128, 128, 16), (96, 64, 8),
                                     (200, 50, 32), (17, 33, 4)])
@pytest.mark.parametrize("sigma", [0.5, 2.0])
def test_rbf_block_vs_ref(nr, nc, d, sigma):
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    X = jax.random.normal(ks[0], (nr, d))
    Y = jax.random.normal(ks[1], (nc, d))
    out = rbf_ops.rbf_block(X, Y, sigma)
    ref = rbf_ref.rbf_block(X, Y, sigma)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)


def test_rbf_block_diag_is_one():
    X = jax.random.normal(jax.random.PRNGKey(5), (64, 8))
    K = rbf_ops.rbf_block(X, X, 1.3)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(K)), 1.0, atol=1e-4)


def test_sketched_gram_vs_ref():
    X = jax.random.normal(jax.random.PRNGKey(6), (150, 12))
    g1 = rbf_ops.sketched_gram(X, 1.1)
    g2 = rbf_ref.sketched_gram(X, 1.1)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4,
                               atol=2e-4)
    # SPSD check
    ev = np.linalg.eigvalsh(np.asarray(g2, np.float64))
    assert ev.min() > -1e-4


def test_rbf_kernel_operator_uses_pallas_path():
    """RBFKernel(use_pallas=True) must agree with the jnp path."""
    from repro.core.kernelop import RBFKernel
    X = jax.random.normal(jax.random.PRNGKey(7), (100, 10))
    idx = jnp.arange(20)
    a = RBFKernel(X, sigma=1.7, use_pallas=False).block(idx, idx + 5)
    b = RBFKernel(X, sigma=1.7, use_pallas=True).block(idx, idx + 5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("n,d,m", [(128, 8, 128), (300, 16, 7), (130, 5, 1),
                                   (256, 32, 200)])
def test_rbf_matmat_vs_ref(n, d, m):
    """Fused streaming K @ V (kernel tiles stay in VMEM) vs dense oracle."""
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    X = jax.random.normal(ks[0], (n, d))
    V = jax.random.normal(ks[1], (n, m))
    out = rbf_ops.rbf_matmat(X, V, 1.3)
    ref = rbf_ref.rbf_matmat(X, V, 1.3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("nr,nc,d", [(128, 256, 8), (67, 533, 6), (40, 40, 4)])
def test_rbf_matmat_multi_rows_vs_ref(nr, nc, d):
    """Rectangular row-slab multi-RHS launch (the shard_map fast path) vs
    the dense oracle: K[r0:r1, :] @ [V...] with one K-tile evaluation."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    Xc = jax.random.normal(ks[0], (nc, d))
    Xr = Xc[:nr]                             # a row slab of the point set
    Vs = (jax.random.normal(ks[1], (nc, 5)),
          jax.random.normal(ks[2], (nc, 130)))
    outs = rbf_ops.rbf_matmat_multi_rows(Xr, Xc, Vs, 1.3)
    refs = rbf_ref.rbf_matmat_multi_rows(Xr, Xc, Vs, 1.3)
    assert len(outs) == 2
    for out, ref in zip(outs, refs):
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_rbf_matmat_multi_square_delegates_to_rows():
    """The square multi-RHS path and the rows path agree exactly."""
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    X = jax.random.normal(ks[0], (150, 8))
    Vs = (jax.random.normal(ks[1], (150, 9)),)
    a = rbf_ops.rbf_matmat_multi(X, Vs, 0.8)
    b = rbf_ops.rbf_matmat_multi_rows(X, X, Vs, 0.8)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_rbf_matmat_vector_rhs_and_operator_wiring():
    from repro.core.kernelop import RBFKernel
    X = jax.random.normal(jax.random.PRNGKey(9), (100, 6))
    v = jax.random.normal(jax.random.PRNGKey(10), (100,))
    out = rbf_ops.rbf_matmat(X, v, 0.9)
    assert out.shape == (100,)
    Kop = RBFKernel(X, sigma=0.9, use_pallas=True)
    np.testing.assert_allclose(np.asarray(Kop.matmat(v[:, None])[:, 0]),
                               np.asarray(out), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(Kop.full() @ v), np.asarray(out),
                               rtol=2e-3, atol=2e-3)
