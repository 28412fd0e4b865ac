"""Pluggable kernel-operator subsystem: every registered KernelSpec through
the shared Pallas sweep template vs its independent dense oracle, the
PairwiseKernel operator protocol, and registry round-trips (including a
user-registered custom kernel riding the full fused machinery)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spsd
from repro.core import sweep as sw
from repro.core.instrument import CountingOperator
from repro.core.kernelop import LinearKernel, PairwiseKernel, RBFKernel
from repro.kernels.pairwise import ops as pw_ops
from repro.kernels.pairwise import ref as pw_ref
from repro.kernels.pairwise import specs

# the shared registry-sweep parameterization (specs.suggested_params keeps
# entries O(1) on unit-scale data; custom kernels get factory defaults)
_spec = specs.suggested_spec


def _points(seed, n, d=8):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, d)), jnp.float32)


def assert_parity(got, ref, tol=1e-5):
    """max|got − ref| ≤ tol · max(1, max|ref|): parity at tol relative to the
    result scale (contractions legitimately reassociate f32 sums, so a plain
    elementwise rtol explodes on near-zero entries of sign-mixed products)."""
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape
    scale = max(1.0, float(np.max(np.abs(ref))) if ref.size else 0.0)
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * scale)


ALL_KERNELS = specs.registered_kernels()


def test_registry_covers_the_paper_suite():
    for name in ("rbf", "laplacian", "matern32", "polynomial", "linear"):
        assert name in ALL_KERNELS
    with pytest.raises(ValueError, match="unknown kernel"):
        specs.get_spec("no-such-kernel")


def test_spec_factories_cache_one_object_per_parameter_set():
    """jit caches key on the spec object, so factories must dedup."""
    assert specs.get_spec("rbf", sigma=2.0) is specs.get_spec("rbf", sigma=2.0)
    assert specs.get_spec("rbf", sigma=2.0) is specs.get_spec("rbf", sigma=2)
    assert specs.get_spec("rbf", sigma=2.0) is not specs.get_spec("rbf",
                                                                  sigma=3.0)


# ---------------------------------------------------------------------------
# the shared Pallas template vs the independent dense oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_KERNELS)
@pytest.mark.parametrize("nr,nc", [(128, 128), (96, 64), (137, 51)])
def test_pairwise_block_vs_ref(name, nr, nc):
    spec = _spec(name)
    X = _points(0, nr)
    Y = _points(1, nc)
    out = pw_ops.kernel_block(spec, X, Y)
    ref = pw_ref.kernel_block(spec, X, Y)
    assert out.shape == (nr, nc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_pairwise_matmat_multi_rows_vs_ref(name):
    """Rectangular row-slab multi-RHS launch (the shard_map fast path)."""
    spec = _spec(name)
    Xc = _points(2, 300)
    Xr = Xc[:70]                               # a row slab of the point set
    rng = np.random.default_rng(3)
    Vs = (jnp.asarray(rng.normal(size=(300, 5)), jnp.float32),
          jnp.asarray(rng.normal(size=(300, 130)), jnp.float32))
    outs = pw_ops.kernel_matmat_multi_rows(spec, Xr, Xc, Vs)
    refs = pw_ref.kernel_matmat_multi_rows(spec, Xr, Xc, Vs)
    assert len(outs) == 2
    for out, ref in zip(outs, refs):
        assert_parity(out, ref)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_dense_fallback_matches_oracle(name):
    """The non-Pallas route (specs.apply) agrees with the independent ref."""
    spec = _spec(name)
    X = _points(4, 90)
    np.testing.assert_allclose(
        np.asarray(pw_ops.kernel_block(spec, X, X, use_pallas=False)),
        np.asarray(pw_ref.kernel_block(spec, X, X)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# PairwiseKernel operator protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_KERNELS)
def test_pairwise_kernel_block_columns_diag(name):
    spec = _spec(name)
    X = _points(5, 120)
    Kp = PairwiseKernel(X, spec, use_pallas=True)
    Kg = PairwiseKernel(X, spec, use_pallas=False)
    Kd = np.asarray(pw_ref.kernel_block(spec, X, X))
    idx = jnp.asarray([0, 7, 63, 119])
    for K in (Kp, Kg):
        np.testing.assert_allclose(np.asarray(K.columns(idx)),
                                   Kd[:, np.asarray(idx)],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(K.block(idx, idx)),
                                   Kd[np.ix_(np.asarray(idx),
                                             np.asarray(idx))],
                                   rtol=1e-5, atol=1e-5)
        # diag shortcut touches no off-diagonal entry but must match them
        np.testing.assert_allclose(np.asarray(K.diag()), np.diagonal(Kd),
                                   rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("name", ALL_KERNELS)
def test_every_kernel_rides_the_fused_sweep(name):
    """fast_model on any registered kernel: ONE fused sweep, finite error —
    the zero-per-call-site promise of the capability protocol."""
    spec = _spec(name)
    rng = np.random.default_rng(6)
    centers = rng.normal(size=(4, 8)) * 1.5           # low-rank-ish structure
    X = jnp.asarray(centers[rng.integers(0, 4, size=150)]
                    + rng.normal(size=(150, 8)) * 0.2, jnp.float32)
    Kc = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
    ap = spsd.fast_model(Kc, jax.random.PRNGKey(0), c=10, s=40,
                         s_sketch="gaussian", streaming=True)
    assert Kc.last_route == "pallas_fused"
    assert Kc.counts["fused_sweeps"] == 1 and Kc.counts["sweeps"] == 1
    err = float(spsd.relative_error(
        PairwiseKernel(X, spec, use_pallas=False), ap, method="dense"))
    assert np.isfinite(err) and 0.0 <= err < 1.0, err


def test_custom_registered_kernel_end_to_end():
    """The docstring integration story: register a spec, get the fused path."""
    name = "cauchy-test"
    if name not in specs.registered_kernels():
        @specs.register_kernel(name)
        def cauchy(gamma: float = 1.0) -> specs.KernelSpec:
            g = float(gamma)
            return specs.KernelSpec(
                name=name, stat="sqdist",
                entry_fn=lambda sq: 1.0 / (1.0 + g * sq),
                params=(("gamma", g),))

    spec = specs.get_spec(name, gamma=0.5)
    X = _points(7, 140)
    Kc = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
    V = jnp.asarray(np.random.default_rng(8).normal(size=(140, 4)),
                    jnp.float32)
    (got,) = Kc.sweep([sw.MatmulPlan(V)])
    assert Kc.last_route == "pallas_fused"
    Kd = 1.0 / (1.0 + 0.5 * np.asarray(
        specs.stat_block("sqdist", X, X)))
    assert_parity(got, Kd @ np.asarray(V))


# ---------------------------------------------------------------------------
# mixed-precision policy through the shared template
# ---------------------------------------------------------------------------

#: f32 at template parity; bf16 tiles within the quantization budget
PREC_TOL = {"f32": 1e-5, "bf16_f32acc": 5e-2}


@pytest.mark.parametrize("name", ALL_KERNELS)
@pytest.mark.parametrize("precision", specs.PRECISIONS)
def test_pairwise_block_precision_vs_oracle(name, precision):
    """Both tile policies against the f32 oracle, Pallas and dense routes —
    and the two routes agree with each other bit-for-policy (both quantize
    identically, so their mutual gap stays at f32 parity)."""
    spec = _spec(name).with_precision(precision)
    X = _points(12, 100)
    Y = _points(13, 90)
    out = pw_ops.kernel_block(spec, X, Y)
    dense = pw_ops.kernel_block(spec, X, Y, use_pallas=False)
    ref = pw_ref.kernel_block(_spec(name), X, Y)
    assert_parity(out, ref, tol=PREC_TOL[precision])
    assert_parity(out, dense)


@pytest.mark.parametrize("precision", specs.PRECISIONS)
def test_fast_model_end_to_end_precision(precision):
    """fast_model_with_error runs the whole fused pipeline under each policy;
    bf16_f32acc may degrade the approximation by at most 5e-2."""
    spec = _spec("rbf").with_precision(precision)
    rng = np.random.default_rng(14)
    centers = rng.normal(size=(4, 8)) * 1.5
    X = jnp.asarray(centers[rng.integers(0, 4, size=150)]
                    + rng.normal(size=(150, 8)) * 0.2, jnp.float32)
    Kc = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
    ap, err = spsd.fast_model_with_error(Kc, jax.random.PRNGKey(1), c=10,
                                         s=40, s_sketch="gaussian", probes=16)
    suffix = "" if precision == "f32" else "+" + precision
    assert Kc.last_route == "pallas_fused" + suffix
    assert np.isfinite(float(err))
    ref_err = float(spsd.relative_error(
        PairwiseKernel(X, _spec("rbf"), use_pallas=False), ap,
        method="dense"))
    assert ref_err < 1.0
    # the bf16 model's true error may exceed the f32 pipeline's by at most
    # the quantization budget (both are ~0.2 at these shapes)
    f32_ap = spsd.fast_model(
        PairwiseKernel(X, _spec("rbf"), use_pallas=True),
        jax.random.PRNGKey(1), c=10, s=40, s_sketch="gaussian")
    f32_err = float(spsd.relative_error(
        PairwiseKernel(X, _spec("rbf"), use_pallas=False), f32_ap,
        method="dense"))
    assert ref_err <= f32_err + 5e-2


# ---------------------------------------------------------------------------
# back-compat constructors
# ---------------------------------------------------------------------------

def test_rbf_kernel_is_thin_pairwise_constructor():
    X = _points(9, 80)
    K = RBFKernel(X, sigma=1.7, use_pallas=True)
    assert isinstance(K, PairwiseKernel)
    assert K.spec is specs.get_spec("rbf", sigma=1.7)
    assert K.sigma == pytest.approx(1.7)
    # pytree round-trip (what vmap/jit do) preserves the spec
    leaves, treedef = jax.tree_util.tree_flatten(K)
    K2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(K2, RBFKernel) and K2.spec is K.spec


def test_linear_kernel_keeps_factored_fast_paths():
    X = _points(10, 80, d=5)
    K = LinearKernel(X)
    assert isinstance(K, PairwiseKernel)
    assert K.spec is specs.get_spec("linear")
    Kd = np.asarray(X @ X.T, np.float32)
    V = jnp.asarray(np.random.default_rng(11).normal(size=(80, 3)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(K.matmat(V)), Kd @ np.asarray(V),
                               rtol=1e-4, atol=1e-4)
    assert float(K.frobenius_norm_sq()) == pytest.approx(
        float((Kd ** 2).sum()), rel=1e-4)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["dense", "pallas"])
@pytest.mark.parametrize("d", [1, 5, 16, 112])
def test_vpu_l1dist_matches_laplacian_reference(use_pallas, d):
    """The VPU l1dist route (masked per-feature reductions, no dynamic lane
    slice) reproduces ``ref.laplacian_block`` on continuous data, where no
    sign-split plan exists — in the dense apply and the Pallas tile body."""
    spec = specs.get_spec("laplacian", gamma=1.0 / d)   # entries ~ e^-1
    X = _points(40 + d, 150, d)
    Y = _points(41 + d, 70, d)
    op = PairwiseKernel(X, spec)
    assert op.l1_route() == "vpu_loop"
    got = pw_ops.kernel_block(spec, X, Y, use_pallas=use_pallas)
    assert_parity(got, pw_ref.laplacian_block(X, Y, spec.param("gamma")))
