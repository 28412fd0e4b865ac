"""Sign-split l1dist (the MXU segment decomposition) and the scalar-prefetch
slab launch: plan construction, MXU-vs-VPU route equivalence (including
adversarial sign patterns and odd feature counts), and slab-vs-gather launch
parity at every alignment the sharded sweep produces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import sweep as sw
from repro.core.instrument import CountingOperator
from repro.core.kernelop import PairwiseKernel
from repro.kernels.pairwise import ops as pw_ops
from repro.kernels.pairwise import signsplit, specs

multidevice = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _quantized(seed, n, d=8, levels=9, scale=0.5):
    """Points on a small lattice — per-feature cardinality ≤ ``levels``, so
    the sign-split plan is buildable and the decomposition is EXACT."""
    rng = np.random.default_rng(seed)
    v = rng.integers(-(levels // 2), levels // 2 + 1, size=(n, d))
    return jnp.asarray(v * scale, jnp.float32)


def _l1_oracle(X, Y):
    X64 = np.asarray(X, np.float64)
    Y64 = np.asarray(Y, np.float64)
    return np.abs(X64[:, None, :] - Y64[None, :, :]).sum(-1)


def _parity(got, ref, tol=1e-5):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    scale = max(1.0, float(np.max(np.abs(ref))))
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * scale)


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------

def test_build_plan_on_lattice_data():
    X = _quantized(0, 200, d=6, levels=7)
    plan = signsplit.build_plan(X)
    assert plan is not None
    assert plan.edges.shape[0] == 6
    assert 2 <= plan.segments <= signsplit.MAX_SEGMENTS


def test_build_plan_refuses_continuous_data():
    """Cardinality beyond the segment budget -> None (the VPU route)."""
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(100, 4)), jnp.float32)
    assert signsplit.build_plan(X) is None


def test_build_plan_refuses_plans_wider_than_the_slot_budget():
    """Lattice data whose d·B embedding would overflow a tile's VMEM gets no
    plan: the VPU route is the recorded decision, not a fallback."""
    X = _quantized(33, 64, d=300, levels=16)
    assert 300 * 16 > signsplit.MAX_SLOTS
    assert signsplit.build_plan(X) is None
    spec = specs.suggested_spec("laplacian", 300)
    assert PairwiseKernel(X, spec).l1_route() == "vpu_loop"
    narrow = _quantized(33, 64, d=100, levels=16)
    assert signsplit.build_plan(narrow) is not None


def test_build_plan_refuses_tracers():
    X = _quantized(2, 64, d=4)
    seen = []

    @jax.jit
    def f(x):
        seen.append(signsplit.build_plan(x))
        return x

    f(X)
    assert seen == [None]


# ---------------------------------------------------------------------------
# MXU-vs-VPU equivalence: the decomposition is exact on in-plan data
# ---------------------------------------------------------------------------

def test_l1dist_signsplit_matches_oracle_exactly():
    X = _quantized(3, 150, d=8)
    Y = _quantized(4, 90, d=8)
    plan = signsplit.build_plan(jnp.concatenate([X, Y]))
    got = signsplit.l1dist(X, Y, plan.edges)
    _parity(got, _l1_oracle(X, Y))


def test_l1dist_adversarial_signs():
    """Every sign pattern per feature — the decomposition's hard case is
    values straddling zero in both operands."""
    X = jnp.asarray([[-2.0, -0.5, 0.0, 1.5],
                     [2.0, 0.5, -1.0, -1.5],
                     [0.0, 0.0, 1.0, 0.0],
                     [-2.0, 0.5, 1.0, 1.5]], jnp.float32)
    plan = signsplit.build_plan(X)
    got = signsplit.l1dist(X, X, plan.edges)
    np.testing.assert_allclose(np.asarray(got), _l1_oracle(X, X), atol=1e-6)


def test_l1dist_odd_feature_count_and_ragged_cardinality():
    """d=5 (no tile alignment) with a different cardinality per feature —
    the padded +inf edges must not contribute."""
    rng = np.random.default_rng(5)
    cols = [rng.choice(np.linspace(-1.0, 1.0, card), size=120)
            for card in (2, 3, 5, 11, 29)]
    X = jnp.asarray(np.stack(cols, axis=1), jnp.float32)
    plan = signsplit.build_plan(X)
    assert plan is not None and plan.segments <= signsplit.MAX_SEGMENTS
    _parity(signsplit.l1dist(X, X, plan.edges), _l1_oracle(X, X))


def test_l1dist_bf16_within_quantization_budget():
    X = _quantized(6, 128, d=8)
    plan = signsplit.build_plan(X)
    got = signsplit.l1dist(X, X, plan.edges, compute_dtype=jnp.bfloat16)
    _parity(got, _l1_oracle(X, X), tol=5e-2)


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "dense"])
def test_ops_block_mxu_vs_vpu_routes(use_pallas):
    """The same tile template with and without an edge table: the MXU form
    must reproduce the VPU loop to f32 parity on both evaluation routes."""
    spec = specs.suggested_spec("laplacian", 8)
    X = _quantized(7, 140)
    Y = _quantized(8, 70)
    edges = signsplit.build_plan(jnp.concatenate([X, Y])).edges
    mxu = pw_ops.kernel_block(spec, X, Y, use_pallas=use_pallas, edges=edges)
    vpu = pw_ops.kernel_block(spec, X, Y, use_pallas=use_pallas, edges=None)
    _parity(mxu, vpu)


# ---------------------------------------------------------------------------
# operator-level routing
# ---------------------------------------------------------------------------

def test_pairwise_kernel_l1_route_selection():
    spec = specs.suggested_spec("laplacian", 8)
    assert PairwiseKernel(_quantized(9, 100), spec).l1_route() \
        == "mxu_signsplit"
    cont = jnp.asarray(np.random.default_rng(10).normal(size=(100, 8)),
                       jnp.float32)
    assert PairwiseKernel(cont, spec).l1_route() == "vpu_loop"
    rbf = specs.suggested_spec("rbf", 8)
    assert PairwiseKernel(_quantized(9, 100), rbf).l1_route() is None


def test_laplacian_full_parity_across_routes():
    """full() on lattice data (MXU route) vs the dense VPU evaluation."""
    spec = specs.suggested_spec("laplacian", 8)
    X = _quantized(11, 130)
    K_mxu = PairwiseKernel(X, spec, use_pallas=True).full()
    dist = _l1_oracle(X, X)
    gamma = spec.param("gamma")
    np.testing.assert_allclose(np.asarray(K_mxu), np.exp(-gamma * dist),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# query-side routing: cross() takes the MXU form for on-lattice queries
# ---------------------------------------------------------------------------

def _on_lattice_queries(X, m, seed):
    """Out-of-sample rows whose every feature value is drawn from the
    realized per-feature values of ``X`` — on-lattice by construction."""
    rng = np.random.default_rng(seed)
    Xh = np.asarray(X)
    cols = [rng.choice(np.unique(Xh[:, k]), size=m)
            for k in range(Xh.shape[1])]
    return jnp.asarray(np.stack(cols, axis=1), jnp.float32)


def test_query_in_plan_membership():
    X = _quantized(20, 120, d=6)
    assert signsplit.query_in_plan(X, _on_lattice_queries(X, 9, 21))
    cont = np.random.default_rng(22).normal(size=(9, 6)).astype(np.float32)
    assert not signsplit.query_in_plan(X, cont)
    # one off-lattice value in one feature poisons the whole batch
    almost = np.asarray(_on_lattice_queries(X, 9, 23)).copy()
    almost[3, 2] += 1e-3
    assert not signsplit.query_in_plan(X, almost)
    # shape mismatch / non-finite values are conservatively off-plan
    assert not signsplit.query_in_plan(X, np.zeros((4, 5), np.float32))
    bad = np.asarray(_on_lattice_queries(X, 4, 24)).copy()
    bad[0, 0] = np.nan
    assert not signsplit.query_in_plan(X, bad)
    # tracers (jit-abstract queries) are off-plan, never an error
    seen = []

    @jax.jit
    def f(q):
        seen.append(signsplit.query_in_plan(X, q))
        return q

    f(_on_lattice_queries(X, 4, 25))
    assert seen == [False]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_cross_mxu_route_for_on_lattice_queries_is_exact(use_pallas):
    """On-lattice queries route through the sign-split MXU form and must
    reproduce the f64 l1 oracle — the exactness contract that justifies
    the routing."""
    spec = specs.suggested_spec("laplacian", 8)
    X = _quantized(26, 140)
    op = PairwiseKernel(X, spec, use_pallas=use_pallas)
    assert op.l1_edges() is not None
    Xq = _on_lattice_queries(X, 33, 27)
    assert op.l1_route(Xq) == "mxu_signsplit"
    V = jnp.asarray(np.random.default_rng(28).normal(size=(140, 5)),
                    jnp.float32)
    (got,) = op.cross(Xq, (V,))
    assert op._last_cross_l1_route == "mxu_signsplit"
    assert "+mxu_signsplit" in op._last_sweep_route
    gamma = spec.param("gamma")
    ref = np.exp(-gamma * _l1_oracle(Xq, X)) @ np.asarray(V, np.float64)
    _parity(got, ref)


def test_cross_vpu_route_for_off_lattice_queries():
    """Off-lattice queries keep the always-exact VPU loop: no MXU suffix
    on the recorded route, same answer as the oracle."""
    spec = specs.suggested_spec("laplacian", 8)
    X = _quantized(29, 140)
    op = PairwiseKernel(X, spec, use_pallas=False)
    Xq = jnp.asarray(np.random.default_rng(30).normal(size=(17, 8)),
                     jnp.float32)
    assert op.l1_route(Xq) == "vpu_loop"
    V = jnp.asarray(np.random.default_rng(31).normal(size=(140, 3)),
                    jnp.float32)
    (got,) = op.cross(Xq, (V,))
    assert op._last_cross_l1_route == "vpu_loop"
    assert "+mxu_signsplit" not in op._last_sweep_route
    gamma = spec.param("gamma")
    ref = np.exp(-gamma * _l1_oracle(Xq, X)) @ np.asarray(V, np.float64)
    _parity(got, ref)


def test_cross_route_is_none_for_non_l1_stats():
    rbf = specs.suggested_spec("rbf", 8)
    op = PairwiseKernel(_quantized(32, 100), rbf, use_pallas=False)
    Xq = _on_lattice_queries(op.X, 7, 33)
    assert op.l1_route(Xq) is None
    op.cross(Xq, (jnp.ones((100, 2), jnp.float32),))
    assert op._last_cross_l1_route is None
    assert "+mxu_signsplit" not in op._last_sweep_route


# ---------------------------------------------------------------------------
# scalar-prefetch slab launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["rbf", "laplacian"])
@pytest.mark.parametrize("start,slab", [(0, 64), (64, 64), (37, 80),
                                        (250, 64)])
def test_fused_slab_matches_fused_rows(name, start, slab):
    """The prefetch slab launch answers exactly what the gather launch
    answers, at aligned, unaligned, and past-the-end (clamp-duplicate)
    starts — only in-range rows are compared (the sweep masks the rest)."""
    n = 300
    spec = specs.suggested_spec(name, 8)
    X = _quantized(12, n)
    op = PairwiseKernel(X, spec, use_pallas=True)
    assert op.supports_prefetch_slab()
    rng = np.random.default_rng(13)
    Vs = (jnp.asarray(rng.normal(size=(n, 5)), jnp.float32),
          jnp.asarray(rng.normal(size=(n, 17)), jnp.float32))
    got = op.fused_slab(jnp.int32(start), slab, Vs)
    idx = jnp.clip(jnp.arange(start, start + slab), 0, n - 1)
    ref = op.fused_rows(idx, Vs)
    valid = min(slab, n - start)
    for g, r in zip(got, ref):
        _parity(g[:valid], r[:valid])


def test_fused_slab_traced_start():
    """start_row may be a tracer (it is, inside the sharded sweep)."""
    n = 256
    spec = specs.suggested_spec("rbf", 8)
    X = _quantized(14, n)
    op = PairwiseKernel(X, spec, use_pallas=True)
    V = jnp.asarray(np.random.default_rng(15).normal(size=(n, 4)),
                    jnp.float32)

    out = jax.jit(lambda s: op.fused_slab(s, 64, (V,))[0])(jnp.int32(128))
    ref = op.fused_rows(jnp.arange(128, 192), (V,))[0]
    _parity(out, ref)


@multidevice
@pytest.mark.parametrize("precision", specs.PRECISIONS)
def test_sharded_sweep_takes_prefetch_slab_route(precision):
    """The sharded sweep dispatches prefetch slabs (no gathered row copy),
    records the mode, and stays at parity — under both tile policies."""
    n = 259
    spec = specs.suggested_spec("rbf", 8).with_precision(precision)
    X = _quantized(16, n)
    Kc = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
    V = jnp.asarray(np.random.default_rng(17).normal(size=(n, 4)),
                    jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    (got,) = Kc.sweep([sw.MatmulPlan(V)], mesh=mesh)
    suffix = "" if precision == "f32" else "+bf16_f32acc"
    assert Kc.last_route == "pallas_fused_sharded" + suffix
    assert Kc.last_slab_mode == "prefetch"
    ref = PairwiseKernel(X, spec.with_precision("f32"),
                         use_pallas=False).matmat(V)
    _parity(got, ref, tol=1e-5 if precision == "f32" else 5e-2)


@multidevice
def test_sharded_sweep_gather_fallback_for_slabless_operators():
    """Fused-capable operators without the slab capability still sweep
    sharded through the gathered-rows path (and the mode says so)."""
    n = 259
    spec = specs.suggested_spec("rbf", 8)
    X = _quantized(18, n)
    op = PairwiseKernel(X, spec, use_pallas=True)
    op.supports_prefetch_slab = lambda: False
    Kc = CountingOperator(op)
    V = jnp.asarray(np.random.default_rng(19).normal(size=(n, 4)),
                    jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    (got,) = Kc.sweep([sw.MatmulPlan(V)], mesh=mesh)
    assert Kc.last_route == "pallas_fused_sharded"
    assert Kc.last_slab_mode == "gather"
    _parity(got, PairwiseKernel(X, spec, use_pallas=False).matmat(V))
