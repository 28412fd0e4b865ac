"""Column gathers inside the fused sweep launch.

A ``ColumnGatherPlan`` riding a fused bundle is computed in the same Pallas
launch as the bundle's products, from the selected (landmark) points: the
launch returns C = K(X, X[idx]) first, with the statistic, ``entry_fn`` and
precision policy of ``columns``, and the products unchanged.  Checked here
for every plan order, for gathers narrower than, equal to and wider than a
tile, for every statistic route, under both precision policies, on the
square launch and on the scalar-prefetch slab launch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sweep as sw
from repro.core.instrument import CountingOperator
from repro.core.kernelop import PairwiseKernel
from repro.kernels.pairwise import specs

N = 200                  # pads to 256 rows; three gathers of 130 pad to 384
SLAB_START, SLAB_LEN = 37, 100

ORDERS = {
    "gather-matmul": ("g", "m"),
    "matmul-gather": ("m", "g"),
    "gather-gather-matmul": ("g", "g", "m"),
}
WIDTHS = (3, 128, 130)


def _points(kernel, seed):
    """Continuous points, or points on a small lattice for the sign-split
    l1 route (so its segment plan exists)."""
    rng = np.random.default_rng(seed)
    if kernel == "laplacian-signsplit":
        return jnp.asarray(rng.integers(-4, 5, size=(N, 6)) * 0.5,
                           jnp.float32)
    return jnp.asarray(rng.normal(size=(N, 6)), jnp.float32)


def _operator(kernel, precision, seed):
    name = "laplacian" if kernel.startswith("laplacian") else kernel
    spec = specs.suggested_spec(name, 6).with_precision(precision)
    op = PairwiseKernel(_points(kernel, seed), spec, use_pallas=True)
    route = {"laplacian-signsplit": "mxu_signsplit",
             "laplacian-vpu": "vpu_loop"}.get(kernel)
    assert op.l1_route() == route
    return op


def _plans(order, c, seed):
    keys = jax.random.split(jax.random.key(seed), len(order))
    return [sw.ColumnGatherPlan(jax.random.choice(k, N, (c,), replace=False))
            if kind == "g" else
            sw.MatmulPlan(jax.random.normal(k, (N, 7), jnp.float32))
            for kind, k in zip(order, keys)]


def _close(got, ref, tol=1e-6):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    scale = max(1.0, float(np.max(np.abs(ref))))
    assert float(np.max(np.abs(got - ref))) <= tol * scale


def _fused(op, plans, launch):
    """The plans' results from one fused launch, in plan order, and the
    rows they cover."""
    if launch == "square":
        return op.sweep(plans), slice(None)
    Vs, col_idx = sw.fused_right_hand_sides(plans)
    outs = op.fused_slab(jnp.int32(SLAB_START), SLAB_LEN, Vs, col_idx)
    return (sw._in_plan_order(plans, outs),
            slice(SLAB_START, SLAB_START + SLAB_LEN))


def _products_alone(op, plans, launch):
    """The same products from a launch without a gather."""
    mats = [p for p in plans if isinstance(p, sw.MatmulPlan)]
    if launch == "square":
        return op.sweep(mats)
    Vs, _ = sw.fused_right_hand_sides(mats)
    return op.fused_slab(jnp.int32(SLAB_START), SLAB_LEN, Vs)


def _cases():
    """Every statistic route × precision × launch, with the plan orders and
    gather widths spread across them so that each (order, width) pair
    appears at least once."""
    out, k = [], 0
    for kernel in ("rbf", "linear", "laplacian-signsplit", "laplacian-vpu"):
        for precision in specs.PRECISIONS:
            for launch in ("square", "slab"):
                order = list(ORDERS)[k % 3]
                c = WIDTHS[(k // 3) % 3]
                out.append(pytest.param(
                    kernel, precision, launch, order, c,
                    id=f"{kernel}-{precision}-{launch}-{order}-c{c}"))
                k += 1
    return out


@pytest.mark.parametrize("kernel,precision,launch,order,c", _cases())
def test_fused_gather_matches_columns(kernel, precision, launch, order, c):
    op = _operator(kernel, precision, seed=len(order) + c)
    plans = _plans(ORDERS[order], c, seed=c)
    got, rows = _fused(op, plans, launch)
    assert len(got) == len(plans)
    for p, g in zip(plans, got):
        if isinstance(p, sw.ColumnGatherPlan):
            _close(g, op.columns(p.col_idx)[rows])
    products = [g for p, g in zip(plans, got) if isinstance(p, sw.MatmulPlan)]
    for g, r in zip(products, _products_alone(op, plans, launch)):
        _close(g, r)


@pytest.mark.parametrize("launch", ["square", "slab"])
@pytest.mark.parametrize("c", WIDTHS)
@pytest.mark.parametrize("order", list(ORDERS))
def test_fused_sweep_gathers_in_plan_order(order, c, launch):
    """Every plan order and gather width, against the dense kernel: one
    fused sweep (the square launch, metered) with no separate column call,
    every result in plan order."""
    op = CountingOperator(_operator("rbf", "f32", seed=c))
    plans = _plans(ORDERS[order], c, seed=c + 1)
    got, rows = _fused(op.inner if launch == "slab" else op, plans, launch)
    if launch == "square":
        assert op.last_route == "pallas_fused"
        assert op.counts["fused_sweeps"] == 1 and op.counts["columns"] == 0
    K = op.inner.full()[rows]
    for p, g in zip(plans, got):
        ref = (K[:, p.col_idx] if isinstance(p, sw.ColumnGatherPlan)
               else K @ p.V)
        _close(g, ref, tol=1e-5)


def test_gather_only_bundle_is_one_column_call():
    """A bundle of gathers alone needs no O(n²) sweep: it is answered by the
    operator's column gather."""
    op = _operator("rbf", "f32", seed=5)
    plans = _plans(("g", "g"), 3, seed=6)
    got = op.sweep(plans)
    assert op._last_sweep_route == "pallas_fused"
    for p, g in zip(plans, got):
        _close(g, op.columns(p.col_idx))
