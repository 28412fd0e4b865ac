"""KernelSpec: the pluggable kernel-operator registry.

The paper's O(n) cost analysis (Table 3 "#Entries") is kernel-agnostic — it
only needs SPSD kernel entries computed on the fly from the data points.  A
``KernelSpec`` captures exactly what varies between kernels so that ONE tiled
Pallas sweep template (``repro.kernels.pairwise.kernel``) serves all of them:

- ``stat``: which pairwise statistic a kernel tile computes from
  the point tiles — ``'sqdist'`` (‖x−y‖₂², MXU cross product + VPU combine),
  ``'dot'`` (xᵀy, pure MXU), or ``'l1dist'`` (‖x−y‖₁: the MXU sign-split
  route of ``repro.kernels.pairwise.signsplit`` when the operator has a
  segment plan for its data, else a VPU accumulation over the feature axis —
  the retained reference route).
- ``entry_fn``: a *pure elementwise* statistic → kernel-entry function (runs
  on the VPU inside the kernel, and verbatim in the dense fallback).
- ``precision``: the mixed-precision tile policy — ``'f32'`` (default) or
  ``'bf16_f32acc'`` (operand tiles quantized to bf16, every contraction and
  elementwise combine accumulated in f32 via ``preferred_element_type``).
  The policy is a spec FIELD so it rides the existing static-argument
  plumbing (jit keys, serve artifacts, registry factories) for free; derive
  variants with ``spec.with_precision("bf16_f32acc")``.

Everything else — tiling, padding, the multi-right-hand-side fusion, the
shard_map row-slab claim, diag shortcuts — is shared machinery.

Registering a custom kernel
---------------------------

Factories are registered by name and return (cached) ``KernelSpec`` objects,
so jit caches key on one spec instance per parameter set::

    from repro.kernels.pairwise import specs

    @specs.register_kernel("cauchy")
    def cauchy(gamma: float = 1.0) -> specs.KernelSpec:
        gamma = float(gamma)
        return specs.KernelSpec(
            name="cauchy",
            stat="sqdist",                            # reuse the MXU distance
            entry_fn=lambda sq: 1.0 / (1.0 + gamma * sq),
            params=(("gamma", gamma),))

    spec = specs.get_spec("cauchy", gamma=0.5)

    from repro.core import PairwiseKernel
    K = PairwiseKernel(X, spec, use_pallas=True)      # full fused-sweep path

That is the whole integration: the operator layer, the sweep-engine routing
(``pallas_fused`` / ``pallas_fused_sharded`` / ``panel``), CUR and eig all
pick the new kernel up through the registry with zero per-call-site
changes.  ``entry_fn`` must be elementwise and produce an SPSD kernel for
the intended statistic — the registry does not check positivity.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.pairwise import signsplit

#: statistics the sweep template knows how to compute from point tiles
STAT_KINDS = ("sqdist", "dot", "l1dist")

#: tile-evaluation precision policies (operand dtype × accumulator dtype)
PRECISIONS = ("f32", "bf16_f32acc")


def tile_dtype(precision: str):
    """Operand dtype of a precision policy (accumulators are always f32)."""
    if precision == "bf16_f32acc":
        return jnp.bfloat16  # repro: allow-dtype(the precision policy's own definition site)
    if precision == "f32":
        return jnp.float32
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def f32_precision(dtype):
    """``Precision.HIGHEST`` for f32 operands, None (one MXU pass) otherwise.

    Every contraction of the ``'f32'`` policy passes this: XLA and Mosaic
    would otherwise contract f32 operands on the TPU in one bf16 pass.
    """
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One SPSD kernel family for the shared pairwise sweep template.

    ``entry_fn`` maps the pairwise statistic elementwise to kernel entries
    (f32 in, f32 out) and must be jax-traceable; it runs unchanged inside the
    Pallas kernel body and in the dense fallback.  ``params`` is a hashable
    ``((name, value), ...)`` tuple recorded for repr/factory caching — specs
    are compared and hashed by field identity, so always build them through
    the registered (cached) factories.
    """

    name: str
    stat: str
    entry_fn: Callable[[jnp.ndarray], jnp.ndarray]
    params: Tuple[Tuple[str, float], ...] = ()
    precision: str = "f32"

    def __post_init__(self):
        if self.stat not in STAT_KINDS:
            raise ValueError(
                f"KernelSpec {self.name!r}: unknown stat {self.stat!r}; "
                f"one of {STAT_KINDS}")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"KernelSpec {self.name!r}: unknown precision "
                f"{self.precision!r}; one of {PRECISIONS}")

    def param(self, name: str):
        return dict(self.params)[name]

    def with_precision(self, precision: str) -> "KernelSpec":
        """This spec under another tile-precision policy (cached — one
        object per (spec, precision), preserving the one-jit-entry-per-
        parameter-set invariant the factories establish)."""
        return _with_precision(self, precision)

    def tile_dtype(self):
        """Operand dtype the tile/dense paths quantize point blocks to."""
        return tile_dtype(self.precision)

    def __repr__(self):  # stable, param-revealing (lambdas repr poorly)
        ps = ", ".join(f"{k}={v}" for k, v in self.params)
        prec = "" if self.precision == "f32" else f", {self.precision}"
        return f"KernelSpec({self.name}({ps}), stat={self.stat}{prec})"


#: (spec, precision) -> variant.  A manual cache (not lru_cache) so the
#: round-trip can be seeded: X.with_precision(p).with_precision(q) must land
#: on the SAME object as X.with_precision(q) — including q == X.precision,
#: where it must be X itself — or the jit caches fork per route.
_PRECISION_VARIANTS: dict = {}


def _with_precision(spec: KernelSpec, precision: str) -> KernelSpec:
    if precision == spec.precision:
        return spec
    key = (spec, precision)
    hit = _PRECISION_VARIANTS.get(key)
    if hit is None:
        hit = dataclasses.replace(spec, precision=precision)
        _PRECISION_VARIANTS[key] = hit
        _PRECISION_VARIANTS[(hit, spec.precision)] = spec
    return hit


# ---------------------------------------------------------------------------
# dense statistic + entry evaluation (the non-Pallas route / diag shortcut)
# ---------------------------------------------------------------------------

_DOT_DN = (((1,), (1,)), ((), ()))


def dot_f32acc(Xr: jnp.ndarray, Xc: jnp.ndarray) -> jnp.ndarray:
    """Xr @ Xc.T with an f32 accumulator regardless of operand dtype — the
    one contraction primitive every tile/dense statistic routes through, so
    the bf16_f32acc policy means the same thing everywhere (bf16 operands on
    the MXU, ``preferred_element_type=f32`` partial sums).  f32 operands
    contract at ``Precision.HIGHEST``: on a TPU the default f32 matmul is a
    single bf16 pass, so the ``'f32'`` policy would otherwise not be f32."""
    return jax.lax.dot_general(Xr, Xc, dimension_numbers=_DOT_DN,
                               precision=f32_precision(Xr.dtype),
                               preferred_element_type=jnp.float32)


def _sqdist(Xr: jnp.ndarray, Xc: jnp.ndarray) -> jnp.ndarray:
    """Pairwise squared distances, MXU-friendly: |x|² + |y|² − 2 x·y.

    Operands may be bf16 (the precision policy's quantization); the norms
    and the combine run in f32 on the quantized values so dense and tile
    routes stay bit-comparable per policy.
    """
    Xr32 = Xr.astype(jnp.float32)
    Xc32 = Xc.astype(jnp.float32)
    xx = jnp.sum(Xr32 * Xr32, axis=1)
    yy = jnp.sum(Xc32 * Xc32, axis=1)
    cross = dot_f32acc(Xr, Xc)
    return jnp.maximum(xx[:, None] + yy[None, :] - 2.0 * cross, 0.0)


def _l1dist(Xr: jnp.ndarray, Xc: jnp.ndarray) -> jnp.ndarray:
    """Pairwise L1 distances accumulated one feature at a time — the VPU
    reference route.

    The MXU default for fused launches is the sign-split decomposition
    (``signsplit.l1dist``), which needs a data-derived segment plan; this
    loop is the plan-free route (continuous/high-cardinality features,
    traced inputs) and the parity oracle the MXU route is asserted against.
    Looping the feature axis keeps the live set at one (nr, nc) f32
    accumulator regardless of d.  Feature k is picked out with masked
    reductions (Xr column k as (nr, 1), Xcᵀ row k as (1, nc)) rather than a
    dynamic slice, which Mosaic cannot lower; each is exact (one nonzero
    term per sum).
    """
    Xr = Xr.astype(jnp.float32)
    XcT = Xc.astype(jnp.float32).T
    nr, d = Xr.shape
    nc = XcT.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)

    def body(k, acc):
        xr = jnp.sum(jnp.where(lane == k, Xr, 0.0), axis=1, keepdims=True)
        xc = jnp.sum(jnp.where(sublane == k, XcT, 0.0), axis=0,
                     keepdims=True)
        return acc + jnp.abs(xr - xc)

    return jax.lax.fori_loop(0, d, body, jnp.zeros((nr, nc), jnp.float32))


def stat_block(stat: str, Xr: jnp.ndarray, Xc: jnp.ndarray,
               precision: str = "f32",
               bounds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The (|Xr| × |Xc|) pairwise statistic (f32 out).

    ``precision`` quantizes the point operands (``tile_dtype``) while every
    accumulator stays f32.  ``bounds`` — a sign-split slot table
    (``signsplit.slot_bounds``) — selects the MXU route for ``l1dist``;
    without it the VPU reference loop runs.  Other statistics ignore it.
    """
    dt = tile_dtype(precision)
    Xr = Xr.astype(dt)
    Xc = Xc.astype(dt)
    if stat == "dot":
        return dot_f32acc(Xr, Xc)
    if stat == "sqdist":
        return _sqdist(Xr, Xc)
    if stat == "l1dist":
        if bounds is not None:
            return signsplit.l1dist_slots(Xr, Xc, bounds, dt)
        return _l1dist(Xr, Xc)
    raise ValueError(f"unknown stat {stat!r}")


def apply(spec: KernelSpec, Xr: jnp.ndarray, Xc: jnp.ndarray,
          edges: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """K[ri, cj] = entry_fn(stat(x_ri, x_cj)) — the dense evaluation every
    non-Pallas route (panel scans, ``full()``) runs.  Precision follows the
    spec; ``edges`` opts l1dist statistics into the MXU sign-split form."""
    bounds = None if edges is None else signsplit.slot_bounds(edges)
    return spec.entry_fn(
        stat_block(spec.stat, Xr, Xc, spec.precision, bounds))


def diag(spec: KernelSpec, X: jnp.ndarray) -> jnp.ndarray:
    """diag(K) in O(n·d) without touching any off-diagonal entry.

    Distance statistics vanish on the diagonal (stat ≡ 0 → a constant
    entry, e.g. 1.0 for rbf/laplacian/matern); the dot statistic reduces to
    the row norms ‖x_i‖² (computed on precision-quantized values so the
    diagonal matches what a fused sweep would produce under the policy).
    """
    X32 = X.astype(spec.tile_dtype()).astype(jnp.float32)
    if spec.stat == "dot":
        t = jnp.sum(X32 * X32, axis=1)
    else:
        t = jnp.zeros((X.shape[0],), jnp.float32)
    return spec.entry_fn(t)


@functools.lru_cache(maxsize=None)
def _stat_only(stat: str) -> KernelSpec:
    return KernelSpec(f"stat[{stat}]", stat, lambda t: t)


def stat_only(spec) -> KernelSpec:
    """Identity-entry spec over ``spec``'s pairwise statistic.

    The resulting kernel's entries ARE the raw statistic (‖x−y‖², xᵀy, or
    ‖x−y‖₁), so the whole operator/sweep machinery — including the fused
    Pallas template — can stream statistic panels; per-spec bandwidth
    calibration (``repro.kernels.pairwise.calibrate``) quantiles them in one
    sweep.  ``spec`` may be a ``KernelSpec`` or a bare stat name.  Cached, so
    each statistic costs one jit entry.
    """
    return _stat_only(spec if isinstance(spec, str) else spec.stat)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., KernelSpec]] = {}


def register_kernel(name: str):
    """Decorator: register a ``KernelSpec`` factory under ``name``."""
    def deco(factory: Callable[..., KernelSpec]):
        _REGISTRY[name] = factory
        return factory
    return deco


def get_spec(name: str, **params) -> KernelSpec:
    """Build the named spec (default parameters unless overridden)."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown kernel {name!r}; registered: "
                         f"{registered_kernels()}")
    return _REGISTRY[name](**params)


def registered_kernels() -> Tuple[str, ...]:
    """Registered kernel names, sorted (the test sweep order)."""
    return tuple(sorted(_REGISTRY))


# Parameterizations that keep entries O(1) on standardized/unit-scale data —
# the single source the registry-sweeping parity and conformance tests share
# (polynomial is normalized by 1/d, the sklearn convention).  Kernels not
# listed (user-registered specs) fall back to their factory defaults, so a
# custom registration never breaks the registry sweeps.
_SUGGESTED_PARAMS = {
    "rbf": lambda d: dict(sigma=1.5),
    "laplacian": lambda d: dict(gamma=0.3),
    "matern32": lambda d: dict(length_scale=1.5),
    "polynomial": lambda d: dict(degree=3, gamma=1.0 / d, coef0=1.0),
    "linear": lambda d: {},
}


def suggested_params(name: str, d: int = 8) -> dict:
    """Benchmark/test parameters for ``name`` given feature dim ``d``
    (``{}`` — factory defaults — for kernels without an entry)."""
    fn = _SUGGESTED_PARAMS.get(name)
    return fn(d) if fn is not None else {}


def suggested_spec(name: str, d: int = 8) -> KernelSpec:
    """``get_spec`` with the suggested benchmark/test parameters."""
    return get_spec(name, **suggested_params(name, d))


# ---------------------------------------------------------------------------
# built-in specs (cached: one spec object — hence one jit cache entry — per
# parameter set)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rbf(sigma: float) -> KernelSpec:
    gamma = 1.0 / (2.0 * sigma ** 2)
    return KernelSpec("rbf", "sqdist",
                      lambda sq: jnp.exp(-gamma * sq),
                      params=(("sigma", sigma),))


@register_kernel("rbf")
def rbf(sigma: float = 1.0) -> KernelSpec:
    """K_ij = exp(−‖x_i − x_j‖² / (2σ²))."""
    return _rbf(float(sigma))


@functools.lru_cache(maxsize=None)
def _laplacian(gamma: float) -> KernelSpec:
    return KernelSpec("laplacian", "l1dist",
                      lambda t: jnp.exp(-gamma * t),
                      params=(("gamma", gamma),))


@register_kernel("laplacian")
def laplacian(gamma: float = 1.0) -> KernelSpec:
    """K_ij = exp(−γ ‖x_i − x_j‖₁) (the exponential/L1 kernel of the
    Gittens–Mahoney Nyström evaluation suite)."""
    return _laplacian(float(gamma))


@functools.lru_cache(maxsize=None)
def _matern32(length_scale: float) -> KernelSpec:
    a = 3.0 ** 0.5 / length_scale

    def entry(sq):
        r = jnp.sqrt(jnp.maximum(sq, 0.0))
        return (1.0 + a * r) * jnp.exp(-a * r)

    return KernelSpec("matern32", "sqdist", entry,
                      params=(("length_scale", length_scale),))


@register_kernel("matern32")
def matern32(length_scale: float = 1.0) -> KernelSpec:
    """Matérn-3/2: K_ij = (1 + √3 r/ℓ) exp(−√3 r/ℓ), r = ‖x_i − x_j‖₂."""
    return _matern32(float(length_scale))


@functools.lru_cache(maxsize=None)
def _polynomial(degree: int, gamma: Optional[float],
                coef0: float) -> KernelSpec:
    def entry(t):
        g = gamma if gamma is not None else 1.0
        return (g * t + coef0) ** degree

    return KernelSpec("polynomial", "dot", entry,
                      params=(("degree", degree), ("gamma", gamma),
                              ("coef0", coef0)))


@register_kernel("polynomial")
def polynomial(degree: int = 3, gamma: Optional[float] = None,
               coef0: float = 1.0) -> KernelSpec:
    """K_ij = (γ xᵢᵀxⱼ + c)ᵖ — SPSD for integer p ≥ 1, γ > 0, c ≥ 0.

    ``gamma=None`` means 1.0 (pass e.g. ``1/d`` to keep entries O(1) on
    standardized data, the sklearn convention).
    """
    return _polynomial(int(degree), None if gamma is None else float(gamma),
                       float(coef0))


@functools.lru_cache(maxsize=None)
def _linear() -> KernelSpec:
    return KernelSpec("linear", "dot", lambda t: t)


@register_kernel("linear")
def linear() -> KernelSpec:
    """K = X Xᵀ — the identity entry function over the dot statistic."""
    return _linear()
