"""Traffic kind ``certify``: certified fast-SPSD builds, back to back.

Each build is one call of the program's ``spsd.fast_model_with_error`` over a
metered ``PairwiseKernel(use_pallas=True)``: uniform landmarks P, C = K P and
the Hutchinson products K Z from one fused sweep, U = (SᵀC)⁺ SᵀKS (CᵀS)⁺ from
a uniform column sketch S ⊇ P, and the relative error ‖K − CUCᵀ‖²_F/‖K‖²_F
estimated with the probes.  Build b draws its landmarks, sketch and probes
from the key (seed, b).  Set-up compiles the build once, ahead of time, for
the key as an argument, so every build of the window runs that program, and
runs it once on a key of its own, so that the program's first execution
(loading it, allocating its buffers) is set-up too.

The window runs whole builds and closes at the end of the first build that
ends at or after ``--seconds``; ``build_s`` is its length over its builds.
One build, drawn from the seed, is checked afterwards against
``bench.reference`` (see ``check``).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench import reference as ref

#: rcond of the fast U's pseudo-inverse: max(shape)·eps(float32), the
#: numerical rank rule the configurations state
F32_EPS = float(np.finfo(np.float32).eps)


def _sizes(config):
    return (int(config["n"]), int(config["d"]),
            int(config["c"]), int(config["s"]), int(config["probes"]))


def required_work(config: dict, traffic: dict) -> dict:
    """FLOPs and bytes one certified build needs, from its shapes alone.

    Counted as the algorithm's work, whatever route the program takes:
    the O(n²) sweep is the distance cross term and K·Z over all pairs
    (2n²d + 2n²p); C = K P is n·c entries (2ncd), not a gather by matrix
    product; SᵀKS is m² entries (2m²d) for the m = s + c sketch rows; the
    fast U is an SVD of the m × c block SᵀC (4mc² + 22c³, Golub–Van Loan
    R-SVD) and two products (2cm² + 2c²m); the residual C U Cᵀ Z is 4ncp +
    2c²p.  Bytes are what the sweep must read and write at least once: X,
    Z in and K Z out (C is written by the gather, n·c·4 more for the build).
    """
    n, d, c, s, p = _sizes(config)
    m = s + c
    sweep = 2 * n * n * (d + p)
    build = (sweep + 2 * n * c * d + 2 * m * m * d
             + 4 * m * c * c + 22 * c ** 3 + 2 * c * m * m + 2 * c * c * m
             + 4 * n * c * p + 2 * c * c * p)
    sweep_bytes = 4 * n * (d + 2 * p)
    return {"sweep_flops": float(sweep), "sweep_bytes": float(sweep_bytes),
            "build_flops": float(build),
            "build_bytes": float(sweep_bytes + 4 * n * c)}


@dataclasses.dataclass
class State:
    cell: object
    X: jnp.ndarray
    build: object          # the compiled build: (X, key) -> (C, U, idx, err)
    route: str
    sweeps: int
    fused_sweeps: int
    slab_mode: object


def build_key(cell, b: int) -> jax.Array:
    return data.derive(cell.key, 2, b)


def setup(cell, warm: bool = True) -> State:
    """Data on the device, the build compiled and, with ``warm``, run once
    untimed."""
    from repro.core import spsd
    from repro.core.instrument import CountingOperator
    from repro.core.kernelop import PairwiseKernel
    from repro.kernels.pairwise import specs

    cfg = cell.config
    n, d, c, s, p = _sizes(cfg)
    X, _ = data.make_dataset(cfg, data.derive(cell.key, 0), n)
    spec = specs.get_spec(cfg["kernel"], sigma=float(cfg["sigma"]))
    ops = []

    def run(X, key):
        op = CountingOperator(PairwiseKernel(X, spec, use_pallas=True))
        ops.append(op)
        ap, err = spsd.fast_model_with_error(
            op, key, c=c, s=s, s_sketch=cfg["sketch"], probes=p,
            selection=cfg["selection"])
        return ap.C, ap.U, ap.P_indices, err

    compiled = jax.jit(run).lower(X, build_key(cell, 0)).compile()
    op = ops[-1]
    if warm:
        jax.block_until_ready(compiled(X, data.derive(cell.key, 3)))
    return State(cell, X, compiled, op.last_route, op.counts["sweeps"],
                 op.counts["fused_sweeps"], op.last_slab_mode)


def window(state: State, seconds: float) -> dict:
    """Whole builds until one ends at or after ``seconds``.  One build is
    kept for the check, drawn uniformly from the window's builds by
    reservoir sampling seeded from the seed."""
    cell = state.cell
    rng = np.random.default_rng(
        np.random.SeedSequence([cell.seed & 0xFFFFFFFF, cell.seed >> 32, 7]))
    errs, times, failed = [], [], 0
    kept = kept_index = None
    t0 = time.perf_counter()
    b = 0
    while True:
        tb = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.build"):
            out = jax.block_until_ready(state.build(state.X, build_key(cell, b)))
        times.append(time.perf_counter() - tb)
        err = float(out[3])
        errs.append(err)
        failed += not np.isfinite(err)
        if rng.integers(0, b + 1) == 0:
            kept, kept_index = out, b
        del out
        b += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    return {
        "attempted": b, "failed": failed, "window_s": window_s,
        "e2e": {"build_s": window_s / b},
        "counters": {"builds": b, "build_times_s": times,
                     "sweeps_per_build": state.sweeps,
                     "fused_sweeps_per_build": state.fused_sweeps,
                     "route": state.route, "slab_mode": state.slab_mode},
        "lines": [f"route {state.route} slab_mode {state.slab_mode} "
                  f"sweeps/build {state.sweeps} fused {state.fused_sweeps}",
                  "build seconds " + " ".join(repr(t) for t in times),
                  "hutchinson error " + " ".join(repr(e) for e in errs),
                  f"checked build {kept_index}"],
        "sample": (kept_index, kept),
    }


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reference:
    C: jnp.ndarray
    idx: jnp.ndarray
    KZ: jnp.ndarray
    Z: jnp.ndarray
    U: jnp.ndarray


def reference_build(X, cfg: dict, key, precision: str) -> Reference:
    """The build by its definition, from the same key schedule: uniform P
    (and S ⊇ P, unscaled), C = K(X, X_P) entry by entry, K Z row block by
    row block, and U = (SᵀC)⁺ SᵀKS (CᵀS)⁺ in float32 with the
    configuration's rank rule."""
    n, d, c, s, p = _sizes(cfg)
    sigma = float(cfg["sigma"])
    kc, ks = jax.random.split(key)
    kz = jax.random.fold_in(key, 777)
    idx = jax.random.choice(kc, n, shape=(c,), replace=False)
    Z = jax.random.rademacher(kz, (n, p), dtype=jnp.float32)
    C = ref.rbf_rows(X, X[idx], sigma, precision)
    KZ = ref.rbf_matmat(X, X, Z, sigma, precision)
    sidx = jnp.concatenate(
        [idx, jax.random.choice(ks, n, shape=(s,), replace=False)])
    StC = C[sidx]
    StKS = ref.rbf(X[sidx], X[sidx], sigma, precision)
    P = ref.pinv(StC, max(StC.shape) * F32_EPS, precision)
    U = ref.mm(ref.mm(P, StKS, precision), P.T, precision)
    return Reference(C, idx, KZ, Z, U)


def model_action(C, U, Z) -> np.ndarray:
    """C U Cᵀ Z in float64 on the host."""
    C64 = np.asarray(C, np.float64)
    return C64 @ (np.asarray(U, np.float64)
                  @ (C64.T @ np.asarray(Z, np.float64)))


def residual_ratio(KZ64: np.ndarray, A64: np.ndarray) -> float:
    """‖K Z − C U Cᵀ Z‖² / ‖K Z‖², from K Z and the model's action."""
    R = KZ64 - A64
    return float(np.sum(R * R) / np.sum(KZ64 * KZ64))


def compare(out, reference: Reference) -> list:
    """The numbers that decide ``correct`` for one build ``out`` =
    (C, U, idx, err) against ``reference``:

    - ``landmarks``: landmark indices that differ from the key's draw;
    - ``C``: max |C − C_ref| / max(1, max |C_ref|): the column gather;
    - ``model``: ‖C U Cᵀ Z − C_r U_r C_rᵀ Z‖_F / ‖C_r U_r C_rᵀ Z‖_F, the
      model's action on the probes against the reference's: the fast U
      (an ill-conditioned U is compared by what it does, not entry by
      entry);
    - ``probe``: |err − e| / e, e the build's own C, U scored against the
      reference's K Z: the fused probe sweep and the certificate.
    """
    C, U, idx, err = out
    r = reference
    A = model_action(C, U, r.Z)
    A_r = model_action(r.C, r.U, r.Z)
    model = float(np.linalg.norm(A - A_r) / np.linalg.norm(A_r))
    del A_r
    e = residual_ratio(np.asarray(r.KZ, np.float64), A)
    return [
        ("landmarks", float(np.sum(np.asarray(idx) != np.asarray(r.idx)))),
        ("C", ref.max_gap(C, r.C)),
        ("model", model),
        ("probe", abs(float(err) - e) / e),
    ]


def _reference(state: State, win: dict) -> Reference:
    """The reference for the window's checked build, computed once."""
    if "reference" not in win:
        b, _ = win["sample"]
        win["reference"] = reference_build(
            state.X, state.cell.config, build_key(state.cell, b), "highest")
    return win["reference"]


def check(state: State, win: dict) -> list:
    return compare(win["sample"][1], _reference(state, win))


def control(state: State, win: dict) -> list:
    """The reference at the precision below the configuration's, put in
    the program's place (its certificate from its own K Z) and compared
    the same way."""
    b, _ = win["sample"]
    low = reference_build(state.X, state.cell.config,
                          build_key(state.cell, b), "high")
    err = residual_ratio(np.asarray(low.KZ, np.float64),
                         model_action(low.C, low.U, low.Z))
    return compare((low.C, low.U, low.idx, err), _reference(state, win))
