"""Jaxpr abstract-interpretation checks: the dynamic contracts, statically.

``jax.make_jaxpr`` runs every public entry point *abstractly* — no kernel
entry is ever evaluated — while a :class:`CountingOperator` wrapped around
the smoke operator bumps its Python-side meters at trace time.  That one
trace yields three static verdicts:

RPRJ01 *densify detector* — walk the closed jaxpr (recursing into pjit /
    scan / cond / pallas_call sub-jaxprs) and fail if any intermediate
    value is Θ(n²) for the operator's n.  The streaming claim of
    arXiv:1503.08395 holds iff no trace ever materializes the kernel.
RPRJ02 *sweep-budget verifier* — the trace-time counters must equal each
    ``SelectionPolicy.sweep_budget()`` declaration and the documented
    pipeline contracts (``fast_model`` = 1 + budget, ``fast_cur`` =
    1 + 2·budget, ``serve_kernel_model`` = one cross launch per bucket).
    Registered policies are discovered from the registry, so a new policy
    is checked the moment it registers.
RPRJ03 *accumulation-precision scan* — under the ``bf16_f32acc`` policy
    every ``dot_general`` with a low-precision operand must emit an f32
    result (i.e. carry ``preferred_element_type=f32``); scanned for every
    registered kernel spec.

Entry points traced: ``fast_model`` (every registered policy),
``fast_model_with_error``, ``fast_cur`` (every registered policy), each
policy's ``select`` (plus a GROWING-operator variant for every policy with
a nonzero sweep budget — the incremental-append invariant), and
``serve_kernel_model`` over a small built artifact.  The incremental
``append_rows`` absorb is checked concretely (its refresh algebra is
host-side f64 numpy by design): one ``append_sweeps`` tick, exactly b·c
entries, zero panel/full/cross launches.  Smoke shapes are tiny — tracing
costs seconds, not sweeps.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.core import cur as cur_lib
from repro.core import selection as selection_lib
from repro.core import spsd
from repro.core.instrument import CountingOperator
from repro.core.kernelop import PairwiseKernel
from repro.kernels.pairwise import specs as pw_specs

# smoke shape: big enough that Θ(n²) separates from Θ(n·c), Θ(128·n)
# padded-sketch slabs, and the launch template's VMEM tiles (128 × 128 at
# this n: a sweep tile side is at most a quarter of its axis) — n²/2 must
# exceed all three — yet small enough that tracing is instant.  n=512 puts
# the threshold at 131072 elements vs 65536 for the largest legitimate slab
# (a right-hand side padded to 128 lanes).
SMOKE_N = 512
SMOKE_D = 4
SMOKE_C = 12
SMOKE_S = 24
SMOKE_BLOCK = 64          # keeps legitimate row panels (64 × n) thin
DENSIFY_FRACTION = 0.5    # an aval ≥ n²/2 elements counts as densified

_LOW_PRECISION = (jnp.bfloat16, jnp.float16)


def _smoke_points(n: int = SMOKE_N, d: int = SMOKE_D, seed: int = 0,
                  lattice: bool = False) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    if lattice:  # small per-feature cardinality -> sign-split MXU route
        X = rng.integers(0, 5, size=(n, d)).astype(np.float32)
    else:
        X = rng.standard_normal((n, d)).astype(np.float32)
    return jnp.asarray(X)


def smoke_operator(spec_name: str = "rbf", precision: str = "f32",
                   n: int = SMOKE_N, d: int = SMOKE_D,
                   use_pallas: bool = True) -> CountingOperator:
    """A counting-wrapped PairwiseKernel at the smoke shape."""
    lattice = spec_name == "laplacian"
    X = _smoke_points(n=n, d=d, lattice=lattice)
    params = pw_specs.suggested_params(spec_name, d)
    spec = pw_specs.get_spec(spec_name, **params).with_precision(precision)
    return CountingOperator(PairwiseKernel(X, spec, use_pallas))


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _subjaxprs(params: dict):
    """Yield every Jaxpr hiding in an eqn's params (pjit/scan/cond/pallas)."""
    def visit(val):
        if hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
            yield val.jaxpr             # ClosedJaxpr
        elif hasattr(val, "eqns"):
            yield val                   # bare Jaxpr
        elif isinstance(val, (tuple, list)):
            for item in val:
                yield from visit(item)
        elif isinstance(val, dict):
            for item in val.values():
                yield from visit(item)
    for val in params.values():
        yield from visit(val)


def iter_eqns(closed):
    """Every eqn in a (closed) jaxpr, recursing into sub-jaxprs."""
    jaxpr = getattr(closed, "jaxpr", closed)
    stack = [jaxpr]
    seen = set()
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        for eqn in jx.eqns:
            yield eqn
            stack.extend(_subjaxprs(eqn.params))


def _aval_of(var):
    return getattr(var, "aval", None)


def scan_densify(closed, n: int, entry: str) -> List[Finding]:
    """RPRJ01: any intermediate with ≥ DENSIFY_FRACTION·n² elements."""
    threshold = max(1, int(n * n * DENSIFY_FRACTION))
    findings: List[Finding] = []
    reported = set()
    for eqn in iter_eqns(closed):
        for var in list(eqn.outvars) + list(eqn.invars):
            aval = _aval_of(var)
            shape = getattr(aval, "shape", None)
            if not shape:
                continue
            size = int(np.prod([int(s) for s in shape]))
            if size < threshold:
                continue
            sig = (eqn.primitive.name, tuple(int(s) for s in shape))
            if sig in reported:
                continue
            reported.add(sig)
            findings.append(Finding(
                path=f"jaxpr:{entry}", line=0, rule="RPRJ01",
                message=(f"Θ(n²) intermediate {tuple(shape)} "
                         f"({size} elems ≥ {threshold}) at primitive "
                         f"'{eqn.primitive.name}' — a streaming entry point "
                         f"materialized the operator (n={n})"),
                snippet=f"{eqn.primitive.name}{tuple(shape)}"))
    return findings


def scan_contractions(closed, entry: str) -> List[Finding]:
    """RPRJ03: dot_general with a low-precision operand must emit f32."""
    findings: List[Finding] = []
    reported = set()
    for eqn in iter_eqns(closed):
        if eqn.primitive.name != "dot_general":
            continue
        in_dts = [getattr(_aval_of(v), "dtype", None) for v in eqn.invars]
        out_dts = [getattr(_aval_of(v), "dtype", None) for v in eqn.outvars]
        if not any(dt in _LOW_PRECISION for dt in in_dts):
            continue
        if all(dt == jnp.float32 for dt in out_dts if dt is not None):
            continue
        sig = (tuple(str(d) for d in in_dts), tuple(str(d) for d in out_dts))
        if sig in reported:
            continue
        reported.add(sig)
        findings.append(Finding(
            path=f"jaxpr:{entry}", line=0, rule="RPRJ03",
            message=(f"dot_general accumulates {in_dts} -> {out_dts} under "
                     "a low-precision tile policy — pass "
                     "preferred_element_type=jnp.float32 (specs.dot_f32acc)"),
            snippet=f"dot_general{sig}"))
    return findings


def _check_counts(entry: str, counts: Dict[str, int],
                  expected: Dict[str, int]) -> List[Finding]:
    """RPRJ02: trace-time meters vs declared budgets."""
    findings = []
    for key, want in expected.items():
        got = counts.get(key, 0)
        if got != want:
            findings.append(Finding(
                path=f"jaxpr:{entry}", line=0, rule="RPRJ02",
                message=(f"declared budget says {key}={want} but the "
                         f"abstract trace metered {key}={got} — the "
                         "declaration and the implementation disagree"),
                snippet=f"{entry}:{key}={got}!={want}"))
    return findings


def _trace(entry: str, fn: Callable, *args) -> Tuple[Optional[object],
                                                     List[Finding]]:
    """make_jaxpr(fn)(*args); a raised exception is itself a finding."""
    try:
        return jax.make_jaxpr(fn)(*args), []
    except Exception as exc:  # noqa: BLE001 — any trace failure is a gate failure
        return None, [Finding(
            path=f"jaxpr:{entry}", line=0, rule="RPRJ02",
            message=f"entry point failed to trace abstractly: {exc!r}",
            snippet=f"{entry}:trace-error")]


def _entry_report(entry: str, counts: Dict[str, int],
                  expected: Dict[str, int],
                  findings: Sequence[Finding]) -> dict:
    return {"entry": entry, "counts": dict(counts),
            "expected": dict(expected),
            "ok": not findings}


# ---------------------------------------------------------------------------
# entry-point checks (each returns (findings, report))
# ---------------------------------------------------------------------------

def check_policy_select(policy_name: str,
                        op: Optional[CountingOperator] = None,
                        ) -> Tuple[List[Finding], dict]:
    """policy.select == sweep_budget() sweeps, gathers as declared, 0 fulls."""
    pol = selection_lib.get_policy(policy_name)
    opc = op if op is not None else smoke_operator()
    opc.reset()
    entry = f"select[{policy_name}]"
    closed, findings = _trace(
        entry,
        lambda key: pol.select(opc, key, SMOKE_C, block_size=SMOKE_BLOCK),
        jax.random.PRNGKey(0))
    expected = {"sweeps": pol.sweep_budget(), "columns": pol.gathers,
                "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, opc.n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


class _GrowingOperator(CountingOperator):
    """A CountingOperator whose corpus GROWS after every panel sweep — the
    trace-time model of the incremental maintainer rebinding the live
    operator between adaptive selection rounds (appended rows arriving
    while ``select`` runs).  The meters are cumulative across the growth
    (``rebind`` keeps them), so budget declarations stay assertable."""

    def __init__(self, X_full: jnp.ndarray, spec, n0: int, grow: int,
                 use_pallas: bool = True):
        self._X_full = X_full
        self._spec = spec
        self._grow = grow
        self._use_pallas = use_pallas
        self._live_n = n0
        super().__init__(PairwiseKernel(X_full[:n0], spec, use_pallas))

    def sweep(self, plans, block_size=None, mesh=None):
        out = super().sweep(plans, block_size=block_size, mesh=mesh)
        nxt = min(self._live_n + self._grow, int(self._X_full.shape[0]))
        if nxt != self._live_n:
            self._live_n = nxt
            self.rebind(PairwiseKernel(self._X_full[:nxt], self._spec,
                                       self._use_pallas))
        return out


def check_policy_select_grown(policy_name: str,
                              grow: int = SMOKE_BLOCK,
                              ) -> Tuple[List[Finding], dict]:
    """Adaptive selection over a GROWING operator: budgets still exact.

    The incremental append-row path can grow an operator's n between a
    policy's adaptive rounds; a policy that sizes per-round masks from an
    n captured at entry either hides the appended rows from the draw or
    fails to broadcast against the grown round's statistics (the latter
    surfaces here as a trace failure → RPRJ02).  The declared sweep/gather
    budget must hold unchanged — growth adds rows, never kernel passes.
    """
    pol = selection_lib.get_policy(policy_name)
    params = pw_specs.suggested_params("rbf", SMOKE_D)
    spec = pw_specs.get_spec("rbf", **params)
    X_full = _smoke_points(n=SMOKE_N + pol.sweep_budget() * grow, d=SMOKE_D)
    opc = _GrowingOperator(X_full, spec, n0=SMOKE_N, grow=grow)
    entry = f"select_grown[{policy_name}]"
    closed, findings = _trace(
        entry,
        lambda key: pol.select(opc, key, SMOKE_C, block_size=SMOKE_BLOCK),
        jax.random.PRNGKey(0))
    expected = {"sweeps": pol.sweep_budget(), "columns": pol.gathers,
                "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        if pol.sweep_budget() > 0 and opc._live_n <= SMOKE_N:
            findings.append(Finding(
                path=f"jaxpr:{entry}", line=0, rule="RPRJ02",
                message=("growth harness did not grow the operator — the "
                         "grown-selection invariant was checked vacuously"),
                snippet=f"{entry}:no-growth"))
        findings += scan_densify(closed, opc._live_n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def check_append(batch_rows: int = 16) -> Tuple[List[Finding], dict]:
    """Incremental absorb: ONE thin metered launch of exactly b·c entries.

    Runs CONCRETELY, not under ``make_jaxpr`` — the refresh algebra is
    host-side f64 numpy by design (it mirrors ``build_artifact``'s
    accuracy contract), so the abstract tracer would reject it.  The
    RPRJ02 budget verdict is the same: the ``CountingOperator`` meters are
    bumped identically either way, and O(b·c) is asserted via the exact
    ``entries`` count (zero panel sweeps, zero fulls, zero query crosses).
    """
    from repro.serve.artifact import build_artifact
    from repro.serve.incremental import append_rows, init_state

    n, d, c, s = SMOKE_N, 6, 12, 24
    X = _smoke_points(n=n, d=d, seed=7)
    y = jnp.asarray(np.random.default_rng(8).standard_normal(n), jnp.float32)
    spec = pw_specs.get_spec("rbf", sigma=1.5)
    entry = "append_rows"
    expected = {"append_sweeps": 1, "sweeps": 0, "fulls": 0,
                "cross_sweeps": 0, "columns": 0,
                "entries": batch_rows * c}
    opc = None
    try:
        artifact = build_artifact(X, y, spec, c, s,
                                  key=jax.random.PRNGKey(0),
                                  use_pallas=False)
        state = init_state(artifact, y)
        opc = CountingOperator(artifact.landmark_operator())
        rng = np.random.default_rng(9)
        X_new = jnp.asarray(rng.standard_normal((batch_rows, d)), jnp.float32)
        y_new = jnp.asarray(rng.standard_normal(batch_rows), jnp.float32)
        append_rows(artifact, state, X_new, y_new, op=opc)
        findings = _check_counts(entry, opc.counts, expected)
    except Exception as exc:  # noqa: BLE001 — any failure is a gate failure
        findings = [Finding(
            path=f"jaxpr:{entry}", line=0, rule="RPRJ02",
            message=f"append path failed to run: {exc!r}",
            snippet=f"{entry}:run-error")]
    counts = opc.counts if opc is not None else {}
    return findings, _entry_report(entry, counts, expected, findings)


def check_fast_model(policy_name: str = "uniform",
                     precision: str = "f32") -> Tuple[List[Finding], dict]:
    """fast_model(gaussian, streaming) == 1 sweep + the policy's budget."""
    pol = selection_lib.get_policy(policy_name)
    opc = smoke_operator(precision=precision)
    entry = f"fast_model[{policy_name}"
    entry += f",{precision}]" if precision != "f32" else "]"
    closed, findings = _trace(
        entry,
        lambda key: spsd.fast_model(
            opc, key, c=SMOKE_C, s=SMOKE_S, s_sketch="gaussian",
            streaming=True, block_size=SMOKE_BLOCK, selection=policy_name),
        jax.random.PRNGKey(0))
    expected = {"sweeps": 1 + pol.sweep_budget(), "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, opc.n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def check_fast_model_with_error(policy_name: str = "uniform",
                                ) -> Tuple[List[Finding], dict]:
    """Model + Hutchinson error fused: STILL 1 sweep + the policy budget."""
    pol = selection_lib.get_policy(policy_name)
    opc = smoke_operator()
    entry = f"fast_model_with_error[{policy_name}]"
    closed, findings = _trace(
        entry,
        lambda key: spsd.fast_model_with_error(
            opc, key, c=SMOKE_C, s=SMOKE_S, s_sketch="gaussian", probes=8,
            block_size=SMOKE_BLOCK, selection=policy_name),
        jax.random.PRNGKey(0))
    expected = {"sweeps": 1 + pol.sweep_budget(), "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, opc.n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def check_fast_cur(policy_name: str = "uniform",
                   ) -> Tuple[List[Finding], dict]:
    """Streaming kernel-CUR: 1 sweep + 2× the policy budget (C and R)."""
    pol = selection_lib.get_policy(policy_name)
    opc = smoke_operator()
    entry = f"fast_cur[{policy_name}]"
    closed, findings = _trace(
        entry,
        lambda key: cur_lib.fast_cur(
            opc, key, c=SMOKE_C, r=SMOKE_C, sc=SMOKE_S, sr=SMOKE_S,
            sketch_kind="gaussian", block_size=SMOKE_BLOCK,
            selection=policy_name),
        jax.random.PRNGKey(3))
    expected = {"sweeps": 1 + 2 * pol.sweep_budget(), "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, opc.n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def check_serve(precision: str = "f32") -> Tuple[List[Finding], dict]:
    """serve_kernel_model: one fused cross launch per query bucket, 0 sweeps.

    Builds a tiny real artifact (concrete, off-trace), then traces the
    serving path over abstract query batches whose sizes force two buckets.
    """
    from repro.serve.artifact import build_artifact
    from repro.serve.engine import QueryRequest, plan_buckets, \
        serve_kernel_model

    n, d, c, s = SMOKE_N, 6, 12, 24
    X = _smoke_points(n=n, d=d, seed=7)
    y = jnp.asarray(np.random.default_rng(8).standard_normal(n),
                    jnp.float32)
    spec = pw_specs.get_spec("rbf", sigma=1.5)
    artifact = build_artifact(X, y, spec, c, s, key=jax.random.PRNGKey(0),
                              use_pallas=False)
    opc = CountingOperator(
        artifact.landmark_operator(use_pallas=True, precision=precision))
    sizes = (40, 5, 4)   # bucket_by_size -> [[40], [5, 4]]: two launches
    reqs = [QueryRequest(X=jnp.zeros((m, d))) for m in sizes]
    nbuckets = len(plan_buckets(reqs))
    entry = "serve_kernel_model"
    entry += f"[{precision}]" if precision != "f32" else ""

    def run(*qs):
        res = serve_kernel_model(
            artifact, [QueryRequest(X=q) for q in qs], op=opc)
        return tuple(r.out for r in res)

    closed, findings = _trace(
        entry, run, *[jnp.zeros((m, d), jnp.float32) for m in sizes])
    expected = {"cross_sweeps": nbuckets, "sweeps": 0, "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def check_kernel_precision(spec_name: str) -> Tuple[List[Finding], dict]:
    """One bf16_f32acc sweep per registered kernel: every dot accumulates f32."""
    opc = smoke_operator(spec_name=spec_name, precision="bf16_f32acc")
    entry = f"sweep[{spec_name},bf16_f32acc]"
    from repro.core import sweep as sweep_lib
    closed, findings = _trace(
        entry,
        lambda V: opc.sweep([sweep_lib.MatmulPlan(V)],
                            block_size=SMOKE_BLOCK),
        jnp.zeros((opc.n, 8), jnp.float32))
    expected = {"sweeps": 1, "fulls": 0}
    if closed is not None:
        findings += _check_counts(entry, opc.counts, expected)
        findings += scan_densify(closed, opc.n, entry)
        findings += scan_contractions(closed, entry)
    return findings, _entry_report(entry, opc.counts, expected, findings)


def run_jaxpr_checks(log: Optional[Callable[[str], None]] = None,
                     ) -> Tuple[List[Finding], List[dict]]:
    """Every entry-point check over the live registries."""
    def note(msg):
        if log:
            log(msg)

    findings: List[Finding] = []
    reports: List[dict] = []

    policies = selection_lib.registered_policies()
    for name in policies:
        for check in (check_policy_select, check_fast_model, check_fast_cur):
            note(f"trace {check.__name__}[{name}]")
            fs, rep = check(name)
            findings += fs
            reports.append(rep)
    for name in policies:
        if selection_lib.get_policy(name).sweep_budget() > 0:
            note(f"trace select_grown[{name}]")
            fs, rep = check_policy_select_grown(name)
            findings += fs
            reports.append(rep)

    note("run append_rows (concrete)")
    fs, rep = check_append()
    findings += fs
    reports.append(rep)

    note("trace fast_model_with_error[uniform]")
    fs, rep = check_fast_model_with_error("uniform")
    findings += fs
    reports.append(rep)

    note("trace fast_model[uniform,bf16_f32acc]")
    fs, rep = check_fast_model("uniform", precision="bf16_f32acc")
    findings += fs
    reports.append(rep)

    for prec in ("f32", "bf16_f32acc"):
        note(f"trace serve_kernel_model[{prec}]")
        fs, rep = check_serve(precision=prec)
        findings += fs
        reports.append(rep)

    for spec_name in pw_specs.registered_kernels():
        note(f"trace sweep[{spec_name},bf16_f32acc]")
        fs, rep = check_kernel_precision(spec_name)
        findings += fs
        reports.append(rep)

    return findings, reports
