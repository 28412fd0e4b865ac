"""KernelModelArtifact: the warm-boot factor store of the serving path.

After ``fast_model`` there is everything a replica needs to answer queries
*forever* without touching the n × n kernel again: the landmark points
X_S = X[P], the C basis K(X, X_S), the fast U, and small dense "heads" that
turn one rectangular cross-kernel launch G = K(X_query, X_S) into each
downstream answer:

- KRR prediction      f(x) = G  @ head_krr,   head = U Cᵀ w        (c × t)
- KPCA projection     z(x) = G  @ head_kpca,  head = U Cᵀ V Λ^-½   (c × k)
- Nyström features    φ(x) = G  @ head_feat,  head = E_r Λ_U,r^½   (c × r)

all derived from the Nyström out-of-sample extension of the fast model,
k̂(x, ·) = K(x, X_S) U Cᵀ (rows of C *are* K(x_i, X_S), so train points
round-trip exactly).  The KRR weights come from the cached
``woodbury_solve`` route, and the (c × c) Woodbury workspace
M = U (αI + CᵀC U)⁻¹ is kept on the artifact so re-fitting NEW targets on
the same kernel is one thin matmul (``refit``), never another solve.

Persistence rides ``repro.checkpoint``: the artifact flattens to a
JSON-style dict tree (arrays + one ``meta_json`` string leaf for the
KernelSpec / selection metadata), committed atomically per step so replicas
boot warm from ``load_artifact`` — a fresh process needs no shape knowledge
(``checkpoint.restore_tree`` reconstructs from the manifest).  Damage is
detected as ``CheckpointCorruptionError`` and handled by
``load_or_rebuild`` through ``runtime.fault_tolerance.ArtifactRecovery``:
rebuild from source, persist, keep serving.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.core import eig as eig_lib
from repro.core import spsd
from repro.core.kernelop import PairwiseKernel
from repro.kernels.pairwise import specs as pw_specs
from repro.runtime.fault_tolerance import ArtifactRecovery

#: the query tasks the engine can answer; head matrices are keyed by these
TASKS = ("krr", "kpca", "features")


def _mm(a, b):
    """f32 product at full f32 precision (a TPU's default f32 matmul is one
    bf16 pass)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def krr_head(M, Cty):
    """The KRR head U Cᵀ w, w = (C U Cᵀ + αI)⁻¹ y, from the Woodbury
    workspace M = U (αI + CᵀC U)⁻¹ and Cᵀy: U Cᵀ w = M Cᵀ y.

    Equal to U Cᵀ (y − C M Cᵀ y)/α, but without its cancellation: at large
    n the top eigenvalues of C U Cᵀ reach ~1e5·α, the difference keeps only
    the part of y they do not explain, and even in f64 the head lost ~1e-4
    of its accuracy at n = 2^18."""
    return M @ Cty


@dataclasses.dataclass
class KernelModelArtifact:
    """Everything ``serve_kernel_model`` needs, independent of train-set size
    at query time (heads are c × out; only ``C`` keeps an n-sized factor, for
    target re-fits and diagnostics)."""

    X_landmarks: jnp.ndarray            # (c, d) selected points X[P]
    C: jnp.ndarray                      # (n, c) basis K(X, X_S)
    U: jnp.ndarray                      # (c, c) fast-model U
    heads: Dict[str, jnp.ndarray]       # task -> (c, out_dim)
    woodbury_M: jnp.ndarray             # (c, c) cached U (αI + CᵀC U)⁻¹
    kpca_eigvals: jnp.ndarray           # (k,) spectrum of the KPCA head
    spec: pw_specs.KernelSpec           # calibrated kernel spec
    alpha: float                        # KRR ridge
    selection: str = "uniform"          # SelectionPolicy that chose P
    landmark_indices: Optional[jnp.ndarray] = None
    use_pallas: bool = True
    # sign-split plan for l1dist specs, built ONCE over the landmark points
    # at precompute time and persisted with the artifact: l1_route is
    # 'mxu_signsplit' (l1_edges holds the segment table), 'vpu_loop' (plan
    # infeasible — the VPU decision itself is replicated), or None
    # (non-l1dist spec, or a legacy checkpoint from before the field — the
    # operator falls back to its lazy per-instance build)
    l1_edges: Optional[jnp.ndarray] = None
    l1_route: Optional[str] = None

    @property
    def c(self) -> int:
        return int(self.X_landmarks.shape[0])

    def landmark_operator(self, use_pallas: Optional[bool] = None,
                          precision: Optional[str] = None) -> PairwiseKernel:
        """The data-backed operator query launches run through: a
        ``PairwiseKernel`` over the landmark points, so
        ``op.cross(X_query, heads)`` is K(X_query, X_S) @ head per head in
        one fused rectangular launch.  ``precision`` overrides the spec's
        tile policy for query-time launches (e.g. ``'bf16_f32acc'`` to serve
        an f32-built artifact with bf16 cross tiles)."""
        up = self.use_pallas if use_pallas is None else use_pallas
        spec = self.spec
        if precision is not None:
            spec = spec.with_precision(precision)
        op = PairwiseKernel(self.X_landmarks, spec, up)
        if self.l1_route is not None and spec.stat == "l1dist":
            # restore the precomputed sign-split plan instead of letting the
            # operator rebuild it host-side per instance (ROADMAP gap); a
            # persisted 'vpu_loop' decision seeds None so routing is
            # byte-identical to build time
            op._l1_edges_cache = \
                self.l1_edges if self.l1_route == "mxu_signsplit" else None
        return op

    def refit(self, y: jnp.ndarray) -> "KernelModelArtifact":
        """New KRR targets on the SAME kernel via the cached Woodbury
        workspace: head = M Cᵀ y (``krr_head``) — one thin matmul, no
        solve.  Returns a copy with ``heads['krr']`` replaced."""
        y2 = (y[:, None] if y.ndim == 1 else y).astype(jnp.float32)
        Cty = _mm(self.C.astype(jnp.float32).T, y2)
        heads = dict(self.heads)
        heads["krr"] = _mm(self.woodbury_M, Cty)
        return dataclasses.replace(self, heads=heads)


def _meta(artifact: KernelModelArtifact) -> str:
    return json.dumps({
        "spec_name": artifact.spec.name,
        "spec_params": list(artifact.spec.params),
        "spec_precision": artifact.spec.precision,
        "alpha": float(artifact.alpha),
        "selection": artifact.selection,
        "use_pallas": bool(artifact.use_pallas),
        "l1_route": artifact.l1_route,
        "format": 1,
    })


def artifact_to_tree(artifact: KernelModelArtifact) -> dict:
    """The JSON-style dict tree ``checkpoint.save`` persists (and
    ``checkpoint.restore_tree`` reconstructs shape-free)."""
    tree = {
        "X_landmarks": artifact.X_landmarks,
        "C": artifact.C,
        "U": artifact.U,
        "heads": dict(artifact.heads),
        "woodbury_M": artifact.woodbury_M,
        "kpca_eigvals": artifact.kpca_eigvals,
        "meta_json": _meta(artifact),
    }
    if artifact.landmark_indices is not None:
        tree["landmark_indices"] = artifact.landmark_indices
    if artifact.l1_edges is not None:
        tree["l1_edges"] = artifact.l1_edges
    return tree


def artifact_from_tree(tree: dict) -> KernelModelArtifact:
    meta = json.loads(str(np.asarray(tree["meta_json"]).item()))
    spec = pw_specs.get_spec(meta["spec_name"],
                             **{k: v for k, v in meta["spec_params"]})
    # precision is a spec field, not a factory param, so artifacts written
    # before the field existed restore as f32 (the old behavior)
    spec = spec.with_precision(meta.get("spec_precision", "f32"))
    idx = tree.get("landmark_indices")
    edges = tree.get("l1_edges")
    return KernelModelArtifact(
        X_landmarks=jnp.asarray(tree["X_landmarks"]),
        C=jnp.asarray(tree["C"]),
        U=jnp.asarray(tree["U"]),
        heads={k: jnp.asarray(v) for k, v in tree["heads"].items()},
        woodbury_M=jnp.asarray(tree["woodbury_M"]),
        kpca_eigvals=jnp.asarray(tree["kpca_eigvals"]),
        spec=spec,
        alpha=float(meta["alpha"]),
        selection=meta["selection"],
        landmark_indices=None if idx is None else jnp.asarray(idx),
        use_pallas=bool(meta["use_pallas"]),
        # legacy checkpoints carry no l1_route key -> None -> the operator's
        # lazy per-instance plan build (the pre-field behavior)
        l1_edges=None if edges is None else jnp.asarray(edges),
        l1_route=meta.get("l1_route"),
    )


# ---------------------------------------------------------------------------
# build (training side)
# ---------------------------------------------------------------------------

def build_artifact(
    X: jnp.ndarray,
    y: jnp.ndarray,
    spec: pw_specs.KernelSpec,
    c: int,
    s: int,
    *,
    alpha: float = 1.0,
    n_components: int = 8,
    n_features: Optional[int] = None,
    s_sketch: str = "gaussian",
    selection: str = "uniform",
    key: Optional[jax.Array] = None,
    use_pallas: bool = True,
    block_size: Optional[int] = None,
    mesh=None,
) -> KernelModelArtifact:
    """Algorithm 1 + every downstream head, once, at precompute time.

    Runs ``fast_model`` on the streaming substrate (``selection`` /
    ``mesh`` / ``block_size`` thread straight through), then derives the
    KRR weights via ``woodbury_solve``'s identity — keeping its (c × c)
    workspace for ``refit`` — the KPCA head from ``approx_eigh`` (Lemma 10),
    and the rank-``n_features`` Nyström feature head from the
    eigendecomposition of U.
    """
    key = jax.random.PRNGKey(0) if key is None else key
    X = jnp.asarray(X, jnp.float32)
    Kop = PairwiseKernel(X, spec, use_pallas)
    ap = spsd.fast_model(Kop, key, c=c, s=s, s_sketch=s_sketch,
                         selection=selection, block_size=block_size,
                         mesh=mesh)
    C32 = ap.C.astype(jnp.float32)
    U32 = 0.5 * (ap.U + ap.U.T).astype(jnp.float32)

    # KRR: head = U Cᵀ w with w = (C U Cᵀ + αI)⁻¹ y from the Woodbury
    # identity, workspace cached for refits.  The build-time algebra runs in
    # f64 numpy (offline, host-side) so the f32 heads it emits are
    # true-solution-accurate — the serving parity gate (≤1e-5 vs the f64
    # reference) then measures only f32 rounding plus the Pallas cross
    # launch, not solver conditioning.
    a = float(alpha)
    if not (a > 0.0 and np.isfinite(a)):
        raise ValueError(f"alpha must be a finite positive ridge, got {a!r}")
    C64 = np.asarray(C32, np.float64)
    U64 = np.asarray(U32, np.float64)
    inner = a * np.eye(c) + (C64.T @ C64) @ U64
    M64 = U64 @ np.linalg.solve(inner, np.eye(c))
    y64 = np.asarray(y[:, None] if y.ndim == 1 else y, np.float64)
    head_krr = jnp.asarray(krr_head(M64, C64.T @ y64), jnp.float32)  # (c, t)
    M = jnp.asarray(M64, jnp.float32)

    # KPCA: z(x) = Λ^-½ Vᵀ k̂(x,·)ᵀ = K(x,X_S) · U Cᵀ V Λ^-½
    eres = eig_lib.approx_eigh(C32, U32, n_components)
    lam = jnp.maximum(eres.eigenvalues, 1e-12)
    head_kpca = _mm(U32, _mm(C32.T, eres.eigenvectors)) / jnp.sqrt(lam)[None, :]

    # Nyström feature map: U = E Λ_U Eᵀ ⇒ φ(x) = Λ_U,r^½ E_rᵀ K(x,X_S)ᵀ
    r = c if n_features is None else min(int(n_features), c)
    lam_u, E = jnp.linalg.eigh(U32)                  # ascending
    lam_u = jnp.maximum(lam_u[::-1], 0.0)
    E = E[:, ::-1]
    head_feat = E[:, :r] * jnp.sqrt(lam_u[:r])[None, :]

    # Sign-split plan for the landmark operator, built once here (host-side
    # pass over the c landmark points) and persisted with the artifact so
    # warm-booted replicas and every landmark_operator() instance share it
    # instead of rebuilding per instance.
    X_land = jnp.take(X, ap.P_indices, axis=0)
    l1_edges, l1_route = None, None
    if spec.stat == "l1dist":
        from repro.kernels.pairwise import signsplit
        plan = signsplit.build_plan(X_land)
        l1_edges = None if plan is None else plan.edges
        l1_route = "vpu_loop" if plan is None else "mxu_signsplit"

    return KernelModelArtifact(
        X_landmarks=X_land,
        C=C32, U=U32,
        heads={"krr": head_krr, "kpca": head_kpca, "features": head_feat},
        woodbury_M=M, kpca_eigvals=eres.eigenvalues,
        spec=spec, alpha=a, selection=str(selection),
        landmark_indices=ap.P_indices, use_pallas=use_pallas,
        l1_edges=l1_edges, l1_route=l1_route)


# ---------------------------------------------------------------------------
# persistence (checkpoint/ + fault-tolerance recompute hook)
# ---------------------------------------------------------------------------

def save_artifact(directory: str, artifact: KernelModelArtifact,
                  step: int = 0) -> str:
    """Atomically commit the artifact as checkpoint ``step`` (refresh
    generations bump the step; replicas always boot the latest)."""
    return ckpt.save(directory, step, artifact_to_tree(artifact))


def load_artifact(directory: str,
                  step: Optional[int] = None) -> Optional[KernelModelArtifact]:
    """Latest (or pinned) committed artifact, or None when none exists.

    Delta-chain aware: when the target step is an incremental refresh
    generation (``delta_json`` leaf, see ``repro.serve.incremental``), the
    chain is replayed onto its base snapshot — a warm boot lands on the
    LIVE grown artifact, not the last full rebuild.  File-level damage and
    broken chains raise ``CheckpointCorruptionError`` — callers that must
    keep serving go through ``load_or_rebuild`` instead."""
    if step is None:
        step = ckpt.latest_step(directory)
        if step is None:
            return None
    # peek the step KIND from the manifest alone before choosing a decoder
    # (a delta tree has no meta_json leaf and would mis-classify as corrupt)
    if "delta_json" in ckpt.step_leaf_paths(directory, step):
        from repro.serve import incremental
        return incremental.load_artifact_chain(directory, step)
    tree = ckpt.restore_tree(directory, step)
    try:
        return artifact_from_tree(tree)
    except (KeyError, ValueError, TypeError, json.JSONDecodeError) as e:
        raise ckpt.CheckpointCorruptionError(
            f"artifact at {directory} step {step} does not decode "
            f"({type(e).__name__}: {e})") from e


def load_or_rebuild(
    directory: str,
    build_fn,
    recovery: Optional[ArtifactRecovery] = None,
    step: int = 0,
) -> Tuple[KernelModelArtifact, ArtifactRecovery]:
    """Warm boot with the recompute-on-corruption policy.

    ``build_fn()`` recreates the artifact from source data; it only runs
    when the store is missing or damaged, and its output is persisted so the
    next replica boots warm.  Returns ``(artifact, recovery)`` — inspect
    ``recovery.warm`` / ``recovery.events`` to distinguish warm from cold
    boots (the serve-smoke CI job requires warm).
    """
    if recovery is None:
        recovery = ArtifactRecovery(
            corruption_types=(ckpt.CheckpointCorruptionError,))
    out = recovery.run(
        load=lambda: load_artifact(directory),
        rebuild=build_fn,
        save=lambda a: save_artifact(directory, a, step=step))
    return out, recovery
