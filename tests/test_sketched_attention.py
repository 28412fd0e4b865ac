"""The paper's technique applied to attention: quality + scaling laws."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketched_attention import (build_landmark_state,
                                           landmark_decode,
                                           sketched_attention)


def _qkv(key, S=256, D=32, scale=0.4):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (S, D)) * scale
    k = jax.random.normal(ks[1], (S, D)) * scale
    v = jax.random.normal(ks[2], (S, D))
    return q, k, v


def _exact(q, k, v):
    logits = (q @ k.T) / np.sqrt(q.shape[-1])
    w = jax.nn.softmax(logits, axis=-1)
    return w @ v


def _err(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_sketched_attention_error_decreases_with_c():
    q, k, v = _qkv(jax.random.PRNGKey(0))
    exact = _exact(q, k, v)
    errs = []
    for c in (8, 32, 128):
        outs = [sketched_attention(q, k, v, jax.random.PRNGKey(10 + i),
                                   c=c, theta=4)
                for i in range(3)]
        errs.append(np.mean([_err(o, exact) for o in outs]))
    assert errs[0] > errs[-1], errs
    assert errs[-1] < 0.15, errs


def test_fast_mode_beats_nystrom_mode():
    """The paper's core claim transplanted to the softmax Gram."""
    q, k, v = _qkv(jax.random.PRNGKey(1), S=384)
    exact = _exact(q, k, v)
    e_fast = np.mean([_err(sketched_attention(
        q, k, v, jax.random.PRNGKey(20 + i), c=24, theta=8, mode="fast"),
        exact) for i in range(5)])
    e_nys = np.mean([_err(sketched_attention(
        q, k, v, jax.random.PRNGKey(20 + i), c=24, theta=8, mode="nystrom"),
        exact) for i in range(5)])
    assert e_fast <= e_nys + 1e-3, (e_fast, e_nys)


def test_landmark_state_decode_read():
    """Prefill-built landmark state answers one-token reads close to exact
    attention over the full context."""
    key = jax.random.PRNGKey(2)
    S, D = 512, 32
    _, k, v = _qkv(key, S=S, D=D)
    state = build_landmark_state(k, v, jax.random.fold_in(key, 1), c=64,
                                 theta=4)
    q1 = jax.random.normal(jax.random.fold_in(key, 2), (4, D)) * 0.4
    got = jax.vmap(lambda qq: landmark_decode(state, qq))(q1)
    want = _exact(q1, k, v)
    assert _err(got, want) < 0.35, _err(got, want)


def test_landmark_read_kernel_path_matches_core():
    """``landmark_decode`` reads through the landmark-attention launch; it
    agrees with the jnp oracle on a prefill-built state."""
    from repro.kernels.landmark_attention import ref as lm_ref
    key = jax.random.PRNGKey(3)
    S, D = 256, 32
    _, k, v = _qkv(key, S=S, D=D)
    state = build_landmark_state(k, v, jax.random.fold_in(key, 1), c=32,
                                 theta=4)
    q1 = jax.random.normal(jax.random.fold_in(key, 2), (8, D)) * 0.4
    a = jax.vmap(lambda qq: landmark_decode(state, qq))(q1)
    b = lm_ref.landmark_read(q1, state.k_land, state.UV, state.U1,
                             state.scale)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# bugfix regression tests (each fails deterministically on the pre-PR code)
# ---------------------------------------------------------------------------

def _sk_module():
    # ``repro.core`` re-exports the *function* ``sketched_attention``, which
    # shadows the submodule attribute — route around it via importlib.
    import importlib
    return importlib.import_module("repro.core.sketched_attention")


def _spy_fast_U_cur(monkeypatch, captured):
    sk_mod = _sk_module()
    orig = sk_mod.fast_U_cur

    def spy(ScC, G_blk, RSr):
        captured["ScC"] = np.asarray(ScC)
        captured["RSr"] = np.asarray(RSr)
        return orig(ScC, G_blk, RSr)

    monkeypatch.setattr(sk_mod, "fast_U_cur", spy)


def test_rectangular_fast_sketch_rows_stay_in_bounds(monkeypatch):
    """m < c fast mode: the row sketch must index REAL rows of Q.

    The old code started ``sq`` from ``jnp.arange(c)``, which clamp-gathers
    out-of-bounds (duplicated) rows of an m-row Q whenever m < c, and padded
    it to s = θc rows regardless of m.
    """
    captured = {}
    _spy_fast_U_cur(monkeypatch, captured)
    m, n, D, c = 8, 256, 32, 32
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(jax.random.fold_in(key, 0), (m, D)) * 0.4
    k = jax.random.normal(jax.random.fold_in(key, 1), (n, D)) * 0.4
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, D))
    out = sketched_attention(q, k, v, jax.random.fold_in(key, 3), c=c,
                             theta=4, mode="fast")
    assert np.all(np.isfinite(np.asarray(out)))
    ScC = captured["ScC"]
    assert ScC.shape[0] <= m, \
        f"row sketch has {ScC.shape[0]} rows from an m={m} query block"
    assert np.unique(ScC, axis=0).shape[0] == ScC.shape[0], \
        "duplicated rows in the sketched C panel"


def test_square_fast_sketch_is_duplicate_free(monkeypatch):
    """Square fast mode: sketch extensions must exclude the landmarks and
    sample without replacement (old code: replace=True over ALL of [0, n),
    so duplicated rows/columns biased fast_U_cur)."""
    captured = {}
    _spy_fast_U_cur(monkeypatch, captured)
    S, D, c, theta = 64, 16, 16, 4          # s = 64 = n: any dup is provable
    q, k, v = _qkv(jax.random.PRNGKey(5), S=S, D=D)
    sketched_attention(q, k, v, jax.random.PRNGKey(6), c=c, theta=theta,
                       mode="fast")
    ScC, RSr = captured["ScC"], captured["RSr"]
    assert np.unique(ScC, axis=0).shape[0] == ScC.shape[0], \
        "duplicated rows in S_qᵀĈ"
    assert np.unique(RSr.T, axis=0).shape[0] == RSr.shape[1], \
        "duplicated columns in R̂S_k"


def test_build_landmark_state_sketch_is_duplicate_free(monkeypatch):
    captured = {}
    _spy_fast_U_cur(monkeypatch, captured)
    _, k, v = _qkv(jax.random.PRNGKey(7), S=64, D=16)
    build_landmark_state(k, v, jax.random.PRNGKey(8), c=16, theta=4)
    RSr = captured["RSr"]
    assert np.unique(RSr.T, axis=0).shape[0] == RSr.shape[1], \
        "duplicated columns in the prefill sketch"


def test_landmark_indices_degenerate_request():
    """c >= n: old code computed seg = n // c == 0 and returned ALL-ZERO
    indices (every landmark the same token)."""
    import warnings as _warnings

    from repro.core.sketched_attention import landmark_indices
    with _warnings.catch_warnings(record=True) as w:
        _warnings.simplefilter("always")
        idx = np.asarray(landmark_indices(jax.random.PRNGKey(0), 16, 32))
    assert np.unique(idx).shape[0] == 16, idx
    assert any("clamping" in str(x.message) for x in w)
    # c == n is not degenerate: distinct, no warning needed
    idx_eq = np.asarray(landmark_indices(jax.random.PRNGKey(1), 16, 16))
    assert np.unique(idx_eq).shape[0] == 16


def test_denominator_sign_preserved_under_U_flip(monkeypatch):
    """out = (ĈŨR̂V)/(ĈŨR̂1) is invariant to Ũ → −Ũ *only* if the
    denominator floor preserves sign; the old maximum(den, 1e-6) clamped a
    negated (negative) denominator to +1e-6 and blew the output up."""
    sk_mod = _sk_module()
    q, k, v = _qkv(jax.random.PRNGKey(9), S=128, D=16)
    kr = jax.random.PRNGKey(10)
    out_pos = sketched_attention(q, k, v, kr, c=16, theta=4, mode="fast")
    orig = sk_mod.fast_U_cur
    monkeypatch.setattr(sk_mod, "fast_U_cur",
                        lambda *a: -orig(*a))
    out_neg = sketched_attention(q, k, v, kr, c=16, theta=4, mode="fast")
    np.testing.assert_allclose(np.asarray(out_neg), np.asarray(out_pos),
                               rtol=1e-4, atol=1e-5)


def test_decode_and_kernel_read_sign_preserved():
    """The decode cache and both fused-read paths (pallas + ref) share the
    sign-preserving floor: negating (UV, U1) must leave the read unchanged."""
    from repro.kernels.landmark_attention import ops as lm_ops
    _, k, v = _qkv(jax.random.PRNGKey(11), S=128, D=16)
    state = build_landmark_state(k, v, jax.random.PRNGKey(12), c=16)
    q1 = jax.random.normal(jax.random.PRNGKey(13), (4, 16)) * 0.4

    a = jax.vmap(lambda qq: landmark_decode(state, qq))(q1)
    neg = state._replace(UV=-state.UV, U1=-state.U1)
    b = jax.vmap(lambda qq: landmark_decode(neg, qq))(q1)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)
    for use_pallas in (True, False):
        r1 = lm_ops.landmark_read(q1, state.k_land, state.UV, state.U1,
                                  state.scale, use_pallas=use_pallas)
        r2 = lm_ops.landmark_read(q1, neg.k_land, neg.UV, neg.U1, neg.scale,
                                  use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                                   rtol=1e-4, atol=1e-5)


def test_selection_policy_landmarks():
    """SelectionPolicy-chosen landmarks ride the attention path end-to-end:
    distinct indices from the softmax-Gram operator, finite output, and
    accuracy in the same band as strided landmarks."""
    from repro.core.sketched_attention import select_landmarks
    q, k, v = _qkv(jax.random.PRNGKey(14), S=192, D=16)
    exact = _exact(q, k, v)
    for sel in ("uniform", "leverage", "uniform_adaptive2"):
        idx = np.asarray(select_landmarks(k, jax.random.PRNGKey(15), 24,
                                          selection=sel))
        assert np.unique(idx).shape[0] == 24, (sel, idx)
        errs = [_err(sketched_attention(q, k, v, jax.random.PRNGKey(20 + i),
                                        c=24, theta=4, selection=sel),
                     exact) for i in range(3)]
        assert np.mean(errs) < 0.35, (sel, errs)
