"""mellum2-12b-a2.5b at its CPU twin (``SMOKE``, float32, seeded random
weights) against the plain reference ``models/reference_mellum2.py``, and
the parts it forced: YaRN on the full layers, the landmark fold, the offset
guard, the dropless served MoE and the sliding ring."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.sketched_attention import build_landmark_state
from repro.kernels.landmark_attention import ops as lm_ops
from repro.models import layers as L
from repro.models import moe as M
from repro.models import reference_mellum2 as ref
from repro.models.model import build_model

SMOKE = get_smoke("mellum2-12b-a2.5b")


def hf_config(cfg) -> dict:
    """The published ``config.json`` keys (and landmark settings) that the
    reference reads, for a program configuration."""
    y = cfg.rope_yarn
    kinds = {"local": "sliding_attention", "global": "full_attention"}
    return {
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "sliding_window": cfg.window, "rms_norm_eps": cfg.norm_eps,
        "num_experts_per_tok": cfg.moe_top_k,
        "landmark_c": cfg.landmark_c, "landmark_theta": cfg.landmark_theta,
        "layer_types": [kinds[cfg.layer_pattern[i % len(cfg.layer_pattern)]]
                        for i in range(cfg.n_layers)],
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta_local},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "attention_factor": y.attention_factor}},
    }


# ---------------------------------------------------------------------------
# prefill, then decode on the device, against the reference's full forward
# ---------------------------------------------------------------------------

def test_prefill_decode_matches_reference():
    """Prefill a 7-token prompt (window 8), then 20 decode steps on the
    device: the steps sit at positions W − 1, W and W + 1 of the sliding
    ring and run past twice the window; 3 landmarks and a 6-key sketch per
    full layer and KV head."""
    P, G = 7, 20
    cfg = dataclasses.replace(SMOKE, landmark_c=3, landmark_theta=2)
    m = build_model(cfg)
    params = jax.jit(m.init)(jax.random.PRNGKey(0))
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size)
    key = jax.random.PRNGKey(2)
    logits0, _ = jax.jit(m.prefill, static_argnums=3)(
        params, {"tokens": toks}, key, P + G)
    turn = jax.jit(m.generate, static_argnames=("steps",))(
        params, {"tokens": toks}, key, jnp.arange(B, dtype=jnp.int32),
        steps=G)
    for b in range(B):
        seq = jnp.concatenate([toks[b], turn.tokens[b]])
        want, _ = ref.forward(params, hf_config(cfg), seq, P, key, row=b)
        got = np.concatenate([np.asarray(logits0[b])[None],
                              np.asarray(turn.logits[b])])
        want = np.asarray(want)
        # float32 on both sides: what is left is summation order, amplified
        # through the landmark Ũ (pseudo-inverses of an exp-Gram sketch,
        # condition numbers near 1e4 here); measured at most 2e-6
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 1e-4, (b, rel)
    sizes, delivered = turn.taps["sizes"], turn.taps["delivered"]
    assert sizes.shape == (G, cfg.n_layers, cfg.n_experts)
    routed = G * cfg.n_layers * B * cfg.moe_top_k
    assert int(sizes.sum()) == routed and int(delivered.sum()) == routed


@pytest.mark.parametrize("seed", [0, 1])
def test_taps_teacher_force_the_reference(seed):
    """The program's taps (prefill and decode) drive the reference: with
    the program's routing forced, every margin is 0 in float32 and the
    logits agree; the full layers' factors replayed on the program's own
    keys, values and queries read what the program's factors read."""
    import functools
    P, G, B = 12, 6, 2
    cfg = dataclasses.replace(SMOKE, landmark_c=4, landmark_theta=2)
    m = build_model(cfg)
    params = jax.jit(m.init)(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, P), 0,
                              cfg.vocab_size)
    key = jax.random.PRNGKey(seed + 2)
    logits0, cache, pre = jax.jit(functools.partial(m.prefill, taps=True),
                                  static_argnums=3)(
        params, {"tokens": toks}, key, P + G)
    keep = jnp.array([1], jnp.int32)
    turn = jax.jit(m.decode_loop, static_argnames=("steps",))(
        params, cache, jnp.argmax(logits0, -1).astype(jnp.int32),
        jnp.asarray(P, jnp.int32), keep, steps=G)
    b, conf = 1, hf_config(cfg)
    dec = {n: turn.taps[n][0].swapaxes(0, 1) for n in
           ("experts", "landmark_q", "landmark_k", "landmark_v")}
    experts = jnp.concatenate([pre["experts"][:, b], dec["experts"]], 1)
    seq = jnp.concatenate([toks[b], turn.tokens[b]])
    want, margin = ref.forward(params, conf, seq, P, key, row=b,
                               experts=experts)
    assert float(jnp.max(margin)) == 0.0
    got = np.asarray(turn.logits[0])
    rel = np.linalg.norm(got - np.asarray(want[1:])) \
        / np.linalg.norm(np.asarray(want[1:]))
    assert rel < 1e-4, rel
    taps = {"landmark_k": pre["landmark_k"][:, b],
            "landmark_v": pre["landmark_v"][:, b],
            "landmark_q": dec["landmark_q"], "landmark_k_dec":
            dec["landmark_k"], "landmark_v_dec": dec["landmark_v"]}
    (outs, _, final), = ref.landmark_replay(conf, key, taps, row=b)
    entry = [e for e in turn.cache["scanned"] if "k_land" in e][0]
    kv, g, d = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    q = dec["landmark_q"][0].reshape(G, kv, g, d)
    for h in range(kv):
        def read(kl, uv, u1):
            c = jnp.exp(q[:, h] @ kl.T / math.sqrt(d)
                        - jnp.max(q[:, h] @ kl.T / math.sqrt(d), -1,
                                  keepdims=True))
            return (c @ uv) / (c @ u1)[..., None]
        a = read(entry["k_land"][0, b, h], entry["uv"][0, b, h],
                 entry["u1"][0, b, h])
        r = read(final["k_land"][h], final["UV"][h], final["U1"][h])
        # float32 on both sides, the same inputs: summation order only
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4


# ---------------------------------------------------------------------------
# the fold: t decoded keys folded in == the factors rebuilt over all keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,scale", [(1, 1.0), (6, 1.0), (3, 4.0)])
def test_fold_matches_rebuild(t, scale):
    """Folding keys one launch at a time equals Ũ R̂ [V | 1] with R̂ over the
    prompt's and the folded keys, Ũ and landmarks fixed at prefill.  With
    ``scale`` 4 the folded keys are larger than the prompt's (logits well
    past the prompt's offset): the guard raises the offset, and without it
    exp overflows float32."""
    n, d, c = 64, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    K = jax.random.normal(ks[0], (n, d)) * 2.0
    V = jax.random.normal(ks[1], (n, d))
    Kn = jax.random.normal(ks[2], (t, d)) * 2.0 * scale
    Vn = jax.random.normal(ks[3], (t, d))
    st = build_landmark_state(K, V, ks[4], c=c, theta=2)
    uv, u1, off = st.UV[None], st.U1[None], st.scale[None]
    for i in range(t):
        q = Kn[i][None, None]
        out, (uv, u1, off) = lm_ops.landmark_step(
            q, st.k_land[None], st.U[None], uv, u1, off, Kn[i][None],
            Vn[i][None])
        assert bool(jnp.all(jnp.isfinite(out)))
    hi = jax.lax.Precision.HIGHEST
    Kall = jnp.concatenate([K, Kn])
    Vall = jnp.concatenate([V, Vn])
    R = jnp.exp(jnp.matmul(st.k_land, Kall.T, precision=hi) / math.sqrt(d)
                - off[0])
    want_uv = jnp.matmul(st.U, jnp.matmul(R, Vall, precision=hi),
                         precision=hi)
    want_u1 = jnp.matmul(st.U, jnp.sum(R, axis=1), precision=hi)
    if scale > 1.0:
        assert float(off[0]) > float(st.scale)          # the guard moved it
    else:
        assert float(off[0]) == pytest.approx(float(st.scale))
    for got, want in ((uv[0], want_uv), (u1[0], want_u1)):
        gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        assert gap < 1e-5, gap                          # f32 rounding


def test_offset_guard_keeps_reads_finite():
    """A key far larger than the prompt's: without raising the offset its
    column of R̂ would be exp(> 88) — inf in float32.  The fold stays
    finite and the read equals the one from factors rebuilt at the raised
    offset."""
    n, d, c = 32, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    K = jax.random.normal(ks[0], (n, d))
    st = build_landmark_state(K, jax.random.normal(ks[1], (n, d)), ks[2],
                              c=c, theta=2)
    big = st.k_land[0] * 60.0
    assert float(jnp.max(st.k_land @ big) / math.sqrt(d)
                 - st.scale) > 88.0                     # past f32's exp
    out, (uv, u1, off) = lm_ops.landmark_step(
        big[None, None], st.k_land[None], st.U[None], st.UV[None],
        st.U1[None], st.scale[None], big[None], jnp.ones((1, d)))
    for a in (out, uv, u1, off):
        assert bool(jnp.all(jnp.isfinite(a)))
    want = lm_ops.landmark_read(big[None], st.k_land, uv[0], u1[0], off[0],
                                use_pallas=False)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# YaRN on full layers, the default RoPE on sliding ones
# ---------------------------------------------------------------------------

def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """HF ``_compute_yarn_parameters``, written out in numpy."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrap, interp = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp
    return interp * (1 - keep) + extrap * keep


@pytest.mark.parametrize("positions", [[0, 1, 7, 8191], [8192, 65535,
                                                        131071]])
def test_yarn_matches_formula(positions):
    from repro.configs import get_config
    cfg = get_config("mellum2-12b-a2.5b")
    y = cfg.rope_yarn
    D = cfg.head_dim
    inv = _hf_yarn_inv_freq(D, cfg.rope_theta, y.factor,
                            y.original_max_position, y.beta_fast,
                            y.beta_slow)
    np.testing.assert_allclose(
        np.asarray(L.rope_frequencies(D, cfg.rope_theta, y)), inv,
        rtol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(5), (len(positions), D))
    pos = jnp.asarray(positions)
    theta, yarn = L.rope_for(cfg, "global")
    got = np.asarray(L.apply_rope(x, pos, theta, yarn), np.float64)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None]
    cos = np.cos(ang) * y.attention_factor
    sin = np.sin(ang) * y.attention_factor
    x64 = np.asarray(x, np.float64)
    x1, x2 = x64[:, : D // 2], x64[:, D // 2:]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    # f32 angles at positions up to 2^17: |Δangle| ≤ 2^17 · 2^-24
    np.testing.assert_allclose(got, want, atol=2e-2 * max(1, max(
        positions) / 65536), rtol=0)
    assert np.abs(got - want).mean() < 1e-3

    # sliding layers keep the default RoPE at the same theta, unscaled
    theta_l, yarn_l = L.rope_for(cfg, "local")
    assert theta_l == theta and yarn_l is None
    plain = 1.0 / cfg.rope_theta ** (np.arange(0, D, 2) / D)
    np.testing.assert_allclose(np.asarray(L.rope_frequencies(D, theta_l)),
                               plain, rtol=1e-6)
    ang = np.asarray(positions, np.float64)[:, None] * plain[None]
    want_l = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                             x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    got_l = np.asarray(L.apply_rope(x, pos, theta_l, yarn_l), np.float64)
    assert np.abs(got_l - want_l).mean() < 1e-3


# ---------------------------------------------------------------------------
# the served MoE drops nothing
# ---------------------------------------------------------------------------

def test_dropless_moe_when_one_expert_takes_every_token():
    """Router biased so that expert 0 is in every token's top-k: the
    training dispatch (capacity 1.25·T·k/E) must drop most of expert 0's
    assignments, the served one none of them, and it equals the per-token
    loop over each token's top-k."""
    cfg = SMOKE
    p = jax.jit(M.init_moe, static_argnums=1)(jax.random.PRNGKey(6), cfg)
    p["router"] = p["router"].at[:, 0].add(50.0)
    T = 16
    x = jax.random.normal(jax.random.PRNGKey(7), (1, T, cfg.d_model))
    # ensure expert 0 wins for every token: inputs with a positive mean
    x = jnp.abs(x)
    got, dispatch = jax.jit(M.moe_ffn_served, static_argnums=1)(p, cfg, x)
    sizes = dispatch["sizes"]
    assert int(sizes[0]) == T and int(sizes.sum()) == T * cfg.moe_top_k
    assert int(dispatch["delivered"]) == T * cfg.moe_top_k
    assert bool(jnp.all(dispatch["experts"][0, :, 0] == 0))

    xf = x[0]
    probs = jax.nn.softmax(xf @ p["router"], axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one_token(xt, wt, it):                          # the per-token loop
        y = jnp.zeros((cfg.d_model,))
        for j in range(cfg.moe_top_k):
            h = jax.nn.silu(xt @ p["wi_gate"][it[j]]) \
                * (xt @ p["wi_up"][it[j]])
            y = y + wt[j] * (h @ p["wo"][it[j]])
        return y

    want = jax.jit(jax.vmap(one_token))(xf, w, idx)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dropped_path, _ = jax.jit(M.moe_ffn, static_argnums=1)(p, cfg, x)
    assert float(jnp.max(jnp.abs(dropped_path[0] - want))) > 1e-3


def _top_k_loop(p, cfg, xf):
    """Each token through its top-k experts one at a time, weighted by its
    renormalised router probabilities."""
    probs = jax.nn.softmax(jnp.matmul(xf, p["router"],
                                      precision=jax.lax.Precision.HIGHEST))
    w, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def one_token(xt, wt, it):
        y = jnp.zeros((cfg.d_model,))
        for j in range(cfg.moe_top_k):
            h = jax.nn.silu(xt @ p["wi_gate"][it[j]]) \
                * (xt @ p["wi_up"][it[j]])
            y = y + wt[j] * (h @ p["wo"][it[j]])
        return y

    return jax.jit(jax.vmap(one_token))(xf, w, idx)


def _served(p, cfg, x):
    """``moe_ffn_served`` under a fresh jit, so a patched compute is traced."""
    return jax.jit(lambda p, x: M.moe_ffn_served(p, cfg, x))(p, x)


@pytest.mark.parametrize("tokens,path", [
    (16, "dense"),          # T <= 128 and T·k = 64 >= E = 16
    (160, "grouped"),       # past the dense pass's 128 tokens
    (3, "grouped"),         # T·k = 12 < E: most experts untouched
])
def test_served_moe_paths_match_the_loop(monkeypatch, tokens, path):
    """The served MoE takes the dense pass over every expert or the grouped
    products from the call's shape (its span says which), and both equal
    the per-token top-k loop and report the same dispatch; an expert whose
    output is zero counts its routed rows as not delivered in both."""
    cfg = SMOKE
    p = jax.jit(M.init_moe, static_argnums=1)(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens,
                                                       cfg.d_model))
    # token 0's first expert outputs zeros: its rows are routed, not delivered
    silent = int(jnp.argmax(x[0, 0] @ p["router"]))
    p["wo"] = p["wo"].at[silent].set(0.0)
    text = jax.jit(lambda p, x: M.moe_ffn_served(p, cfg, x)).lower(
        p, x).as_text(debug_info=True)               # scopes in locations
    other = {"dense": "grouped", "grouped": "dense"}[path]
    assert f"moe.{path}" in text and f"moe.{other}" not in text
    want = np.asarray(_top_k_loop(p, cfg, x[0]))

    runs = {}
    for name, fn in (("dense", M._dense_compute),
                     ("grouped", M._grouped_compute)):
        monkeypatch.setattr(M, "_dropless_compute", fn)
        runs[name] = _served(p, cfg, x)
        np.testing.assert_allclose(np.asarray(runs[name][0][0]), want,
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    dense, grouped = runs["dense"][1], runs["grouped"][1]
    for key in ("sizes", "experts", "delivered"):
        np.testing.assert_array_equal(np.asarray(dense[key]),
                                      np.asarray(grouped[key]), err_msg=key)
    assert int(dense["sizes"][silent]) >= 1
    assert int(dense["delivered"]) == (tokens * cfg.moe_top_k
                                       - int(dense["sizes"][silent]))


def test_dense_moe_selects_routed_pairs():
    """An expert no token is routed to, with an infinite ``wo``: the dense
    pass computes its output (inf or NaN) for every token, and the combine
    must select the routed pairs with the mask, not multiply the rest by 0,
    for the output to stay equal to the per-token loop."""
    cfg = SMOKE
    E = cfg.n_experts
    p = jax.jit(M.init_moe, static_argnums=1)(jax.random.PRNGKey(9), cfg)
    p["router"] = p["router"].at[:, E - 1].add(-50.0)
    p["wo"] = p["wo"].at[E - 1].set(jnp.inf)
    T = 16
    # positive inputs: expert E − 1's logit is the lowest for every token
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (1, T,
                                                          cfg.d_model)))
    got, dispatch = _served(p, cfg, x)
    assert int(dispatch["sizes"][E - 1]) == 0
    assert int(dispatch["delivered"]) == T * cfg.moe_top_k
    want = np.asarray(_top_k_loop(p, cfg, x[0]))
    assert np.all(np.isfinite(want))
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5,
                               atol=1e-5)
