"""Plain float32 reference of Mellum2-12B-A2.5B as served: prompt, then
decoded tokens, with its full (global) layers read through landmark factors.

    logits, margins = forward(params, config, tokens, prompt_len, key)
    layers = landmark_replay(config, key, taps)

One causal forward over ``tokens`` (prompt then the tokens a decode fed,
teacher-forced), layer by layer, every weight upcast to float32 as its layer
runs, every contraction at ``jax.default_matmul_precision("highest")``.  No
kernels, no cache, no batching over sessions; attention is computed in
blocks of query rows only so that a 32k-token panel fits.

The layer, in the HF Qwen-MoE / Mistral form: x + o(attn(rope(q(n(x))),
rope(k(n(x))), v(n(x)))), then x + moe(n(x)), n = RMSNorm (ε from the
config, weight 1 + scale), no attention bias, no q/k norm.  Sliding layers
attend to keys j with 0 ≤ i − j < window under the default RoPE; full layers
use YaRN (``rope_parameters.full_attention``) and exact causal attention at
the prompt's positions.  The MoE routes each token by softmax over all
experts, keeps its top-k and renormalises them (``norm_topk_prob``), and
adds every one of the k SwiGLU experts' outputs: nothing is dropped (the sum
runs expert by expert over all tokens, with a gate of zero where a token was
not routed).

Departures from the published model, both of them the served system's:

- At decode positions (≥ ``prompt_len``) a full layer attends through the
  paper's fast model on the softmax Gram of its prompt keys: c landmarks
  (strided with jitter), a column sketch S ⊇ P of θ·c keys, Ũ =
  (SᵀĈ)⁺ (Sᵀ G S) (R̂ S)⁺ with R̂ = exp(K_P Kᵀ/√d − off), UV = Ũ R̂ V and
  U1 = Ũ R̂ 1, drawn from the same key schedule as the program's prefill.
  Each decoded key k_t and value v_t is folded in as one more column of R̂
  (off' = max(off, max k_P k_t/√d); UV ← UV e^(off−off') + (Ũ r) v_tᵀ,
  U1 ← U1 e^(off−off') + Ũ r), then the token's queries read
  exp(q K_Pᵀ/√d − off')·UV / sgnfloor(exp(q K_Pᵀ/√d − off')·U1).
- The multi-token-prediction head is left out.

``act_dtype`` holds the model to the precision the configuration states
for its activations: where it is set (``bfloat16`` for the served model),
every activation the served program keeps in that dtype is rounded to it at
the same point — the residual stream after each addition, both RMSNorm
outputs, q, k and v after their projection and again after RoPE, the
softmax weights before they multiply the values, the attention output, the
o-projection, each expert's SwiGLU product and its output, and the MoE's
combined output.  Everything between those points, the router, the
softmax, the landmark factors and the logits stay float32 at ``highest``.
``state_dtype`` holds the landmark factors (k_P, Ũ, UV, U1) in a lower
precision between steps: the control of the benchmark's comparison.

Two independent bf16 computations of the same model part ways by more than
rounding: a value that differs in its last f32 bits rounds to another bf16
number now and then, a near-tie among the router's probabilities then picks
another expert, and the landmark factors' Ũ (pseudo-inverses of an
exp-Gram sketch) magnifies small changes of the keys.  So the benchmark
drives this reference with the program's own discrete choices and inputs,
as it feeds it the program's tokens: ``forward(..., experts=...)`` takes
the program's routing (and reports how far each choice lies from its own
top-k), and ``landmark_replay`` rebuilds and folds the full layers' factors
from the program's own keys, values and queries.

The parameters are the program's pytree (``params["stack"]["scanned"]``:
one entry per position of the layer pattern, stacked over its repeats); the
configuration is the published ``config.json`` keys (``layer_types``,
``rope_parameters``, …) plus ``landmark_c``, ``landmark_theta``.  This file
imports nothing of the program; ``bench/reference_mellum2.py`` is a copy.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


# ---------------------------------------------------------------------------
# the layers as the published config describes them
# ---------------------------------------------------------------------------

def layers_of(params: dict, config: dict) -> list:
    """[(kind, the pattern position's parameters stacked over its repeats,
    the repeat)] in depth order; kind "sliding_attention" or
    "full_attention", from ``layer_types``.  Nothing is sliced here: a
    layer's weights are taken out (and upcast) inside its own program."""
    types = config["layer_types"]
    scanned = params["stack"]["scanned"]
    period = len(scanned)
    return [(t, scanned[l % period], l // period)
            for l, t in enumerate(types)]


def layer_keys(key: jax.Array, config: dict, row: int = 0) -> list:
    """The key each full layer's landmark build draws from, as the program's
    prefill derives it for the session in batch row ``row``: one key per
    repeat of the layer pattern, folded with the layer's position in the
    pattern, then with the row."""
    types = config["layer_types"]
    if "full_attention" not in types:
        return [None] * len(types)
    period = types.index("full_attention") + 1
    reps = jax.random.key_data(jax.random.split(key, len(types) // period))
    return [jax.random.fold_in(jax.random.fold_in(
        jax.random.wrap_key_data(reps[l // period]), l % period), row)
        for l in range(len(types))]


def rounder(dtype):
    """x -> x rounded to ``dtype`` and held in float32 (identity for
    ``None``)."""
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(F32)


def rmsnorm(scale: jnp.ndarray, x: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def inv_frequencies(d: int, rope: dict) -> jnp.ndarray:
    """Default RoPE, or YaRN's blend of interpolated and original
    frequencies (HF ``_compute_yarn_parameters``)."""
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (jnp.arange(0, d, 2, dtype=F32) / d)
    if rope.get("rope_type", "default") != "yarn":
        return inv
    orig = float(rope["original_max_position_embeddings"])

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rope["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    extrapolate = 1.0 - ramp
    return inv / float(rope["factor"]) * (1.0 - extrapolate) \
        + inv * extrapolate


def rope(x: jnp.ndarray, positions: jnp.ndarray, rp: dict) -> jnp.ndarray:
    """x (S, heads, d), rotate-half pairs (i, i + d/2)."""
    d = x.shape[-1]
    ang = positions[:, None].astype(F32) * inv_frequencies(d, rp)[None]
    scale = float(rp.get("attention_factor", 1.0)) \
        if rp.get("rope_type") == "yarn" else 1.0
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend_exact(q, k, v, rows, window, block, act=None):
    """Causal softmax attention of query rows ``rows`` (a multiple of
    ``block``) against all keys, windowed when ``window`` is set: keys j
    with 0 ≤ i − j < window.  q (R, H, d), k/v (S, KV, d).  ``act`` rounds
    the softmax weights before they multiply the values."""
    act = act or rounder(None)
    R, H, d = q.shape
    S, KV, _ = k.shape
    g = H // KV
    span = S if window is None else min(S, window + block)

    def one(i):
        r0 = i * block
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block)
        start = 0 if window is None else jnp.clip(r0 - window + 1, 0,
                                                  S - span)
        kb = jax.lax.dynamic_slice_in_dim(k, start, span)
        vb = jax.lax.dynamic_slice_in_dim(v, start, span)
        i_pos = r0 + jnp.arange(block)[:, None]
        j_pos = start + jnp.arange(span)[None, :]
        ok = j_pos <= i_pos
        if window is not None:
            ok &= i_pos - j_pos < window
        s = jnp.einsum("qkgd,jkd->kgqj", qb.reshape(block, KV, g, d), kb)
        s = jnp.where(ok, s / math.sqrt(d), -jnp.inf)
        w = act(jax.nn.softmax(s, axis=-1))
        o = jnp.einsum("kgqj,jkd->qkgd", w, vb)
        return o.reshape(block, H, d)

    return jax.lax.map(one, jnp.arange(R // block)).reshape(R, H, d)


# ---------------------------------------------------------------------------
# the landmark factors (the paper's fast model on the softmax Gram)
# ---------------------------------------------------------------------------

def pinv(A: jnp.ndarray) -> jnp.ndarray:
    """Moore–Penrose inverse by SVD, cutting singular values at
    max(shape)·eps(f32) of the largest."""
    u, s, vt = jnp.linalg.svd(A, full_matrices=False)
    cut = max(A.shape) * float(jnp.finfo(F32).eps) * jnp.max(s)
    return (vt.T * jnp.where(s > cut, 1.0 / s, 0.0)) @ u.T


def landmark_build(k: jnp.ndarray, v: jnp.ndarray, key: jax.Array, c: int,
                   theta: int) -> dict:
    """One KV head's factors from its prompt keys and values (n, d)."""
    n, d = k.shape
    kp, ks = jax.random.split(key)
    seg = n // c
    idx = jnp.clip(jnp.arange(c) * seg
                   + jax.random.randint(kp, (c,), 0, max(seg, 1)), 0, n - 1)
    s = min(theta * c, n)
    free = jnp.ones((n,), F32).at[idx].set(0.0)
    sk = jnp.concatenate([idx, jax.random.choice(
        ks, n, (s - c,), replace=False, p=free / jnp.sum(free))])
    kl = k[idx]
    off = jnp.max(kl @ kl.T) / math.sqrt(d)

    def gram(a, b):
        return jnp.exp(a @ b.T / math.sqrt(d) - off)

    R = gram(kl, k)                                          # (c, n)
    U = pinv(gram(k[sk], kl)) @ gram(k[sk], k[sk]) @ pinv(R[:, sk])
    return {"k_land": kl, "U": U, "UV": U @ (R @ v),
            "U1": U @ jnp.sum(R, axis=1), "offset": off}


def landmark_decode(st: dict, q, k, v, held):
    """Fold the decoded keys and values (T, d) one at a time and read each
    token's queries q (T, g, d) after its own fold.  ``held`` rounds the
    factors to the precision they are kept in between steps."""
    d = k.shape[-1]

    def step(st, xs):
        qt, kt, vt = xs
        s = st["k_land"] @ kt / math.sqrt(d)
        off = jnp.maximum(st["offset"], jnp.max(s))
        ur = st["U"] @ jnp.exp(s - off)
        resc = jnp.exp(st["offset"] - off)
        st = dict(st, UV=held(st["UV"] * resc + ur[:, None] * vt[None]),
                  U1=held(st["U1"] * resc + ur), offset=off)
        cvec = jnp.exp(qt @ st["k_land"].T / math.sqrt(d) - off)
        den = cvec @ st["U1"]
        den = jnp.where(den < 0, -1.0, 1.0) * jnp.maximum(jnp.abs(den), 1e-6)
        return st, (cvec @ st["UV"]) / den[:, None]

    return jax.lax.scan(step, st, (q, k, v))


def replay(k, v, q_dec, k_dec, v_dec, key, c, theta, state_dtype=F32):
    """A full layer's landmark attention at its decode positions: each KV
    head's factors built from its prompt keys and values k, v (P, KV, d),
    then the decoded k_dec, v_dec (T, KV, d) folded in one at a time, each
    token's queries q_dec (T, H, d) reading after its own fold; head j draws
    from ``split(key, KV)[j]``.  Returns (outputs (T, H, d), the factors
    after the prompt and after the last token, each stacked over KV
    heads)."""
    T, H, d = q_dec.shape
    KV = k.shape[1]
    held = (lambda a: a.astype(state_dtype).astype(F32))

    def head(kh, vh, qh, kk, vd, key_h):
        st = landmark_build(kh, vh, key_h, c, theta)
        st = {n: held(a) if n != "offset" else a for n, a in st.items()}
        fin, od = landmark_decode(st, qh, kk, vd, held)
        return od, st, fin

    t = lambda a: a.astype(F32).transpose(1, 0, 2)
    qd = q_dec.astype(F32).reshape(T, KV, H // KV, d).transpose(1, 0, 2, 3)
    od, setup, final = jax.vmap(head)(t(k), t(v), qd, t(k_dec), t(v_dec),
                                      jax.random.split(key, KV))
    return od.transpose(1, 0, 2, 3).reshape(T, H, d), setup, final


@functools.partial(jax.jit, static_argnames=("c", "theta", "state_dtype"))
def _replay_jit(k, v, q_dec, k_dec, v_dec, key, *, c, theta, state_dtype):
    with jax.default_matmul_precision("highest"):
        return replay(k, v, q_dec, k_dec, v_dec, key, c, theta, state_dtype)


def landmark_replay(config: dict, key: jax.Array, taps: dict, row: int = 0,
                    state_dtype=F32) -> list:
    """The full layers' landmark attention replayed on the program's own
    inputs (teacher-forced, like the tokens): ``taps`` holds, per full
    layer in depth order, the prompt's keys and values ``landmark_k``,
    ``landmark_v`` (layers, P, KV, d) and the decode steps' queries, keys
    and values ``landmark_q`` (layers, T, H, d), ``landmark_k_dec``,
    ``landmark_v_dec`` (layers, T, KV, d).  Keys are drawn as the program's
    prefill draws them for batch row ``row`` of a prefill under ``key``.
    Returns one (outputs, setup, final) per full layer (see ``replay``)."""
    types = config["layer_types"]
    keys = layer_keys(key, config, row)
    full = [l for l, t in enumerate(types) if t == "full_attention"]
    return [_replay_jit(taps["landmark_k"][i], taps["landmark_v"][i],
                        taps["landmark_q"][i], taps["landmark_k_dec"][i],
                        taps["landmark_v_dec"][i], keys[l],
                        c=config["landmark_c"], theta=config["landmark_theta"],
                        state_dtype=state_dtype)
            for i, l in enumerate(full)]


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "kind", "cfg", "prompt_len", "block", "state_dtype", "act_dtype"))
def _layer(stacked, rep, x, key, experts, *, kind, cfg, prompt_len, block,
           state_dtype, act_dtype):
    """x (S_pad, hidden) f32 -> (x', routing margin), with the layer's weights ``stacked[rep]`` upcast to float32;
    ``experts`` (S_pad, k) or None as in ``moe``."""
    with jax.default_matmul_precision("highest"):
        return _layer_body(stacked, rep, x, key, experts, kind=kind, cfg=cfg,
                           prompt_len=prompt_len, block=block,
                           state_dtype=state_dtype, act=rounder(act_dtype))


def _layer_body(stacked, rep, x, key, experts, *, kind, cfg, prompt_len,
                block, state_dtype, act):
    (H, KV, d, window, eps, rp_local, rp_full, top_k, c, theta, S) = cfg
    rp_local, rp_full = dict(rp_local), dict(rp_full)
    p = jax.tree.map(lambda a: a[rep].astype(F32), stacked)
    pos = jnp.arange(x.shape[0])
    h = act(rmsnorm(p["norm1"]["scale"], x, eps))
    m = p["mixer"]
    q = act(jnp.einsum("sx,xhd->shd", h, m["wq"]))
    k = act(jnp.einsum("sx,xhd->shd", h, m["wk"]))
    v = act(jnp.einsum("sx,xhd->shd", h, m["wv"]))
    full = kind == "full_attention"
    rp = rp_full if full else rp_local
    q, k = act(rope(q, pos, rp)), act(rope(k, pos, rp))
    if not full:
        o = attend_exact(q, k, v, x.shape[0], window, block, act)
    else:
        P = prompt_len
        rows = -(-P // block) * block
        o_prompt = attend_exact(q[:rows], k[:P], v[:P], rows, None,
                                block, act)[:P]
        o_dec, _, _ = replay(k[:P], v[:P], q[P:S], k[P:S], v[P:S], key, c,
                             theta, state_dtype)
        pad = jnp.zeros((x.shape[0] - S, H, d), F32)
        o = jnp.concatenate([o_prompt, o_dec, pad])
    x = act(x + act(jnp.einsum("shd,hdx->sx", act(o), m["wo"])))
    h = act(rmsnorm(p["norm2"]["scale"], x, eps))
    y, margin = moe(p["moe"], h, top_k, act, experts,
                    jnp.arange(x.shape[0]) < S)
    return act(x + act(y)), margin


def moe(p: dict, x: jnp.ndarray, top_k: int, act=rounder(None),
        experts=None, valid=None):
    """(y, margin).  Each token takes its top-k experts by softmax router
    probability, or the ``experts`` (T, k) given for it (the program's
    routing, teacher-forced), weighted by its own probabilities of them
    renormalised.  ``margin`` is the largest, over the ``valid`` tokens, of
    (p_k − min p(chosen)) / p_k with p_k the k-th largest probability: 0
    when every token took a top-k, small for near-ties chosen apart."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if experts is not None:
        idx = experts
    w = jnp.take_along_axis(probs, idx, axis=-1)
    p_k = top[:, -1]
    margin = jnp.maximum((p_k - jnp.min(w, axis=-1)) / p_k, 0.0)
    margin = jnp.max(jnp.where(valid, margin, 0.0)) if valid is not None \
        else jnp.max(margin)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None],
                                     idx].set(w)

    def expert(y, e):
        wg, wu, wo, ge = e
        h = act(jax.nn.silu(x @ wg) * (x @ wu))
        return y + ge[:, None] * act(h @ wo), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["wi_gate"], p["wi_up"], p["wo"], gates.T))
    return y, margin


def forward(params: dict, config: dict, tokens: jnp.ndarray, prompt_len: int,
            key: jax.Array, row: int = 0, state_dtype=F32, act_dtype=None,
            experts=None, block: int = 256):
    """tokens (S,) int: the prompt's ``prompt_len`` then the decoded ones,
    of the session in batch row ``row`` of a prefill under ``key``.

    Returns (logits (S − prompt_len + 1, vocab) f32 at the prompt's last
    position and every decode position, the routing margin of each layer
    (see ``moe``)).

    ``experts`` (layers, S, k), where given, is the routing each token
    takes in each layer (the program's, teacher-forced like the tokens):
    a token routed to other experts than the program's would take a wholly
    different FFN output for a difference of a rounding in its router's
    input, so the margin checks the program's routing instead."""
    S = int(tokens.shape[0])
    rps = config["rope_parameters"]
    cfg = (config["num_attention_heads"], config["num_key_value_heads"],
           config["head_dim"], config["sliding_window"],
           config["rms_norm_eps"],
           tuple(sorted(rps["sliding_attention"].items())),
           tuple(sorted(rps["full_attention"].items())),
           config["num_experts_per_tok"], config["landmark_c"],
           config["landmark_theta"], S)
    block = min(block, -(-S // 8) * 8)
    S_pad = -(-S // block) * block
    keys = layer_keys(key, config, row)
    margins = []
    act = rounder(act_dtype)
    if experts is not None:                  # pad rows take experts 0..k-1
        experts = jnp.asarray(experts, jnp.int32)
        fill = jnp.broadcast_to(jnp.arange(experts.shape[-1], dtype=jnp.int32),
                                (experts.shape[0], S_pad - S,
                                 experts.shape[-1]))
        experts = jnp.concatenate([experts, fill], axis=1)
    with jax.default_matmul_precision("highest"):
        x = act(params["embed"]["embedding"][tokens].astype(F32))
        x = jnp.pad(x, ((0, S_pad - S), (0, 0)))
        for l, (kind, stacked, rep) in enumerate(layers_of(params, config)):
            x, margin = _layer(
                stacked, rep, x, keys[l],
                None if experts is None else experts[l], kind=kind, cfg=cfg,
                prompt_len=prompt_len, block=block, state_dtype=state_dtype,
                act_dtype=act_dtype)
            margins.append(margin)
        h = act(rmsnorm(params["final_norm"]["scale"], x[prompt_len - 1:S],
                        config["rms_norm_eps"]))
        logits = h @ params["embed"]["unembed"].astype(F32)
    return logits, jnp.stack(margins)
