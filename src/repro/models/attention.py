"""Attention mixers: GQA / MQA / sliding-window / MLA / cross / landmark-decode.

Layouts: activations are (B, S, d_model); heads live in (B, S, H, D) einsums so
the 'heads' axis is shardable over the mesh 'model' axis.  ``attn_impl``
selects the XLA einsum path (default; what the dry-run lowers) or the Pallas
flash kernel (TPU target, validated in interpret mode).

Decode caches (one per layer; stacked over scanned layers):

- full / global : {"k": (B, Smax, KV, D), "v": ...}           (pos passed in)
- local         : ring buffer {"k": (B, W, KV, D), "v": ...}
- MLA           : {"ckv": (B, Smax, R), "krope": (B, Smax, Dr)} — the latent
                  cache *is* a learned sketch of the KV Gram
- landmark      : the paper's fast-model factors per KV head, in f32:
                  {"k_land": (B, KV, c, D), "u": (B, KV, c, c),
                   "uv": (B, KV, c, Dv), "u1": (B, KV, c), "offset": (B, KV)}
                  — every decoded key and value is folded into uv/u1
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.instrument import span
from repro.distributed import sharding as shd
from repro.models import layers as L


def _sp_active(cfg: ModelConfig, S: int) -> bool:
    """Sequence-parallel attention: only when heads don't divide the TP axis
    (otherwise head sharding is strictly better) and positions do."""
    if not cfg.seq_parallel_attn or S <= 1:
        return False
    tp = shd.ambient_axis_size("model")
    return tp > 1 and cfg.n_heads % tp != 0 and S % tp == 0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig,
                   cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    if cfg.use_mla and not cross:
        p = {
            "wq_a": L.dense_init(ks[0], (d, cfg.q_lora_rank), cfg.pdtype),
            "q_norm": L.init_rmsnorm(cfg.q_lora_rank, cfg.pdtype),
            "wq_b": L.dense_init(
                ks[1], (cfg.q_lora_rank, h, cfg.qk_nope_dim + cfg.qk_rope_dim),
                cfg.pdtype),
            "wkv_a": L.dense_init(
                ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim), cfg.pdtype),
            "kv_norm": L.init_rmsnorm(cfg.kv_lora_rank, cfg.pdtype),
            "wkv_b": L.dense_init(
                ks[3], (cfg.kv_lora_rank, h, cfg.qk_nope_dim + cfg.v_head_dim),
                cfg.pdtype),
            "wo": L.dense_init(ks[4], (h, cfg.v_head_dim, d), cfg.pdtype),
        }
        return p
    p = {
        "wq": L.dense_init(ks[0], (d, h, hd), cfg.pdtype),
        "wk": L.dense_init(ks[1], (d, kv, hd), cfg.pdtype),
        "wv": L.dense_init(ks[2], (d, kv, hd), cfg.pdtype),
        "wo": L.dense_init(ks[3], (h, hd, d), cfg.pdtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd, cfg.pdtype)
        p["k_norm"] = L.init_rmsnorm(hd, cfg.pdtype)
    return p


# ---------------------------------------------------------------------------
# qkv projections
# ---------------------------------------------------------------------------

def _qkv(params: dict, cfg: ModelConfig, x: jnp.ndarray,
         positions: jnp.ndarray, kind: str):
    dt = cfg.cdtype
    theta, yarn = L.rope_for(cfg, kind)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = L.apply_rope(q.swapaxes(1, 2), positions, theta,
                     yarn).swapaxes(1, 2)
    k = L.apply_rope(k.swapaxes(1, 2), positions, theta,
                     yarn).swapaxes(1, 2)
    return q, k, v


NEG = -1e30
# dense path only when the full (Sq, Sk) score panel is small; otherwise a
# q-block scan (XLA-flash) keeps the transient at (B, H, bq, Sk)
CHUNK_Q = 1024
DENSE_LIMIT = 2048 * 2048


def _weighted_values(spec: str, w: jnp.ndarray, v: jnp.ndarray):
    """The softmax weights (f32) times the values, accumulated in f32: the
    weights are rounded to the values' dtype first, the product a TPU makes
    of an f32 operand at default precision, stated for every backend."""
    return jnp.einsum(spec, w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _blk_attend(qb: jnp.ndarray, kb: jnp.ndarray, vb: jnp.ndarray,
                row_ids: jnp.ndarray, col_ids: jnp.ndarray, *,
                scale: float, causal: bool, window: Optional[int],
                kv_valid: Optional[jnp.ndarray]) -> jnp.ndarray:
    """One score panel. qb (B,bq,H,D), kb/vb (B,L,H,D); ids are absolute
    token positions (masks are *computed*, never materialized globally)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", qb.astype(jnp.float32),
                        kb.astype(jnp.float32)) * scale
    m = jnp.ones((row_ids.shape[0], col_ids.shape[0]), bool)
    if causal:
        m &= col_ids[None, :] <= row_ids[:, None]
    if window is not None:
        m &= (row_ids[:, None] - col_ids[None, :]) < window
    logits = jnp.where(m[None, None], logits, NEG)
    if kv_valid is not None:                      # (B or 1, L) key validity
        logits = jnp.where(kv_valid[:, None, None, :], logits, NEG)
    w = jax.nn.softmax(logits, axis=-1)
    return _weighted_values("bhqk,bkhd->bqhd", w, vb)


def _gqa_decode_read(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     cfg: ModelConfig,
                     kv_valid: Optional[jnp.ndarray]) -> jnp.ndarray:
    """q (B,1,H,D), k/v (B,Sk,KV,Dv) -> (B,1,H,Dv) without repeating KV."""
    B, _, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (D ** 0.5)
    if kv_valid is not None:
        logits = jnp.where(kv_valid[:, None, None, :], logits, NEG)
    w = jax.nn.softmax(logits, axis=-1)
    out = _weighted_values("bkgs,bskd->bkgd", w, v)
    return out.reshape(B, 1, H, v.shape[-1]).astype(cfg.cdtype)


def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, cfg: ModelConfig,
          *, causal: bool = True, window: Optional[int] = None,
          offs: Optional[int] = None,
          kv_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B,Sq,H,D); k,v: (B,Sk,KV,D) -> (B,Sq,H,Dv).

    GQA broadcasts KV to H (shards cleanly: 'heads'->model).  offs aligns
    queries to keys (decode: Sk - Sq).  Masks are computed per block from
    position iotas; ``kv_valid`` is an optional (B|1, Sk) key-validity row
    (decode cache bounds / ring buffers).
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    if offs is None:
        offs = Sk - Sq
    G = H // KV
    if Sq == 1 and G > 1:
        # grouped decode read: the KV cache is read ONCE per step instead of
        # materializing a G-times repeated copy (§Perf-C iteration 3 — the
        # decode memory term is dominated by exactly this read)
        return _gqa_decode_read(q, k, v, cfg, kv_valid=kv_valid)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    scale = 1.0 / (D ** 0.5)
    rows = jnp.arange(Sq) + offs
    cols = jnp.arange(Sk)
    sp = _sp_active(cfg, Sq)
    sp_spec = P(None, "model", None, None)
    CHUNK_Q = cfg.chunk_q

    if Sq * Sk <= DENSE_LIMIT or Sq % CHUNK_Q != 0:
        if sp:
            q = shd.constrain(q, sp_spec)
        out = _blk_attend(q, k, v, rows, cols, scale=scale, causal=causal,
                          window=window, kv_valid=kv_valid)
        if sp:
            out = shd.constrain(out, sp_spec)
        return out.astype(cfg.cdtype)

    nb = Sq // CHUNK_Q
    qb = q.reshape(B, nb, CHUNK_Q, H, q.shape[-1]).swapaxes(0, 1)
    rb = rows.reshape(nb, CHUNK_Q)
    sp_blk = _sp_active(cfg, CHUNK_Q)

    if window is not None and Sk > 2 * (window + CHUNK_Q):
        # banded local attention: slice only the keys the window can reach
        L = window + CHUNK_Q
        L = -(-L // 128) * 128

        def body(_, xs):
            qi, ri = xs
            if sp_blk:
                qi = shd.constrain(qi, sp_spec)
            start = jnp.clip(ri[0] - window + 1, 0, Sk - L)
            kb = jax.lax.dynamic_slice_in_dim(k, start, L, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, L, axis=1)
            ci = start + cols[:L]
            kvv = None if kv_valid is None else \
                jax.lax.dynamic_slice_in_dim(kv_valid, start, L, axis=1)
            o = _blk_attend(qi, kb, vb, ri, ci, scale=scale, causal=causal,
                            window=window, kv_valid=kvv)
            if sp_blk:
                o = shd.constrain(o, sp_spec)
            return None, o
    else:
        def body(_, xs):
            qi, ri = xs
            if sp_blk:
                qi = shd.constrain(qi, sp_spec)
            o = _blk_attend(qi, k, v, ri, cols, scale=scale, causal=causal,
                            window=window, kv_valid=kv_valid)
            if sp_blk:
                o = shd.constrain(o, sp_spec)
            return None, o

    if cfg.unroll_scans and nb <= 64:
        blocks = [body(None, (qb[i], rb[i]))[1] for i in range(nb)]
        ob = jnp.stack(blocks)
    else:
        _, ob = jax.lax.scan(body, None, (qb, rb))
    out = ob.swapaxes(0, 1).reshape(B, Sq, H, v.shape[-1])
    return out.astype(cfg.cdtype)


# ---------------------------------------------------------------------------
# full-sequence self-attention (train / prefill)
# ---------------------------------------------------------------------------

def attention_full(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                   positions: jnp.ndarray, kind: str = "attn") -> jnp.ndarray:
    if cfg.use_mla:
        return _mla_full(params, cfg, x, positions)
    sp = _sp_active(cfg, x.shape[1])
    if sp:
        # heads-misfit: shard query positions over 'model' so the q/k/v/o
        # projections and the score panels are TP-parallel in the sequence
        x = shd.constrain(x, P(None, "model", None))
    q, k, v = _qkv(params, cfg, x, positions, kind)
    if sp:
        q = shd.constrain(q, P(None, "model", None, None))
        # keys/values: ONE explicit all-gather per layer (batch stays on the
        # DP axes, 'model' replicated) — without this GSPMD re-gathers the
        # seq-sharded K/V inside every q-block of the scan (iteration B1
        # measured 5.1 TB of all-gather; B2 makes the gather per-layer)
        dp = tuple(a for a in ("pod", "data")
                   if shd.ambient_axis_size(a) > 1)
        kv_spec = P(dp if dp else None, None, None, None)
        k = shd.constrain(k, kv_spec)
        v = shd.constrain(v, kv_spec)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        window = cfg.window if kind == "local" else None
        out = fa_ops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, window=window)
        out = out.transpose(0, 2, 1, 3)
    else:
        window = cfg.window if kind == "local" else None
        out = _sdpa(q, k, v, cfg, causal=True, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cfg.cdtype))
    if sp:
        y = shd.constrain(y, P(None, "model", None))
    return y


def _mla_full(params: dict, cfg: ModelConfig, x: jnp.ndarray,
              positions: jnp.ndarray) -> jnp.ndarray:
    dt = cfg.cdtype
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim

    ql = L.rmsnorm(params["q_norm"], x @ params["wq_a"].astype(dt),
                   cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_b"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope.swapaxes(1, 2), positions,
                          cfg.rope_theta).swapaxes(1, 2)

    kv_a = x @ params["wkv_a"].astype(dt)                    # (B,S,R+dr)
    ckv = L.rmsnorm(params["kv_norm"], kv_a[..., :cfg.kv_lora_rank],
                    cfg.norm_eps)
    k_rope = L.apply_rope(kv_a[..., None, cfg.kv_lora_rank:].swapaxes(1, 2),
                          positions, cfg.rope_theta).swapaxes(1, 2)  # (B,S,1,dr)
    kvb = jnp.einsum("bsr,rhk->bshk", ckv, params["wkv_b"].astype(dt))
    k_nope, v = kvb[..., :dn], kvb[..., dn:]

    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, dr))], axis=-1)
    out = _sdpa(qf, kf, v, cfg, causal=True)                 # (B,S,H,dv)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attention(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                    enc_k: jnp.ndarray, enc_v: jnp.ndarray) -> jnp.ndarray:
    """enc_k/enc_v: (B, S_enc, KV, D) precomputed from encoder output."""
    dt = cfg.cdtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q, cfg.norm_eps)
    out = _sdpa(q, enc_k, enc_v, cfg, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dt))


def encoder_kv(params: dict, cfg: ModelConfig, enc_out: jnp.ndarray):
    dt = cfg.cdtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(dt))
    if cfg.qk_norm:
        k = L.rmsnorm(params["k_norm"], k, cfg.norm_eps)
    return k, v


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> dict:
    """Zero-initialized cache struct for one layer (shapes only matter
    for the dry-run; serve.py fills them via prefill)."""
    dt = cfg.cdtype
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.use_mla and kind in ("attn", "global"):
        return {"ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
                "krope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt)}
    if kind == "local" and cfg.window is not None:
        w = min(cfg.window, max_len)
        return {"k": jnp.zeros((batch, w, kv, hd), dt),
                "v": jnp.zeros((batch, w, kv, hd), dt)}
    if kind == "global" and cfg.use_landmark_decode:
        c, f32 = cfg.landmark_c, jnp.float32
        return {"k_land": jnp.zeros((batch, kv, c, hd), f32),
                "u": jnp.zeros((batch, kv, c, c), f32),
                "uv": jnp.zeros((batch, kv, c, hd), f32),
                "u1": jnp.zeros((batch, kv, c), f32),
                "offset": jnp.zeros((batch, kv), f32)}
    return {"k": jnp.zeros((batch, max_len, kv, hd), dt),
            "v": jnp.zeros((batch, max_len, kv, hd), dt)}


def cache_spec(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> dict:
    """ShapeDtypeStruct version of init_cache (dry-run, no allocation)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.eval_shape(lambda: init_cache(cfg, kind, batch, max_len)))


# ---------------------------------------------------------------------------
# decode steps
# ---------------------------------------------------------------------------

def attention_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                     cache: dict, pos: jnp.ndarray,
                     kind: str = "attn") -> Tuple[jnp.ndarray, dict]:
    """x: (B, 1, d). pos: scalar current position. Returns (y, new_cache)."""
    if cfg.use_mla and kind in ("attn", "global"):
        return _mla_decode(params, cfg, x, cache, pos)
    positions = pos[None]
    q, k_new, v_new = _qkv(params, cfg, x, positions, kind)
    if kind == "global" and cfg.use_landmark_decode and "k_land" in cache:
        return _landmark_decode(params, cfg, q, k_new, v_new, cache)

    if kind == "local" and cfg.window is not None:
        W = cache["k"].shape[1]
        slot = pos % W
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
        j = jnp.arange(W)
        slot_pos = pos - ((pos - j) % W)
        valid = ((slot_pos >= 0) & (slot_pos <= pos))[None]  # (1, W)
        out = _sdpa(q, k_cache, v_cache, cfg, causal=False, kv_valid=valid)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
        S = k_cache.shape[1]
        valid = (jnp.arange(S) <= pos)[None]                 # (1, S)
        out = _sdpa(q, k_cache, v_cache, cfg, causal=False, kv_valid=valid)
        new_cache = {"k": k_cache, "v": v_cache}

    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cfg.cdtype))
    return y, new_cache


def _mla_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                cache: dict, pos: jnp.ndarray) -> Tuple[jnp.ndarray, dict]:
    """Absorbed MLA decode: attend in the latent (kv_lora) space.

    The latent cache ckv is exactly a *learned* c-dimensional sketch of the
    K/V Gram — the architectural cousin of the paper's C = KP.
    """
    dt = cfg.cdtype
    dn, dr, dv, R = (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    positions = pos[None]

    ql = L.rmsnorm(params["q_norm"], x @ params["wq_a"].astype(dt),
                   cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, params["wq_b"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope.swapaxes(1, 2), positions,
                          cfg.rope_theta).swapaxes(1, 2)

    kv_a = x @ params["wkv_a"].astype(dt)
    ckv_new = L.rmsnorm(params["kv_norm"], kv_a[..., :R], cfg.norm_eps)
    krope_new = L.apply_rope(kv_a[..., None, R:].swapaxes(1, 2), positions,
                             cfg.rope_theta).swapaxes(1, 2)[:, :, 0]  # (B,1,dr)

    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], ckv_new.astype(cache["ckv"].dtype), pos, axis=1)
    krope = jax.lax.dynamic_update_slice_in_dim(
        cache["krope"], krope_new.astype(cache["krope"].dtype), pos, axis=1)

    wkv_b = params["wkv_b"].astype(dt)                       # (R, H, dn+dv)
    w_k, w_v = wkv_b[..., :dn], wkv_b[..., dn:]
    if cfg.mla_absorb:
        # q W_k^T: (B,1,H,dn) x (R,H,dn) -> (B,1,H,R); attend against ckv.
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, w_k)
        s_lat = jnp.einsum("bshr,btr->bhst", q_lat.astype(jnp.float32),
                           ckv.astype(jnp.float32))
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                            krope.astype(jnp.float32))
        logits = (s_lat + s_rope) / ((dn + dr) ** 0.5)
        S = ckv.shape[1]
        mask = (jnp.arange(S) <= pos)[None, None, None, :]
        logits = jnp.where(mask, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)                  # (B,H,1,S)
        o_lat = jnp.einsum("bhst,btr->bshr", w, ckv.astype(jnp.float32))
        out = jnp.einsum("bshr,rhk->bshk", o_lat, w_v.astype(jnp.float32))
    else:
        k_nope = jnp.einsum("btr,rhk->bthk", ckv, w_k)
        v = jnp.einsum("btr,rhk->bthk", ckv, w_v)
        kf = jnp.concatenate([k_nope, jnp.broadcast_to(
            krope[:, :, None], k_nope.shape[:3] + (dr,))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        S = ckv.shape[1]
        valid = (jnp.arange(S) <= pos)[None]
        out = _sdpa(qf, kf, v, cfg, causal=False, kv_valid=valid)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(dt),
                   params["wo"].astype(dt))
    return y, {"ckv": ckv, "krope": krope}


def _landmark_decode(params: dict, cfg: ModelConfig, q: jnp.ndarray,
                     k_new: jnp.ndarray, v_new: jnp.ndarray,
                     cache: dict) -> Tuple[jnp.ndarray, dict]:
    """One decode step against the paper's fast-model factors: the new key
    and value (B, 1, KV, D) are folded into every (session, KV head)'s
    factors as one more column of R̂, and the group's queries read the
    updated factors — one landmark-attention launch over B·KV factor sets,
    O(c·D + c²) each, whatever the context length."""
    from repro.kernels.landmark_attention import ops as lm_ops
    B, _, H, D = q.shape
    KV = cfg.n_kv_heads
    N = B * KV
    c = cache["u1"].shape[-1]
    out, (uv, u1, off) = lm_ops.landmark_step(
        q.reshape(N, H // KV, D), cache["k_land"].reshape(N, c, D),
        cache["u"].reshape(N, c, c), cache["uv"].reshape(N, c, -1),
        cache["u1"].reshape(N, c), cache["offset"].reshape(N),
        k_new.reshape(N, D), v_new.reshape(N, -1))
    new_cache = dict(cache, uv=uv.reshape(cache["uv"].shape),
                     u1=u1.reshape(B, KV, c), offset=off.reshape(B, KV))
    out = out.reshape(B, 1, H, out.shape[-1]).astype(cfg.cdtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cfg.cdtype))
    return y, new_cache


@span("landmark.build")
def build_landmark_cache(cfg: ModelConfig, k: jnp.ndarray, v: jnp.ndarray,
                         key: jax.Array) -> dict:
    """Prefill-side construction of the landmark cache from full K/V
    (B, S, KV, D): the paper's Algorithm 1 applied to the softmax Gram,
    batched over (B, KV), in f32.  Head j of session b draws its landmarks
    and sketch from ``split(fold_in(key, b), KV)[j]``."""
    from repro.core.sketched_attention import build_landmark_state

    def one(kh, vh, kk):
        st = build_landmark_state(
            kh, vh, kk, c=cfg.landmark_c, theta=cfg.landmark_theta,
            selection=cfg.landmark_selection)
        return st.k_land, st.U, st.UV, st.U1, st.scale

    B, S, KV, D = k.shape
    # session b's heads draw from fold_in(key, b): a session's landmarks do
    # not depend on the batch it was prefilled in
    keys = jax.vmap(lambda b: jax.random.split(jax.random.fold_in(key, b),
                                               KV))(jnp.arange(B))
    kt = k.transpose(0, 2, 1, 3)                             # (B,KV,S,D)
    vt = v.transpose(0, 2, 1, 3)
    k_land, u, uv, u1, off = jax.vmap(jax.vmap(one))(kt, vt, keys)
    return {"k_land": k_land, "u": u, "uv": uv, "u1": u1, "offset": off}
