"""Block assembly: residual blocks, scanned stacks, decoder-only LM, enc-dec.

A model is ``prefix blocks`` (unscanned, e.g. deepseek's first-3 dense) +
``R`` scanned *superblocks* (one pass through cfg.layer_pattern) +
``remainder blocks`` (pattern prefix, e.g. recurrentgemma's trailing 2).

Every block kind exposes three modes:
  train   : (x) -> (x', aux)
  prefill : (x) -> (x', aux, cache_entry)   cache sized ``max_len``
  decode  : (x, cache_entry, pos) -> (x', cache_entry')
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R


ATTN_KINDS = ("attn", "local", "global")
REC_KINDS = ("mlstm", "slstm", "rglru")


def _is_moe_layer(cfg: ModelConfig, in_prefix: bool) -> bool:
    return cfg.n_experts > 0 and not in_prefix


def _has_mlp(cfg: ModelConfig, kind: str, moe: bool) -> bool:
    if kind in ("mlstm", "slstm"):
        return False                                         # xLSTM blocks
    return moe or cfg.d_ff > 0 or cfg.dense_d_ff > 0


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ModelConfig, kind: str,
               moe: bool, dense_ff: Optional[int] = None) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": L.init_rmsnorm(cfg.d_model, cfg.pdtype)}
    if kind in ATTN_KINDS:
        p["mixer"] = A.init_attention(k1, cfg)
    elif kind == "mlstm":
        p["mixer"] = R.init_mlstm(k1, cfg)
    elif kind == "slstm":
        p["mixer"] = R.init_slstm(k1, cfg)
    elif kind == "rglru":
        p["mixer"] = R.init_rglru(k1, cfg)
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        p["post_norm1"] = L.init_rmsnorm(cfg.d_model, cfg.pdtype)
    if _has_mlp(cfg, kind, moe):
        p["norm2"] = L.init_rmsnorm(cfg.d_model, cfg.pdtype)
        if moe:
            p["moe"] = M.init_moe(k2, cfg)
        else:
            p["mlp"] = L.init_mlp(k3, cfg, d_ff=dense_ff)
        if cfg.post_norm:
            p["post_norm2"] = L.init_rmsnorm(cfg.d_model, cfg.pdtype)
    return p


def _mixer_full(params: dict, cfg: ModelConfig, kind: str, x: jnp.ndarray,
                positions: jnp.ndarray, causal: bool = True) -> jnp.ndarray:
    if kind in ATTN_KINDS:
        if not causal:
            return _encoder_attention(params, cfg, x, positions)
        return A.attention_full(params, cfg, x, positions, kind)
    if kind == "mlstm":
        return R.mlstm_full(params, cfg, x)
    if kind == "slstm":
        return R.slstm_full(params, cfg, x)
    return R.rglru_full(params, cfg, x)


def _encoder_attention(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                       positions: jnp.ndarray) -> jnp.ndarray:
    """Bidirectional self-attention (whisper encoder)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd
    sp = A._sp_active(cfg, x.shape[1])
    if sp:
        x = shd.constrain(x, P(None, "model", None))
    q, k, v = A._qkv(params, cfg, x, positions, "attn")
    out = A._sdpa(q, k, v, cfg, causal=False)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cfg.cdtype))
    if sp:
        y = shd.constrain(y, P(None, "model", None))
    return y


def block_full(params: dict, cfg: ModelConfig, kind: str, moe: bool,
               x: jnp.ndarray, positions: jnp.ndarray,
               causal: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    h = _mixer_full(params["mixer"], cfg, kind,
                    L.rmsnorm(params["norm1"], x, cfg.norm_eps),
                    positions, causal)
    if cfg.post_norm:
        h = L.rmsnorm(params["post_norm1"], h, cfg.norm_eps)
    h = checkpoint_name(h, "mixer_out")
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if "mlp" in params or "moe" in params:
        h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
        if "moe" in params:
            h, aux = M.moe_ffn(params["moe"], cfg, h)
        else:
            h = L.mlp(params["mlp"], cfg, h)
        if cfg.post_norm:
            h = L.rmsnorm(params["post_norm2"], h, cfg.norm_eps)
        h = checkpoint_name(h, "mlp_out")
        x = x + h
    return x, aux


# --- prefill: block_full + cache construction ------------------------------

def block_prefill(params: dict, cfg: ModelConfig, kind: str, moe: bool,
                  x: jnp.ndarray, positions: jnp.ndarray, max_len: int,
                  key: jax.Array) -> Tuple[jnp.ndarray, dict]:
    """Returns (x', cache_entry, taps).  Shares compute structure with
    block_full.  ``taps`` is what the block exposes of its work: a MoE
    layer's ``experts`` (B, S, k), the experts each token was routed to; a
    landmark layer's ``landmark_k`` and ``landmark_v`` (B, S, KV, D), the
    keys and values its factors were built from."""
    B, S, _ = x.shape
    xin = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    taps = {}
    if kind in ATTN_KINDS:
        cache, taps = _attn_prefill_cache(params["mixer"], cfg, kind, xin,
                                          positions, max_len, key)
        h = A.attention_full(params["mixer"], cfg, xin, positions, kind)
    elif kind == "mlstm":
        h = R.mlstm_full(params["mixer"], cfg, xin)
        cache = _rec_prefill_state(params["mixer"], cfg, kind, xin)
    elif kind == "slstm":
        h = R.slstm_full(params["mixer"], cfg, xin)
        cache = _rec_prefill_state(params["mixer"], cfg, kind, xin)
    else:
        h = R.rglru_full(params["mixer"], cfg, xin)
        cache = _rec_prefill_state(params["mixer"], cfg, kind, xin)
    if cfg.post_norm:
        h = L.rmsnorm(params["post_norm1"], h, cfg.norm_eps)
    x, dispatch = _served_ffn(params, cfg, x + h)
    if dispatch is not None:
        taps["experts"] = dispatch["experts"]
    return x, cache, taps


def _served_ffn(params: dict, cfg: ModelConfig, x: jnp.ndarray):
    """The residual FFN half of a served block (prefill or decode): MoE
    layers dispatch dropless.  Returns (x', the MoE's dispatch record
    (``moe.moe_ffn_served``) or None)."""
    dispatch = None
    if "mlp" in params or "moe" in params:
        h = L.rmsnorm(params["norm2"], x, cfg.norm_eps)
        if "moe" in params:
            h, dispatch = M.moe_ffn_served(params["moe"], cfg, h)
        else:
            h = L.mlp(params["mlp"], cfg, h)
        if cfg.post_norm:
            h = L.rmsnorm(params["post_norm2"], h, cfg.norm_eps)
        x = x + h
    return x, dispatch


def _attn_prefill_cache(mp: dict, cfg: ModelConfig, kind: str,
                        xin: jnp.ndarray, positions: jnp.ndarray,
                        max_len: int, key: jax.Array):
    """(cache entry, taps: a landmark layer's keys and values)."""
    B, S, _ = xin.shape
    dt = cfg.cdtype
    if cfg.use_mla:
        kv_a = xin @ mp["wkv_a"].astype(dt)
        ckv = L.rmsnorm(mp["kv_norm"], kv_a[..., :cfg.kv_lora_rank],
                        cfg.norm_eps)
        krope = L.apply_rope(
            kv_a[..., None, cfg.kv_lora_rank:].swapaxes(1, 2), positions,
            cfg.rope_theta).swapaxes(1, 2)[:, :, 0]
        pad = max_len - S
        return {"ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                "krope": jnp.pad(krope, ((0, 0), (0, pad), (0, 0)))}, {}
    _, k, v = A._qkv(mp, cfg, xin, positions, kind)          # (B,S,KV,D)
    if kind == "global" and cfg.use_landmark_decode:
        return A.build_landmark_cache(cfg, k, v, key), \
            {"landmark_k": k, "landmark_v": v}
    if kind == "local" and cfg.window is not None:
        W = min(cfg.window, max_len)
        j = jnp.arange(W)
        src = jnp.maximum(S - W, 0) + j                      # last W positions
        src = jnp.clip(src, 0, S - 1)
        slots = src % W
        kw = jnp.take(k, src, axis=1)
        vw = jnp.take(v, src, axis=1)
        kr = jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(kw)
        vr = jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(vw)
        return {"k": kr, "v": vr}, {}
    pad = max_len - S
    return {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}, {}


def _rec_prefill_state(mp: dict, cfg: ModelConfig, kind: str,
                       xin: jnp.ndarray) -> dict:
    """Recompute the final recurrent state by a decode-scan over the input.

    O(S) like the parallel pass; keeps the *_full implementations scan-free.
    """
    B = xin.shape[0]
    if kind == "mlstm":
        state = R.init_mlstm_state(cfg, B)
        step = functools.partial(R.mlstm_decode, mp, cfg)
    elif kind == "slstm":
        state = R.init_slstm_state(cfg, B)
        step = functools.partial(R.slstm_decode, mp, cfg)
    else:
        state = R.init_rglru_state(cfg, B)
        step = functools.partial(R.rglru_decode, mp, cfg)

    def body(st, xt):
        _, st2 = step(xt[:, None], st)
        return st2, None

    st, _ = jax.lax.scan(body, state, xin.swapaxes(0, 1))
    return st


# --- decode ----------------------------------------------------------------

def block_decode(params: dict, cfg: ModelConfig, kind: str, moe: bool,
                 x: jnp.ndarray, cache: dict, pos: jnp.ndarray):
    """Returns (x', cache', taps): a MoE layer's dispatch record
    (``moe.moe_ffn_served``: ``sizes``, ``experts`` (B, 1, k),
    ``delivered``); a landmark layer's ``landmark_q`` (B, 1, H, D),
    ``landmark_k`` and ``landmark_v`` (B, 1, KV, D), the queries that read
    its factors and the key and value folded into them."""
    xin = L.rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind in ATTN_KINDS:
        h, cache = A.attention_decode(params["mixer"], cfg, xin, cache,
                                      pos, kind)
    elif kind == "mlstm":
        h, cache = R.mlstm_decode(params["mixer"], cfg, xin, cache)
    elif kind == "slstm":
        h, cache = R.slstm_decode(params["mixer"], cfg, xin, cache)
    else:
        h, cache = R.rglru_decode(params["mixer"], cfg, xin, cache)
    if cfg.post_norm:
        h = L.rmsnorm(params["post_norm1"], h, cfg.norm_eps)
    taps = {}
    if kind == "global" and cfg.use_landmark_decode and "k_land" in cache:
        q, k, v = A._qkv(params["mixer"], cfg, xin, pos[None], kind)
        taps = {"landmark_q": q, "landmark_k": k, "landmark_v": v}
    x, dispatch = _served_ffn(params, cfg, x + h)
    return x, cache, dict(taps, **(dispatch or {}))


def block_cache_shape(cfg: ModelConfig, kind: str, batch: int,
                      max_len: int):
    """eval_shape-able zero cache for one block (decode dry-run)."""
    if kind in ATTN_KINDS:
        return A.init_cache(cfg, kind, batch, max_len)
    if kind == "mlstm":
        return R.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return R.init_slstm_state(cfg, batch)
    return R.init_rglru_state(cfg, batch)


# ---------------------------------------------------------------------------
# stacks (prefix + scanned superblocks + remainder)
# ---------------------------------------------------------------------------

def stack_layout(cfg: ModelConfig):
    """-> (prefix_kinds, pattern, n_repeats, remainder_kinds)."""
    pattern = tuple(cfg.layer_pattern)
    prefix = tuple(pattern[i % len(pattern)]
                   for i in range(cfg.first_k_dense))
    n_rest = cfg.n_layers - cfg.first_k_dense
    reps = n_rest // len(pattern)
    remainder = pattern[: n_rest % len(pattern)]
    return prefix, pattern, reps, remainder


def init_stack(key: jax.Array, cfg: ModelConfig) -> dict:
    prefix, pattern, reps, remainder = stack_layout(cfg)
    kp, ks, kr = jax.random.split(key, 3)
    params = {}
    params["prefix"] = tuple(
        init_block(jax.random.fold_in(kp, i), cfg, kind, moe=False,
                   dense_ff=cfg.dense_d_ff or None)
        for i, kind in enumerate(prefix))

    def init_super(k):
        kk = jax.random.split(k, len(pattern))
        return tuple(init_block(kk[i], cfg, kind,
                                moe=_is_moe_layer(cfg, False))
                     for i, kind in enumerate(pattern))

    if reps > 0:
        if cfg.scan_layers:
            keys = jax.random.split(ks, reps)
            params["scanned"] = jax.vmap(init_super)(keys)
        else:
            params["scanned"] = [init_super(jax.random.fold_in(ks, i))
                                 for i in range(reps)]
    else:
        params["scanned"] = ()
    params["remainder"] = tuple(
        init_block(jax.random.fold_in(kr, i), cfg, kind,
                   moe=_is_moe_layer(cfg, False))
        for i, kind in enumerate(remainder))
    return params


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat == "save_io":
        # collective-aware remat: save the post-all-reduce mixer/mlp outputs
        # so the backward recompute does not re-run the forward TP
        # all-reduces (6/layer -> 4/layer AR volume) at the cost of two
        # bf16 (B_micro, S, d) residuals per layer
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "mixer_out", "mlp_out"))
    return jax.checkpoint(fn)


def stack_full(params: dict, cfg: ModelConfig, x: jnp.ndarray,
               positions: jnp.ndarray, causal: bool = True):
    """Train-mode stack. Returns (x, aux_sum)."""
    prefix, pattern, reps, remainder = stack_layout(cfg)
    aux = jnp.zeros((), jnp.float32)
    for p, kind in zip(params["prefix"], prefix):
        x, a = block_full(p, cfg, kind, False, x, positions, causal)
        aux = aux + a

    def super_body(carry, sb_params):
        h, ax = carry
        for i, kind in enumerate(pattern):
            h, a = block_full(sb_params[i], cfg, kind,
                              _is_moe_layer(cfg, False), h, positions, causal)
            ax = ax + a
        return (h, ax), None

    if reps > 0:
        body = _remat(cfg, super_body)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["scanned"])
        else:
            # unrolled path: same remat policy so the dry-run's layer-count
            # extrapolation (dryrun.py) measures the true per-layer cost
            for sb in params["scanned"]:
                (x, aux), _ = body((x, aux), sb)

    for p, kind in zip(params["remainder"], remainder):
        x, a = block_full(p, cfg, kind, _is_moe_layer(cfg, False), x,
                          positions, causal)
        aux = aux + a
    return x, aux


def _stack_taps(per_layer: list, lead: bool = False) -> dict:
    """Per-layer taps in depth order -> one dict: each key's arrays stacked
    over the layers that have it (``lead``: concatenated along an existing
    leading layer axis)."""
    keys = dict.fromkeys(k for t in per_layer for k in t)
    join = jnp.concatenate if lead else jnp.stack
    return {k: join([t[k] for t in per_layer if k in t]) for k in keys}


def _scanned_taps(taps: dict) -> dict:
    """(repeats, layers of a repeat, ...) -> (layers in depth order, ...)."""
    return {k: a.reshape((-1,) + a.shape[2:]) for k, a in taps.items()}


def _first(taps: dict) -> dict:
    return {k: a[None] for k, a in taps.items()}


def stack_prefill(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, max_len: int, key: jax.Array):
    """Returns (x, caches, taps): each block's taps (``block_prefill``)
    stacked over the layers that have them, in depth order."""
    prefix, pattern, reps, remainder = stack_layout(cfg)
    caches = {"prefix": [], "scanned": None, "remainder": []}
    taps = []
    for i, (p, kind) in enumerate(zip(params["prefix"], prefix)):
        x, c, t = block_prefill(p, cfg, kind, False, x, positions, max_len,
                                jax.random.fold_in(key, 1000 + i))
        caches["prefix"].append(c)
        taps.append(_first(t))

    def super_body(h, xs):
        sb_params, kd = xs
        kk = jax.random.wrap_key_data(kd)
        cs, ts = [], []
        for i, kind in enumerate(pattern):
            h, c, t = block_prefill(sb_params[i], cfg, kind,
                                    _is_moe_layer(cfg, False), h, positions,
                                    max_len, jax.random.fold_in(kk, i))
            cs.append(c)
            ts.append(t)
        return h, (tuple(cs), _stack_taps(ts))

    if reps > 0:
        keys = jax.random.key_data(jax.random.split(key, reps))
        if cfg.scan_layers:
            x, (sc, st) = jax.lax.scan(super_body, x,
                                       (params["scanned"], keys))
        else:
            sc_list = []
            for i, sb in enumerate(params["scanned"]):
                x, c = super_body(x, (sb, keys[i]))
                sc_list.append(c)
            sc, st = jax.tree.map(lambda *xs: jnp.stack(xs), *sc_list)
        caches["scanned"] = sc
        taps.append(_scanned_taps(st))

    for i, (p, kind) in enumerate(zip(params["remainder"], remainder)):
        x, c, t = block_prefill(p, cfg, kind, _is_moe_layer(cfg, False), x,
                                positions, max_len,
                                jax.random.fold_in(key, 2000 + i))
        caches["remainder"].append(c)
        taps.append(_first(t))
    caches["prefix"] = tuple(caches["prefix"])
    caches["remainder"] = tuple(caches["remainder"])
    return x, caches, _stack_taps(taps, lead=True)


def stack_decode(params: dict, cfg: ModelConfig, x: jnp.ndarray,
                 caches: dict, pos: jnp.ndarray):
    """One decode step through the stack.  Returns (x, caches', taps): each
    block's taps (``block_decode``) stacked over the layers that have them,
    in depth order — for a MoE model ``sizes`` (MoE layers, E), ``experts``
    (MoE layers, B, 1, k), ``delivered`` (MoE layers,)."""
    prefix, pattern, reps, remainder = stack_layout(cfg)
    taps = []
    new_prefix = []
    for p, kind, c in zip(params["prefix"], prefix, caches["prefix"]):
        x, c2, t = block_decode(p, cfg, kind, False, x, c, pos)
        new_prefix.append(c2)
        taps.append(_first(t))

    def super_body(h, xs):
        sb_params, sb_cache = xs
        cs, ts = [], []
        for i, kind in enumerate(pattern):
            h, c2, t = block_decode(sb_params[i], cfg, kind,
                                    _is_moe_layer(cfg, False), h,
                                    sb_cache[i], pos)
            cs.append(c2)
            ts.append(t)
        return h, (tuple(cs), _stack_taps(ts))

    new_scanned = caches.get("scanned")
    if reps > 0:
        if cfg.scan_layers:
            x, (new_scanned, st) = jax.lax.scan(
                super_body, x, (params["scanned"], caches["scanned"]))
        else:
            outs = []
            for i, sb in enumerate(params["scanned"]):
                sb_cache = jax.tree.map(lambda t: t[i], caches["scanned"])
                x, c2 = super_body(x, (sb, sb_cache))
                outs.append(c2)
            new_scanned, st = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        taps.append(_scanned_taps(st))

    new_rem = []
    for p, kind, c in zip(params["remainder"], remainder,
                          caches["remainder"]):
        x, c2, t = block_decode(p, cfg, kind, _is_moe_layer(cfg, False), x,
                                c, pos)
        new_rem.append(c2)
        taps.append(_first(t))
    caches = {"prefix": tuple(new_prefix), "scanned": new_scanned,
              "remainder": tuple(new_rem)}
    return x, caches, _stack_taps(taps, lead=True)


def stack_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    prefix, pattern, reps, remainder = stack_layout(cfg)
    cache = {
        "prefix": tuple(block_cache_shape(cfg, kind, batch, max_len)
                        for kind in prefix),
        "remainder": tuple(block_cache_shape(cfg, kind, batch, max_len)
                           for kind in remainder),
        "scanned": None,
    }
    if reps > 0:
        one = tuple(block_cache_shape(cfg, kind, batch, max_len)
                    for kind in pattern)
        cache["scanned"] = jax.tree.map(
            lambda t: jnp.zeros((reps,) + t.shape, t.dtype), one)
    return cache
