"""Top-level models: decoder-only LM, early-fusion VLM, whisper enc-dec.

``build_model(cfg)`` -> ``Model`` with the pure entry points the launcher
jits/pjits:

  init(key)                               -> params
  loss(params, batch)                     -> (scalar, metrics)     train_step
  forward(params, batch)                  -> (logits, aux)
  prefill(params, batch, key, max_len)    -> (last_logits, cache)  serve
    (``taps=True``: also the blocks' taps, ``transformer.stack_prefill``)
  decode_step(params, cache, tokens, pos) -> (logits, cache)       serve_step
  decode_loop(params, cache, tokens, pos, keep, steps)             serve turn
  generate(params, batch, key, keep, steps)   prefill + decode_loop

``decode_loop`` runs ``steps`` greedy decode steps as one on-device program
(a ``lax.fori_loop``): it feeds ``tokens`` (B,) at ``pos``, then each step's
argmax, and returns ``Turn`` — the tokens fed (B, steps), the f32 logits of
the sessions ``keep`` (K, steps, V), the cache after the last step, and each
step's taps (``transformer.stack_decode``): the MoE's assignments per expert
and delivered to the combine, of every session, and the routing and the
landmark layers' queries, keys and values of the sessions ``keep``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.instrument import span
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T

MOE_AUX_WEIGHT = 0.01
MTP_WEIGHT = 0.3


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable
    forward: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    cache_shape: Callable        # (batch, max_len) -> zero cache pytree
    decode_loop: Optional[Callable] = None
    generate: Optional[Callable] = None


class Turn(NamedTuple):
    """What one ``decode_loop`` program returns."""
    tokens: jnp.ndarray          # (B, steps) int32, the token fed at each step
    logits: jnp.ndarray          # (K, steps, V) f32, of the kept sessions
    cache: dict                  # after the last step
    taps: dict                   # per step: "sizes" (steps, MoE layers, E),
    # "delivered" (steps, MoE layers); of the kept sessions: "experts"
    # (K, steps, MoE layers, k), "landmark_q" (K, steps, landmark layers,
    # H, D), "landmark_k" / "landmark_v" (K, steps, landmark layers, KV, D)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over all positions; f32 logsumexp; labels < 0 are masked."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# decoder-only LM (dense / moe / ssm / hybrid / vlm)
# ---------------------------------------------------------------------------

def _init_lm(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, ks, kf, km = jax.random.split(key, 4)
    params = {
        "embed": L.init_embed(ke, cfg),
        "stack": T.init_stack(ks, cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
    }
    if cfg.mtp:
        km1, km2 = jax.random.split(km)
        params["mtp"] = {
            "proj": L.dense_init(km1, (2 * cfg.d_model, cfg.d_model),
                                 cfg.pdtype),
            "norm_h": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
            "norm_e": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
            "block": T.init_block(km2, cfg, "attn", moe=False,
                                  dense_ff=cfg.dense_d_ff or None),
            "final_norm": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
        }
    return params


def _embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jnp.ndarray:
    x = L.embed(params["embed"], cfg, batch["tokens"])
    if "patches" in batch:                                   # early fusion
        n_patch = batch["patches"].shape[1]
        x = jnp.concatenate(
            [batch["patches"].astype(cfg.cdtype), x[:, n_patch:]], axis=1)
    return x


def _lm_hidden(params: dict, cfg: ModelConfig, batch: dict):
    x = _embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    x, aux = T.stack_full(params["stack"], cfg, x, positions)
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def _lm_forward(params: dict, cfg: ModelConfig, batch: dict):
    h, aux = _lm_hidden(params, cfg, batch)
    return L.unembed(params["embed"], cfg, h), aux


def _mtp_loss(params: dict, cfg: ModelConfig, batch: dict,
              h: jnp.ndarray) -> jnp.ndarray:
    """deepseek MTP: predict t+2 from [norm(h_t); norm(emb(token_{t+1}))]."""
    mp = params["mtp"]
    tok_next = jnp.roll(batch["tokens"], -1, axis=1)
    e = L.embed(params["embed"], cfg, tok_next)
    z = jnp.concatenate([L.rmsnorm(mp["norm_h"], h, cfg.norm_eps),
                         L.rmsnorm(mp["norm_e"], e, cfg.norm_eps)], axis=-1)
    z = z @ mp["proj"].astype(cfg.cdtype)
    positions = jnp.arange(z.shape[1])
    z, _ = T.block_full(mp["block"], cfg, "attn", False, z, positions)
    z = L.rmsnorm(mp["final_norm"], z, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, z)
    labels2 = jnp.roll(batch["labels"], -1, axis=1)
    labels2 = labels2.at[:, -2:].set(-1)                     # no target
    return softmax_xent(logits, labels2)


def _lm_loss(params: dict, cfg: ModelConfig, batch: dict):
    h, aux = _lm_hidden(params, cfg, batch)
    logits = L.unembed(params["embed"], cfg, h)
    ce = softmax_xent(logits, batch["labels"])
    total = ce + MOE_AUX_WEIGHT * aux
    metrics = {"ce": ce, "aux": aux}
    if cfg.mtp:
        mtp = _mtp_loss(params, cfg, batch, h)
        total = total + MTP_WEIGHT * mtp
        metrics["mtp"] = mtp
    metrics["loss"] = total
    return total, metrics


@span("serve.prefill")
def _lm_prefill(params: dict, cfg: ModelConfig, batch: dict, key: jax.Array,
                max_len: int, taps: bool = False):
    x = _embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    x, caches, tapped = T.stack_prefill(params["stack"], cfg, x, positions,
                                        max_len, key)
    h_last = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h_last, jnp.float32)
    if taps:
        return logits[:, 0], caches, tapped
    return logits[:, 0], caches


def _lm_step(params: dict, cfg: ModelConfig, cache: dict,
             tokens: jnp.ndarray, pos: jnp.ndarray):
    x = L.embed(params["embed"], cfg, tokens)                # (B, 1, d)
    x, cache, taps = T.stack_decode(params["stack"], cfg, x, cache, pos)
    h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h, jnp.float32)
    return logits[:, 0], cache, taps


def _lm_decode(params: dict, cfg: ModelConfig, cache: dict,
               tokens: jnp.ndarray, pos: jnp.ndarray):
    logits, cache, _ = _lm_step(params, cfg, cache, tokens, pos)
    return logits, cache


@span("serve.decode")
def _lm_decode_loop(params: dict, cfg: ModelConfig, cache: dict,
                    tokens: jnp.ndarray, pos: jnp.ndarray,
                    keep: jnp.ndarray, steps: int) -> Turn:
    B, K = tokens.shape[0], keep.shape[0]

    def kept_rows(name, a):
        # taps with a batch axis (layers, B, 1, ...) keep the sessions
        # ``keep``, sessions first; the others keep every session
        if name in ("sizes", "delivered"):
            return a
        return jnp.take(a[:, :, 0], keep, axis=1).swapaxes(0, 1)

    tap_shapes = jax.eval_shape(
        lambda c, t: {n: kept_rows(n, a) for n, a in _lm_step(
            params, cfg, c, t[:, None], pos)[2].items()}, cache, tokens)
    taps0 = {n: jnp.zeros((steps,) + a.shape, a.dtype)
             for n, a in tap_shapes.items()}

    def body(i, carry):
        tok, cache, fed, kept, taps = carry
        logits, cache, t = _lm_step(params, cfg, cache, tok[:, None],
                                    pos + i)
        fed = fed.at[:, i].set(tok)
        kept = kept.at[:, i].set(jnp.take(logits, keep, axis=0))
        taps = {n: taps[n].at[i].set(kept_rows(n, a)) for n, a in t.items()}
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache, fed, kept, taps

    carry = (tokens.astype(jnp.int32), cache,
             jnp.zeros((B, steps), jnp.int32),
             jnp.zeros((K, steps, cfg.vocab_size), jnp.float32), taps0)
    _, cache, fed, kept, taps = jax.lax.fori_loop(0, steps, body, carry)
    # steps first for every tap; the kept sessions' then lead
    taps = {n: a if n in ("sizes", "delivered") else a.swapaxes(0, 1)
            for n, a in taps.items()}
    return Turn(fed, kept, cache, taps)


def _lm_generate(params: dict, cfg: ModelConfig, batch: dict,
                 key: jax.Array, keep: jnp.ndarray, steps: int) -> Turn:
    """Prefill the prompts, then decode ``steps`` greedy tokens on the
    device, the first from the prompt's last logits."""
    S = batch["tokens"].shape[1]
    logits, cache = _lm_prefill(params, cfg, batch, key, S + steps)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _lm_decode_loop(params, cfg, cache, first,
                           jnp.asarray(S, jnp.int32), keep, steps)


# ---------------------------------------------------------------------------
# whisper-style encoder-decoder
# ---------------------------------------------------------------------------

def _sinusoid(S: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10_000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_cfg(cfg: ModelConfig) -> ModelConfig:
    return dataclasses.replace(cfg, n_layers=cfg.n_enc_layers,
                               layer_pattern=("attn",), first_k_dense=0)


def _dec_cfg(cfg: ModelConfig) -> ModelConfig:
    # decoder params are always *stored* stacked (vmap init); execution
    # scans when cfg.scan_layers else unrolls over slices (dry-run A/B)
    return dataclasses.replace(cfg, n_layers=cfg.n_dec_layers,
                               layer_pattern=("attn",), first_k_dense=0,
                               scan_layers=True)


def _slice_i(tree, i):
    return jax.tree.map(lambda t: t[i], tree)


def _init_encdec(key: jax.Array, cfg: ModelConfig) -> dict:
    ke, kd, kx, kt, kp = jax.random.split(key, 5)
    dec_reps = cfg.n_dec_layers
    xattn = jax.vmap(
        lambda k: {"xattn": A.init_attention(k, cfg, cross=True),
                   "xnorm": L.init_rmsnorm(cfg.d_model, cfg.pdtype)}
    )(jax.random.split(kx, dec_reps))
    return {
        "frontend_proj": L.dense_init(kp, (cfg.frontend_dim, cfg.d_model),
                                      cfg.pdtype),
        "encoder": T.init_stack(ke, _enc_cfg(cfg)),
        "enc_norm": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
        "decoder": T.init_stack(kd, _dec_cfg(cfg)),
        "xattn": xattn,
        "embed": L.init_embed(kt, cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model, cfg.pdtype),
    }


def _encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray):
    x = frames.astype(cfg.cdtype) @ params["frontend_proj"].astype(cfg.cdtype)
    x = x + _sinusoid(x.shape[1], cfg.d_model).astype(cfg.cdtype)[None]
    positions = jnp.arange(x.shape[1])
    x, _ = T.stack_full(params["encoder"], _enc_cfg(cfg), x, positions,
                        causal=False)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _dec_stack(params: dict, cfg: ModelConfig, x: jnp.ndarray,
               enc_out: jnp.ndarray, positions: jnp.ndarray):
    """Decoder: (self-attn block + cross-attn) pairs, scanned or unrolled."""
    dcfg = _dec_cfg(cfg)

    def body(h, xs):
        sb, xp = xs
        h, _ = T.block_full(sb[0], dcfg, "attn", False, h, positions)
        xnorm = L.rmsnorm(xp["xnorm"], h, cfg.norm_eps)
        ek, ev = A.encoder_kv(xp["xattn"], cfg, enc_out)
        h = h + A.cross_attention(xp["xattn"], cfg, xnorm, ek, ev)
        return h, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, (params["decoder"]["scanned"],
                                      params["xattn"]))
    else:
        for i in range(cfg.n_dec_layers):
            x, _ = body(x, (_slice_i(params["decoder"]["scanned"], i),
                            _slice_i(params["xattn"], i)))
    return x


def _encdec_loss(params: dict, cfg: ModelConfig, batch: dict):
    enc_out = _encode(params, cfg, batch["frames"])
    x = L.embed(params["embed"], cfg, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x = _dec_stack(params, cfg, x, enc_out, positions)
    h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    ce = softmax_xent(logits, batch["labels"])
    return ce, {"ce": ce, "loss": ce}


def _encdec_forward(params: dict, cfg: ModelConfig, batch: dict):
    enc_out = _encode(params, cfg, batch["frames"])
    x = L.embed(params["embed"], cfg, batch["tokens"])
    positions = jnp.arange(x.shape[1])
    x = _dec_stack(params, cfg, x, enc_out, positions)
    h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.unembed(params["embed"], cfg, h), jnp.zeros((), jnp.float32)


def _encdec_prefill(params: dict, cfg: ModelConfig, batch: dict,
                    key: jax.Array, max_len: int):
    """Encode frames; prime the decoder self-attn cache with the BOS token;
    precompute per-layer cross-attention KV."""
    enc_out = _encode(params, cfg, batch["frames"])
    dcfg = _dec_cfg(cfg)
    x = L.embed(params["embed"], cfg, batch["tokens"])       # (B, 1, d)
    positions = jnp.arange(x.shape[1])

    def body(h, xs):
        sb, xp, kd = xs
        kk = jax.random.wrap_key_data(kd)
        h, c, _ = T.block_prefill(sb[0], dcfg, "attn", False, h,
                                  positions, max_len, kk)
        xnorm = L.rmsnorm(xp["xnorm"], h, cfg.norm_eps)
        ek, ev = A.encoder_kv(xp["xattn"], cfg, enc_out)
        h = h + A.cross_attention(xp["xattn"], cfg, xnorm, ek, ev)
        return h, (c, (ek, ev))

    keys = jax.random.key_data(jax.random.split(key, cfg.n_dec_layers))
    if cfg.scan_layers:
        x, (self_c, enc_kv) = jax.lax.scan(
            body, x, (params["decoder"]["scanned"], params["xattn"], keys))
    else:
        outs = []
        for i in range(cfg.n_dec_layers):
            x, o = body(x, (_slice_i(params["decoder"]["scanned"], i),
                            _slice_i(params["xattn"], i), keys[i]))
            outs.append(o)
        self_c, enc_kv = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    h = L.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    return logits[:, 0], {"self": self_c, "enc_kv": enc_kv}


def _encdec_decode(params: dict, cfg: ModelConfig, cache: dict,
                   tokens: jnp.ndarray, pos: jnp.ndarray):
    dcfg = _dec_cfg(cfg)
    x = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        sb, xp, c, ekv = xs
        h, c2, _ = T.block_decode(sb[0], dcfg, "attn", False, h, c, pos)
        xnorm = L.rmsnorm(xp["xnorm"], h, cfg.norm_eps)
        h = h + A.cross_attention(xp["xattn"], cfg, xnorm, ekv[0], ekv[1])
        return h, c2

    if cfg.scan_layers:
        x, self_c = jax.lax.scan(
            body, x, (params["decoder"]["scanned"], params["xattn"],
                      cache["self"], cache["enc_kv"]))
    else:
        outs = []
        for i in range(cfg.n_dec_layers):
            x, c2 = body(x, (_slice_i(params["decoder"]["scanned"], i),
                             _slice_i(params["xattn"], i),
                             _slice_i(cache["self"], i),
                             _slice_i(cache["enc_kv"], i)))
            outs.append(c2)
        self_c = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    h = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    return logits[:, 0], {"self": self_c, "enc_kv": cache["enc_kv"]}


def _encdec_cache(cfg: ModelConfig, batch: int, max_len: int,
                  enc_len: int = 1500) -> dict:
    dcfg = _dec_cfg(cfg)
    one = T.block_cache_shape(dcfg, "attn", batch, max_len)
    reps = cfg.n_dec_layers
    self_c = jax.tree.map(lambda t: jnp.zeros((reps,) + t.shape, t.dtype),
                          one)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    ekv = (jnp.zeros((reps, batch, enc_len, kv, hd), cfg.cdtype),
           jnp.zeros((reps, batch, enc_len, kv, hd), cfg.cdtype))
    return {"self": self_c, "enc_kv": ekv}


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------

def _bind(fn, cfg):
    """Bind ``cfg`` into the second positional slot of ``fn(first, cfg, *rest)``."""
    @functools.wraps(fn)
    def wrapped(first, *rest, **kw):
        return fn(first, cfg, *rest, **kw)
    return wrapped


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encdec:
        return Model(
            cfg=cfg,
            init=functools.partial(_init_encdec, cfg=cfg),
            forward=_bind(_encdec_forward, cfg),
            loss=_bind(_encdec_loss, cfg),
            prefill=_bind(_encdec_prefill, cfg),
            decode_step=_bind(_encdec_decode, cfg),
            cache_shape=functools.partial(_encdec_cache, cfg),
        )
    return Model(
        cfg=cfg,
        init=functools.partial(_init_lm, cfg=cfg),
        forward=_bind(_lm_forward, cfg),
        loss=_bind(_lm_loss, cfg),
        prefill=_bind(_lm_prefill, cfg),
        decode_step=_bind(_lm_decode, cfg),
        cache_shape=functools.partial(T.stack_cache, cfg),
        decode_loop=_bind(_lm_decode_loop, cfg),
        generate=_bind(_lm_generate, cfg),
    )
