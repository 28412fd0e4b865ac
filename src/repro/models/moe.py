"""Mixture-of-Experts FFN (deepseek-v3 / qwen2-moe families).

Dispatch is the static-shape sort-based gather path (TPU-native; no dense
(T, E, C) one-hot):

  1. route: top-k softmax probs per token
  2. sort the T*k assignments by expert id (stable argsort)
  3. capacity-bound each expert to C = cf * T * k / E slots; overflow drops
  4. gather tokens into an (E, C, d) buffer — under pjit this is the
     data->expert all-to-all — run all experts as one batched GEMM,
     scatter-add back with the routing weights.

Shared experts (deepseek's 1, qwen's 4) are a plain dense MLP of width
n_shared * moe_d_ff added unconditionally.

Serving (prefill and decode, ``moe_ffn_served``) drops nothing: the T*k
assignments are sorted by expert and each weight is one grouped product
(``jax.lax.ragged_dot``) over the sorted rows, so every expert takes however
many tokens were routed to it.  It also returns what it dispatched: the
group sizes, the experts each token was routed to, and the rows the grouped
products delivered to the combine (a caller counts the assignments dropped
as those routed less those delivered).  Its router runs at
``Precision.HIGHEST``: the router weights are f32, and a one-pass bf16
product would move near-tied experts.  The served path has one
implementation; ``moe_impl`` selects the training path only.

``shard_map`` variant (moe_impl='shard_map'): the same algorithm with the
expert GEMMs under an explicit mesh-axis shard_map so the all-to-all is
scheduled manually — used by the §Perf hillclimb.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def init_moe(key: jax.Array, cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], (d, E), jnp.float32),
        "wi_gate": L.dense_init(ks[1], (E, d, ff), cfg.pdtype),
        "wi_up": L.dense_init(ks[2], (E, d, ff), cfg.pdtype),
        "wo": L.dense_init(ks[3], (E, ff, d), cfg.pdtype),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        p["shared"] = L.init_mlp(ks[4], cfg, d_ff=sff)
    return p


def _route(params: dict, cfg: ModelConfig, xf: jnp.ndarray,
           precision=None):
    """xf: (T, d) -> topk weights (T, k), indices (T, k), aux loss scalar."""
    logits = jnp.matmul(xf.astype(jnp.float32), params["router"],
                        precision=precision)                 # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e (frac_tokens_e * mean_prob_e)
    E = cfg.n_experts
    hard = jnp.zeros((xf.shape[0], E), jnp.float32)
    hard = hard.at[jnp.arange(xf.shape[0])[:, None], idx].add(1.0)
    frac = jnp.mean(hard, axis=0) / cfg.moe_top_k
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return w, idx, aux


def _dispatch_compute(params: dict, cfg: ModelConfig, xf: jnp.ndarray,
                      w: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Sort-based capacity dispatch. xf: (T, d) -> (T, d)."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    Tk = T * k
    C = max(1, int(cfg.capacity_factor * Tk / E))
    C = -(-C // 8) * 8                                       # pad to 8

    eids = idx.reshape(-1)                                   # (Tk,)
    tok = jnp.arange(Tk, dtype=jnp.int32) // k
    wts = w.reshape(-1)

    order = jnp.argsort(eids, stable=True)
    se = eids[order]
    st = tok[order]
    sw = wts[order]
    first = jnp.searchsorted(se, se, side="left")
    pos_in_e = jnp.arange(Tk, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)         # drop row at end

    buf = jnp.zeros((E * C + 1, d), cfg.cdtype)
    buf = buf.at[slot].set(jnp.take(xf, st, axis=0))
    eb = buf[: E * C].reshape(E, C, d)                       # (E, C, d)

    dt = cfg.cdtype
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb,
                                  params["wi_gate"].astype(dt)))
    up = jnp.einsum("ecd,edf->ecf", eb, params["wi_up"].astype(dt))
    ob = jnp.einsum("ecf,efd->ecd", gate * up, params["wo"].astype(dt))
    ob_flat = jnp.concatenate(
        [ob.reshape(E * C, d), jnp.zeros((1, d), dt)], axis=0)

    vals = jnp.take(ob_flat, slot, axis=0) * (
        sw * keep.astype(jnp.float32))[:, None].astype(dt)
    out = jnp.zeros((T, d), dt).at[st].add(vals)
    return out


def _dropless_compute(params: dict, cfg: ModelConfig, xf: jnp.ndarray,
                      w: jnp.ndarray, idx: jnp.ndarray):
    """Grouped dispatch with no capacity. xf: (T, d) -> ((T, d), (E,) group
    sizes, rows delivered: the assignments whose expert output reached the
    combine)."""
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    dt = cfg.cdtype
    eids = idx.reshape(-1)
    order = jnp.argsort(eids, stable=True)
    tok = order // k                                         # sorted rows
    sizes = jnp.bincount(eids, length=E).astype(jnp.int32)
    xs = jnp.take(xf, tok, axis=0).astype(dt)

    def grouped(a, wname, out=jnp.float32):
        return jax.lax.ragged_dot(a, params[wname].astype(dt), sizes,
                                  preferred_element_type=out)

    h = (jax.nn.silu(grouped(xs, "wi_gate"))
         * grouped(xs, "wi_up")).astype(dt)
    ob = grouped(h, "wo", dt)                                # (T*k, d)
    delivered = jnp.sum(jnp.any(ob != 0, axis=-1), dtype=jnp.int32)
    vals = ob.astype(jnp.float32) * w.reshape(-1)[order][:, None]
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(vals)
    return out.astype(dt), sizes, delivered


def moe_ffn_served(params: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Serving FFN, dropless. x: (B, S, d) -> (out, dispatch): ``dispatch``
    holds ``sizes`` (E,) the assignments routed to each expert, ``experts``
    (B, S, k) the experts each token was routed to, and ``delivered`` the
    assignments whose expert output reached the combine."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    w, idx, _ = _route(params, cfg, xf, jax.lax.Precision.HIGHEST)
    out, sizes, delivered = _dropless_compute(params, cfg, xf, w, idx)
    if cfg.n_shared_experts:
        out = out + L.mlp(params["shared"], cfg, xf)
    dispatch = {"sizes": sizes, "experts": idx.reshape(B, S, -1),
                "delivered": delivered}
    return out.reshape(B, S, d), dispatch


def moe_ffn(params: dict, cfg: ModelConfig,
            x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out, aux_loss)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    if cfg.moe_impl == "shard_map" and _ep_axes_available(cfg):
        out, aux = _moe_shard_map(params, cfg, xf)
    else:
        w, idx, aux = _route(params, cfg, xf)
        out = _dispatch_compute(params, cfg, xf, w, idx)
    if cfg.n_shared_experts:
        out = out + L.mlp(params["shared"], cfg, xf)
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert parallelism (explicit all_to_all over ('data','model'))
# ---------------------------------------------------------------------------
# GSPMD cannot partition the data-dependent scatter of the gather path: it
# falls back to replicating the (Tk, d) token buffer on every chip, which the
# dry-run measures as hundreds of seconds of all-gather per step on
# deepseek-v3.  The production fix is the explicit EP protocol:
#
#   1. tokens are split across the whole ('data','model') group (each chip
#      routes a disjoint slice),
#   2. each chip sorts its assignments by destination expert and lays them
#      out as (n_ep, E_loc*C, d),
#   3. one all_to_all delivers every chip its own experts' tokens,
#   4. local expert GEMMs, reverse all_to_all, unsort, weighted combine,
#   5. one psum over 'model' restores the (replicated-over-TP) activations.
#
# Expert weights are sharded E -> ('data','model') (one expert per chip on
# the 256-chip pod for deepseek's 256 experts): no ZeRO all-gather is needed
# for expert banks at all.

def _ep_axes(cfg):
    return ("data", "model")


def _ep_axes_available(cfg) -> bool:
    from repro.distributed.sharding import ambient_axis_size
    n = 1
    for a in _ep_axes(cfg):
        n *= ambient_axis_size(a)
    return n > 1 and cfg.n_experts % n == 0


def _moe_shard_map(params: dict, cfg: ModelConfig, xf: jnp.ndarray):
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    axes = _ep_axes(cfg)
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_ep = 1
    for a in axes:
        n_ep *= dict(mesh.shape).get(a, 1)

    tok_spec = P(dp if dp else None, None)     # (T, d): batch rows over DP

    def body(xf_l, router, wig, wiu, wo):
        # xf_l: this dp-slice's tokens, replicated over 'model'.
        # Each 'model' rank takes a disjoint token slice -> EP over n_ep.
        tp = dict(mesh.shape).get("model", 1)
        T_rep, d = xf_l.shape
        T_loc = T_rep // tp
        rank = jax.lax.axis_index("model")
        xs = jax.lax.dynamic_slice_in_dim(xf_l, rank * T_loc, T_loc, axis=0)

        E, k = cfg.n_experts, cfg.moe_top_k
        E_loc = E // n_ep
        logits = xs.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        frac = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                        axis=(0, 1))          # already averaged over k slots
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, axes)

        Tk = T_loc * k
        C = max(8, -(-int(cfg.capacity_factor * Tk / E) // 8) * 8)
        eids = idx.reshape(-1)
        tok = jnp.arange(Tk, dtype=jnp.int32) // k
        wts = w.reshape(-1)
        order = jnp.argsort(eids, stable=True)
        se, st, sw = eids[order], tok[order], wts[order]
        first = jnp.searchsorted(se, se, side="left")
        pos = jnp.arange(Tk, dtype=jnp.int32) - first.astype(jnp.int32)
        keep = pos < C
        slot = jnp.where(keep, se * C + pos, E * C)

        dt = cfg.cdtype
        sbuf = jnp.zeros((E * C + 1, d), dt).at[slot].set(
            jnp.take(xs, st, axis=0).astype(dt))
        sbuf = sbuf[: E * C].reshape(n_ep, E_loc * C, d)
        rbuf = jax.lax.all_to_all(sbuf, axes, split_axis=0, concat_axis=0,
                                  tiled=False)
        rb = rbuf.reshape(n_ep, E_loc, C, d).transpose(1, 0, 2, 3) \
                 .reshape(E_loc, n_ep * C, d)

        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", rb, wig.astype(dt)))
        up = jnp.einsum("ecd,edf->ecf", rb, wiu.astype(dt))
        ob = jnp.einsum("ecf,efd->ecd", gate * up, wo.astype(dt))

        ob = ob.reshape(E_loc, n_ep, C, d).transpose(1, 0, 2, 3) \
               .reshape(n_ep, E_loc * C, d)
        obuf = jax.lax.all_to_all(ob, axes, split_axis=0, concat_axis=0,
                                  tiled=False)
        flat = jnp.concatenate([obuf.reshape(E * C, d),
                                jnp.zeros((1, d), dt)], axis=0)
        vals = jnp.take(flat, slot, axis=0) * (
            sw * keep.astype(jnp.float32))[:, None].astype(dt)
        out_l = jnp.zeros((T_loc, d), dt).at[st].add(vals)

        # reassemble the 'model'-replicated activation: disjoint slices sum
        out = jnp.zeros((T_rep, d), dt)
        out = jax.lax.dynamic_update_slice_in_dim(out, out_l, rank * T_loc,
                                                  axis=0)
        out = jax.lax.psum(out, "model")
        return out, aux

    ep_spec = P(axes, None, None)              # (E, d, ff): E over EP group
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), ep_spec, ep_spec, ep_spec),
        out_specs=(tok_spec, P()),
        check_vma=False)
    return fn(xf, params["router"], params["wi_gate"], params["wi_up"],
              params["wo"])
