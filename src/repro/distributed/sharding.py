"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Physical mesh axes:

- ``pod``   (multi-pod only): pure data parallelism across pods (DCI).
- ``data``  : data parallelism within a pod; also hosts FSDP (ZeRO-3) param
              sharding and sequence parallelism for long-context cells.
- ``model`` : tensor/expert parallelism within a pod (ICI-adjacent).

Rules are *name + shape* based: ``param_pspec`` inspects the param path (e.g.
``stack/scanned/0/mixer/wq``) and the array rank, returns a PartitionSpec, and
silently falls back to replication for any dim not divisible by its axis size
(e.g. kv-heads < model-axis on GQA archs — those weights are replicated inside
the TP group exactly like Megatron does).

Everything here is pure metadata: no jax device state is touched, so importing
is safe before ``XLA_FLAGS`` is set by the dry-run.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= _axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.shape else 1


def _fit(dim: int, axis, mesh: Mesh):
    """Return ``axis`` if ``dim`` is divisible by its mesh size, else None."""
    return axis if axis is not None and dim % _axis_size(mesh, axis) == 0 \
        and _axis_size(mesh, axis) > 1 else None


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The pure-DP axes, outermost first: ('pod','data') or ('data',)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def data_parallel_mesh(devices=None) -> Optional[Mesh]:
    """A 1-D ('data',) mesh over the local devices — the mesh the panel-sweep
    engine (``repro.core.sweep``) shards over.  Returns None when only one
    device is visible, which every ``mesh=`` consumer treats as the
    sequential single-device fallback."""
    import numpy as np
    devices = jax.devices() if devices is None else list(devices)
    if len(devices) <= 1:
        return None
    return Mesh(np.asarray(devices), ("data",))


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

_REPLICATED_KEYS = ("norm", "scale", "router", "q_norm", "k_norm", "kv_norm",
                    "a_param", "conv", "gates", "offset")


def _is_stacked(parts) -> bool:
    """Does this leaf carry a leading layers dim?

    - 'xattn' subtrees (whisper) are always vmap-stacked.
    - scanned mode:   stack/scanned/<slot>/...        (ONE numeric)  stacked
    - unrolled mode:  stack/scanned/<rep>/<slot>/...  (TWO numerics) flat
    """
    if "xattn" in parts:
        return True
    if "scanned" not in parts:
        return False
    i = parts.index("scanned")
    numerics = 0
    for p in parts[i + 1:]:
        if p.lstrip("-").isdigit():
            numerics += 1
        else:
            break
    return numerics <= 1


def param_pspec(path: str, shape: Tuple[int, ...], mesh: Mesh,
                fsdp: bool = False, moe_ep2d: bool = False) -> P:
    """PartitionSpec for one parameter leaf.

    ``path`` is '/'-joined dict keys (ints for scanned stacks). The leading
    scan dim (layers) of stacked params is never sharded.  ``moe_ep2d``
    spreads expert banks over ('data','model') — the shard_map EP layout
    (one deepseek expert per chip; no ZeRO gather for expert weights).
    """
    parts = path.strip("/").split("/")
    key = parts[-1]
    nd = len(shape)
    off = 1 if (_is_stacked(parts) and nd >= 2) else 0   # leading layer dim

    def spec(*axes):
        full = [None] * nd
        for i, ax in enumerate(axes):
            full[off + i] = _fit(shape[off + i], ax, mesh)
        return P(*full)

    fs = "data" if fsdp else None                 # ZeRO-3 axis

    # ---- norms / small vectors -------------------------------------------
    if any(k in key for k in _REPLICATED_KEYS) and nd - off <= 2:
        return P(*([None] * nd))

    # ---- embeddings -------------------------------------------------------
    # vocab -> model only: co-sharding d over 'data' makes the token gather
    # un-partitionable (SPMD falls back to full rematerialization)
    if key == "embedding":                        # (V, d): vocab -> model
        return spec("model", None)
    if key == "unembed":                          # (d, V): vocab -> model
        return spec(None, "model")
    if key == "frontend_proj":                    # (d_front, d)
        return spec(None, "model")

    # ---- MoE expert banks -------------------------------------------------
    if "moe" in parts and key in ("wi_gate", "wi_up", "wo") \
            and "shared" not in parts and nd - off == 3:
        # (E, d, ff) / (E, ff, d): experts -> model (EP); when the expert
        # count doesn't divide the axis (qwen2's 60) fall back to TP inside
        # each expert on the ff dim
        if moe_ep2d and _fit(shape[off], ("data", "model"), mesh):
            return spec(("data", "model"), None, None)
        if _fit(shape[off], "model", mesh):
            return spec("model", fs, None)
        if key == "wo":                       # (E, ff, d)
            return spec(None, "model", fs)
        return spec(None, fs, "model")        # (E, d, ff)

    # ---- attention --------------------------------------------------------
    if key == "wq" and nd - off == 3:             # (d, H, hd): heads -> model
        return spec(fs, "model", None)
    if key in ("wk", "wv") and nd - off == 3:     # (d, KV, hd)
        return spec(fs, "model", None)
    if key == "wo" and nd - off == 3:             # (H, hd, d): heads -> model
        return spec("model", None, fs)

    # ---- MLA (deepseek) ---------------------------------------------------
    if key == "wq_a":                             # (d, q_rank)
        return spec(fs, "model")
    if key == "wq_b":                             # (q_rank, H, k)
        return spec(fs, "model", None)
    if key == "wkv_a":                            # (d, R+dr)
        return spec(fs, None)
    if key == "wkv_b":                            # (R, H, k)
        return spec(fs, "model", None)

    # ---- dense MLP --------------------------------------------------------
    if key in ("wi_gate", "wi_up") and nd - off == 2:   # (d, ff): ff -> model
        return spec(fs, "model")
    if key == "wo" and nd - off == 2:                   # (ff, d)
        return spec("model", fs)

    # ---- recurrent mixers (rglru / mlstm / slstm) -------------------------
    if key in ("wx", "wy"):                       # rglru in/out (d, W)/(W, d)
        return spec(fs, "model") if key == "wx" else spec("model", fs)
    if key in ("wqkv", "wi", "wf", "wz", "wout", "wproj", "wup", "wdown"):
        # generic wide projections: shard the widest non-d dim over model
        full = [None] * nd
        if nd - off >= 2:
            widest = max(range(off, nd), key=lambda i: shape[i])
            full[widest] = _fit(shape[widest], "model", mesh)
        return P(*full)

    # ---- fallback: shard the largest dim over model if it fits ------------
    if nd - off >= 2 and max(shape[off:]) >= 1024:
        full = [None] * nd
        widest = max(range(off, nd), key=lambda i: shape[i])
        full[widest] = _fit(shape[widest], "model", mesh)
        return P(*full)
    return P(*([None] * nd))


def param_shardings(params, mesh: Mesh, fsdp: bool = False,
                    moe_ep2d: bool = False):
    """NamedSharding pytree matching ``params`` (works on ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out.append(NamedSharding(mesh, param_pspec(
            pstr, leaf.shape, mesh, fsdp=fsdp, moe_ep2d=moe_ep2d)))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# batch / activation / cache rules
# ---------------------------------------------------------------------------

def batch_pspec(shape: Tuple[int, ...], mesh: Mesh,
                seq_axis: Optional[int] = None) -> P:
    """Shard the batch dim over as much of (pod, data) as divides it; for
    unshardable batch (e.g. long_500k B=1) shard ``seq_axis`` over 'data'."""
    if not shape:
        return P()
    B = shape[0]
    dp = data_axes(mesh)
    full = [None] * len(shape)
    if dp and B % _axis_size(mesh, dp) == 0:
        full[0] = dp
    elif "data" in mesh.shape and B % mesh.shape["data"] == 0 \
            and mesh.shape["data"] > 1:
        full[0] = "data"
    elif seq_axis is not None and len(shape) > seq_axis \
            and shape[seq_axis] % _axis_size(mesh, "data") == 0:
        full[seq_axis] = "data"
    return P(*full)


def batch_shardings(batch, mesh: Mesh):
    """Shardings for a train/prefill/decode input batch dict."""
    def one(leaf):
        return NamedSharding(mesh, batch_pspec(leaf.shape, mesh))
    return jax.tree.map(one, batch)


def cache_shardings(cache, mesh: Mesh):
    """Decode caches, keyed by leaf name (the cache layout contract):

    - k/v/enc_kv  (L?, B, S, KV, hd): batch -> DP; KV heads -> 'model' when
      divisible (TP-style KV sharding), else the sequence -> 'model'
      (sequence-parallel cache: the softmax reduction becomes a collective,
      visible in the roofline's collective term).
    - ckv/krope   (L?, B, S, R): MLA latent cache — batch -> DP, seq -> model.
    - k_land/uv/u1/offset: landmark factors (O(c), tiny) — batch -> DP only.
    - recurrent states (C/n/m/c/h/conv): batch -> DP; the widest state dim
      -> 'model' when divisible (mirrors the mixer's head/width sharding).
    - long-context fallback (B not shardable): the sequence dim takes every
      axis it divides: ('pod','data','model') -> S/512 per chip.
    """
    dp = data_axes(mesh)

    def one(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        key = keys[-1] if keys else ""
        shape = leaf.shape
        nd = len(shape)
        full = [None] * nd

        if key in ("k", "v") or "enc_kv" in keys:
            off = nd - 4                       # (B, S, KV, hd) trailing
            b, s, kvh = off, off + 1, off + 2
            if shape[b] > 1 and shape[b] % _axis_size(mesh, dp) == 0 \
                    and _axis_size(mesh, dp) > 1:
                full[b] = dp
                leaf_bytes = 2
                for d in shape:
                    leaf_bytes *= d
                local_bytes = leaf_bytes // _axis_size(mesh, dp)
                if _fit(shape[kvh], "model", mesh):
                    full[kvh] = "model"
                elif local_bytes > 2e9 and shape[s] >= 1024 \
                        and _fit(shape[s], "model", mesh):
                    # only sequence-shard caches too big to replicate over
                    # 'model': S-sharding forces a distributed softmax
                    # (all-gathers per decode step, §Perf-C iteration 2)
                    full[s] = "model"
            else:
                # B=1 long-context: sequence takes all axes it divides
                axes = tuple(a for a in ("pod", "data", "model")
                             if a in mesh.shape)
                if shape[s] % _axis_size(mesh, axes) == 0 and shape[s] >= 1024:
                    full[s] = axes
                elif _fit(shape[s], "data", mesh):
                    full[s] = "data"
        elif key in ("ckv", "krope"):
            off = nd - 3                       # (B, S, R)
            b, s = off, off + 1
            if shape[b] > 1 and shape[b] % _axis_size(mesh, dp) == 0 \
                    and _axis_size(mesh, dp) > 1:
                full[b] = dp
                if shape[s] >= 1024 and _fit(shape[s], "model", mesh):
                    full[s] = "model"
            elif shape[s] >= 1024:
                axes = tuple(a for a in ("pod", "data", "model")
                             if a in mesh.shape)
                if shape[s] % _axis_size(mesh, axes) == 0:
                    full[s] = axes
        elif key in ("k_land", "uv", "u1", "offset"):
            # landmark factors: (L?, B, KV, [c, [hd]])
            base_nd = {"k_land": 4, "uv": 4, "u1": 3, "offset": 2}[key]
            b = nd - base_nd                   # 1 when scanned, else 0
            if b < nd and shape[b] > 1 and _axis_size(mesh, dp) > 1 \
                    and shape[b] % _axis_size(mesh, dp) == 0:
                full[b] = dp
        else:
            # recurrent states: batch is the first DP-divisible dim among
            # the first two; widest trailing dim -> model
            for b in range(min(2, nd)):
                if shape[b] > 1 and _axis_size(mesh, dp) > 1 \
                        and shape[b] % _axis_size(mesh, dp) == 0:
                    full[b] = dp
                    break
            if nd >= 2:
                widest = max(range(nd), key=lambda i: shape[i])
                if full[widest] is None and shape[widest] >= 128 \
                        and _fit(shape[widest], "model", mesh):
                    full[widest] = "model"
        return NamedSharding(mesh, P(*full))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def tree_shardings(tree, mesh: Mesh, pspec_fn):
    """Generic: one PartitionSpec per leaf from ``pspec_fn(path, shape)``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        out.append(NamedSharding(mesh, pspec_fn(pstr, leaf.shape)))
    return jax.tree_util.tree_unflatten(treedef, out)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# in-graph helpers (used by model code under an ambient
# ``with jax.set_mesh(mesh):``)
# ---------------------------------------------------------------------------

def ambient_axis_size(name: str) -> int:
    """Size of a mesh axis in the ambient (``jax.set_mesh``) mesh; 1 when no
    mesh is set or the mesh has no such axis."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else dict(mesh.shape).get(name, 1)


def constrain(x, spec: P):
    """``with_sharding_constraint`` under the ambient mesh; the identity when
    no mesh is set (single-device code paths)."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
