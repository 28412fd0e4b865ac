"""Traffic kind ``decode``: turns of greedy decode over long prefilled
sessions, back to back.

Set-up draws the model's weights from the seed and prefills ``sessions``
sessions of ``prompt`` random tokens, one session at a time, through the
program's own ``Model.prefill`` (each full layer builds its landmark factors
there), stacks their caches, compiles the program's ``Model.decode_loop``
for ``steps`` steps ahead of time and runs one untimed turn.

A turn is one call of that program on the set-up cache: every session
decodes ``steps`` tokens greedily on the device, folding each into the
landmark factors.  The window runs whole turns and closes at the end of the
first turn that ends at or after ``--seconds``; ``build_s`` is its length
over its turns.  Every turn is the same computation, so the first one is
checked: for ``checked_sessions`` sessions drawn from the seed, its logits
at every step, its routing, and its landmark factors before and after the
turn against ``bench/reference_mellum2.py`` (see ``compare``).  The
reference is teacher-forced where the program makes discrete choices or
where a rounding of the stated precision would be amplified past any
limit: it is fed the program's tokens, takes the program's routing (and
checks it against its own router), and replays the full layers' landmark
attention on the program's own keys, values and queries (its taps).
"""
from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench import reference_mellum2 as ref

#: the published keys the program's configuration must reproduce
_WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
           "head_dim", "moe_intermediate_size", "num_experts",
           "num_experts_per_tok", "vocab_size", "sliding_window",
           "rms_norm_eps", "num_hidden_layers", "tie_word_embeddings")


def _sizes(traffic):
    return (int(traffic["sessions"]), int(traffic["prompt"]),
            int(traffic["steps"]), int(traffic["checked_sessions"]))


def published(cfg) -> dict:
    """The ``config.json`` keys (and the landmark settings) that a program
    configuration ``cfg`` implies."""
    y = cfg.rope_yarn
    kinds = {"local": "sliding_attention", "global": "full_attention"}
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "moe_intermediate_size": cfg.moe_d_ff, "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.moe_top_k, "vocab_size": cfg.vocab_size,
        "sliding_window": cfg.window, "rms_norm_eps": cfg.norm_eps,
        "num_hidden_layers": cfg.n_layers,
        "tie_word_embeddings": cfg.tie_embeddings,
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
        "landmark_c": cfg.landmark_c, "landmark_theta": cfg.landmark_theta,
        "landmark_selection": cfg.landmark_selection,
        "activation_dtype": jnp.dtype(cfg.cdtype).name,
    }


def model_for(config: dict):
    """(ModelConfig, Model): the registry's configuration ``arch`` (its CPU
    twin where ``smoke`` is set) at the file's depth, refused unless it
    reproduces every width, the layer types, the RoPE and the landmark
    settings the file states."""
    from repro.configs import get_config, get_smoke
    from repro.models.model import build_model

    get = get_smoke if config.get("smoke") else get_config
    cfg = dataclasses.replace(get(config["arch"]),
                              n_layers=int(config["num_hidden_layers"]))
    mine = published(cfg)
    wrong = {k: (config[k], v) for k, v in mine.items()
             if k in config and config[k] != v}
    missing = [k for k in _WIDTHS + ("layer_types", "rope_parameters",
                                      "activation_dtype")
               if k not in config]
    if wrong or missing:
        raise ValueError(f"program configuration disagrees with the file: "
                         f"{wrong}; missing {missing}")
    return cfg, build_model(cfg)


# ---------------------------------------------------------------------------
# required work, from the shapes
# ---------------------------------------------------------------------------

def required_work(config: dict, traffic: dict) -> dict:
    """FLOPs and bytes a turn needs, from its shapes alone.

    Per decoded token: the attention projections (2d(H + 2KV)hd + 2Hhd·d a
    layer), the router (2dE) and the top-k experts (6d·ff each), the
    sliding layers' reads of their window (4H·W·hd), the head (2dV); per
    (session, KV head) of a full layer the fold (k_t against the landmarks,
    Ũ r, the rank-one update: 2c·hd + 2c² + 2c·hd) and per query head its
    read (4c·hd + 2c).

    Bytes a step must read at least once, in the dtypes the configuration
    states: every attention, router and norm weight, the head, the window
    rings (bf16 K and V), the landmark factors that do not change (f32
    k_land, Ũ) with the token's vectors, and ``expert_bytes`` for each
    expert a layer routes at least one token to (counted per step from the
    program's routing).
    ``landmark_*`` are the same counts for the full layers' landmark launch
    alone, per launch."""
    B, P, G, _ = _sizes(traffic)
    d, H, KV = (config["hidden_size"], config["num_attention_heads"],
                config["num_key_value_heads"])
    hd, E, k = (config["head_dim"], config["num_experts"],
                config["num_experts_per_tok"])
    ff, V, W = (config["moe_intermediate_size"], config["vocab_size"],
                config["sliding_window"])
    c = config["landmark_c"]
    types = config["layer_types"]
    L = len(types)
    n_full = types.count("full_attention")
    n_slide = L - n_full

    proj = 2 * d * (H + 2 * KV) * hd + 2 * H * hd * d
    token = L * (proj + 2 * d * E + k * 6 * d * ff) \
        + n_slide * 4 * H * min(W, P) * hd + 2 * d * V
    lm_fold = 2 * c * hd + 2 * c * c + 2 * c * hd
    lm_read = 4 * c * hd + 2 * c
    lm_flops = B * KV * lm_fold + B * H * lm_read
    # k_land and Ũ in, the token's q, k, v in and the reads out; UV and U1,
    # which a launch updates in place, stay on chip across steps (the
    # compiled turn assigns them memory space 1), as the launch's own record
    # counts them (``landmark_attention.kernel.launch_work``: ``bytes``)
    lm_bytes = B * KV * 4 * (c * hd + c * c) \
        + B * 4 * (H * hd + 2 * KV * hd + H * hd)
    step_flops = B * token + n_full * lm_flops

    bf16 = 2
    weights = L * (proj // 2 * bf16 + d * E * 4 + 2 * d * bf16) \
        + d * V * bf16 + d * bf16
    rings = n_slide * B * min(W, P) * KV * hd * 2 * bf16
    static_bytes = weights + rings + n_full * lm_bytes + B * d * bf16
    return {"step_flops": float(step_flops),
            "turn_flops": float(G * step_flops),
            "static_step_bytes": float(static_bytes),
            "expert_bytes": float(3 * d * ff * bf16),
            "landmark_flops": float(lm_flops),
            "landmark_bytes": float(lm_bytes)}


# ---------------------------------------------------------------------------
# set-up, window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class State:
    cell: object
    params: dict
    cache: dict             # the set-up cache, every session stacked
    keep: jnp.ndarray       # (K,) the sessions the check compares
    taps: dict              # the checked sessions' prefill taps, by session
    decode: object          # runs the compiled turn


def prompt(cell, b: int) -> jnp.ndarray:
    _, P, _, _ = _sizes(cell.traffic)
    return jax.random.randint(data.derive(cell.key, 1, b), (1, P), 0,
                              int(cell.config["vocab_size"]), jnp.int32)


def prefill_key(cell, b: int) -> jax.Array:
    return data.derive(cell.key, 2, b)


def checked_sessions(cell) -> np.ndarray:
    B, _, _, K = _sizes(cell.traffic)
    rng = np.random.default_rng(np.random.SeedSequence(
        [cell.seed & 0xFFFFFFFF, cell.seed >> 32, 11]))
    return np.sort(rng.choice(B, size=K, replace=False)).astype(np.int32)


def _stack(caches: list) -> dict:
    """Per-session caches (batch 1) into one: batch is axis 1 of the layers
    stacked over pattern repeats, axis 0 elsewhere."""
    out = {}
    for part in caches[0]:
        axis = 1 if part == "scanned" else 0
        out[part] = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis),
                                 *[c[part] for c in caches])
    return out


def setup(cell, warm: bool = True) -> State:
    _, model = model_for(cell.config)
    B, P, G, _ = _sizes(cell.traffic)
    params = jax.jit(model.init)(data.derive(cell.key, 0))
    prefill = jax.jit(functools.partial(model.prefill, taps=True),
                      static_argnums=(3,))
    keep = checked_sessions(cell)
    caches, first, taps = [], [], {}
    t0 = time.perf_counter()
    for b in range(B):
        logits, c, t = prefill(params, {"tokens": prompt(cell, b)},
                               prefill_key(cell, b), P + G)
        first.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        caches.append(c)
        if b in keep:
            taps[b] = t
        del t
    jax.block_until_ready(caches)
    t1 = time.perf_counter()
    cache = _stack(caches)
    del caches
    first = jnp.concatenate(first)
    keep = jnp.asarray(keep)
    pos = jnp.asarray(P, jnp.int32)
    decode = jax.jit(model.decode_loop, static_argnames=("steps",)).lower(
        params, cache, first, pos, keep, steps=G).compile()
    print(f"prefill of {B} sessions {t1 - t0!r} s (the first compiles); "
          f"decode compiled {time.perf_counter() - t1!r} s later",
          file=sys.stderr, flush=True)
    state = State(cell, params, cache, keep, taps,
                  lambda: decode(params, cache, first, pos, keep))
    if warm:
        finite(jax.block_until_ready(state.decode()).logits)
    return state


@jax.jit
def _all_finite(x):
    return jnp.all(jnp.isfinite(x))


def finite(logits) -> bool:
    """Whether every kept logit is finite, reduced on the device (set-up
    compiles it, so that the window does not)."""
    return bool(_all_finite(logits))


def touched_per_step(taps) -> list:
    """Experts routed at least one token, summed over the MoE layers, per
    step, from the program's dispatch counts ``sizes`` (steps, layers, E)."""
    return [int(x) for x in
            np.sum(np.asarray(taps["sizes"]) > 0, axis=(1, 2))]


def dropped(taps, sessions: int, top_k: int) -> int:
    """Assignments whose expert output did not reach the combine: B·k per
    layer and step less those the program delivered (``delivered``, steps
    × layers)."""
    got = np.asarray(taps["delivered"], np.int64)
    return int(got.size * sessions * top_k - got.sum())


def window(state: State, seconds: float) -> dict:
    """Whole turns until one ends at or after ``seconds``; the first is
    kept for the check."""
    B, _, G, _ = _sizes(state.cell.traffic)
    k = int(state.cell.config["num_experts_per_tok"])
    times, failed, kept, moe_dropped = [], 0, None, 0
    t0 = time.perf_counter()
    while True:
        tb = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.turn"):
            turn = jax.block_until_ready(state.decode())
        times.append(time.perf_counter() - tb)
        failed += not finite(turn.logits)
        moe_dropped += dropped(turn.taps, B, k)
        if kept is None:
            kept = turn
        del turn
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    turns = len(times)
    touched = touched_per_step(kept.taps)
    sizes = kept.taps["sizes"]
    lines = ["turn seconds " + " ".join(repr(t) for t in times),
             f"decode steps {turns * G}; moe_dropped {moe_dropped}; experts "
             f"touched per step (of {sizes.shape[1]} layers x "
             f"{sizes.shape[2]}): min {min(touched)} max {max(touched)}",
             "checked sessions "
             + " ".join(str(int(s)) for s in np.asarray(state.keep))]
    # on standard error too, so that a check that fails still shows them
    print("\n".join(lines), file=sys.stderr, flush=True)
    return {
        "attempted": turns, "failed": failed, "window_s": window_s,
        "e2e": {"build_s": window_s / turns},
        "counters": {"turns": turns, "decode_steps": turns * G,
                     "moe_dropped": moe_dropped, "turn_times_s": times,
                     "experts_touched": touched},
        "lines": lines,
        "sample": kept,
    }


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

def _global_states(cache: dict, session: int) -> list:
    """[full layer × KV head] -> (k_land, uv, u1) in float64: the full
    layers' factors of one session, in depth order."""
    out = []
    entries = [e for e in cache["scanned"] if "k_land" in e]
    reps = entries[0]["k_land"].shape[0] if entries else 0
    for r in range(reps):
        for e in entries:
            kl, uv, u1 = (np.asarray(e[n][r, session], np.float64)
                          for n in ("k_land", "uv", "u1"))
            out += [(kl[h], uv[h], u1[h]) for h in range(len(kl))]
    return out


def read_at(Q, kl, uv, u1) -> np.ndarray:
    """The factors' attention output at queries Q, in float64; the shared
    offset cancels between numerator and denominator, so each row is
    shifted by its own largest logit."""
    logits = Q @ kl.T / np.sqrt(Q.shape[-1])
    cvec = np.exp(logits - logits.max(axis=1, keepdims=True))
    return (cvec @ uv) / (cvec @ u1)[:, None]


def _ratio(num: float, den: float) -> float:
    return float(np.sqrt(num / den)) if den > 0 else float("inf")


def compare(prog: dict, reference: dict) -> list:
    """The numbers that decide ``correct``; ``prog`` and ``reference`` hold,
    for each checked session, its ``logits`` (steps, V), its full layers'
    factors (per layer and KV head: ``setup`` and ``final``), and the
    queries (per layer and KV head) the program read them with:

    - ``logits``: ‖L − L_ref‖_F / ‖L_ref‖_F over the checked sessions and
      every step, the reference held to the stated precision and taking
      the program's routing;
    - ``routing``: the largest routing margin (``reference_mellum2.moe``)
      of the program's routing under the reference's router: 0 when every
      token took the reference's top-k;
    - ``state``: the final factors' reads at the program's decode queries
      against those of the factors the reference rebuilt and folded from
      the program's own keys and values, ‖·‖_F relative over sessions,
      layers and KV heads (factors compared by what they answer: an
      ill-conditioned Ũ differs entry by entry while its reads agree);
    - ``fold``: the same for the change the turn made to those reads
      (final − setup): 1.0 when no token was folded in;
    - ``moe_dropped``: assignments whose expert output did not reach the
      combine, over every session of every turn of the window.
    """
    lp = np.asarray(prog["logits"], np.float64)
    lr = np.asarray(reference["logits"], np.float64)
    out = [("logits", _ratio(np.sum((lp - lr) ** 2), np.sum(lr ** 2))),
           ("routing", float(prog["routing"]))]
    s_num = s_den = f_num = f_den = 0.0
    for s_p, s_r, queries in zip(prog["states"], reference["states"],
                                 prog["queries"]):
        for (p0, p1), (r0, r1), Q in zip(s_p, s_r, queries):
            a_p1, a_r1 = read_at(Q, *p1), read_at(Q, *r1)
            d_p = a_p1 - read_at(Q, *p0)
            d_r = a_r1 - read_at(Q, *r0)
            s_num += np.sum((a_p1 - a_r1) ** 2)
            s_den += np.sum(a_r1 ** 2)
            f_num += np.sum((d_p - d_r) ** 2)
            f_den += np.sum(d_r ** 2)
    out.append(("state", _ratio(s_num, s_den)))
    out.append(("fold", _ratio(f_num, f_den)))
    out.append(("moe_dropped", float(prog["moe_dropped"])))
    return out


def _session_taps(state: State, turn, i: int, s: int) -> dict:
    """Checked session ``s`` (row ``i`` of the turn's kept sessions): its
    routing over the prompt and the turn (layers, P + G, k) and its full
    layers' landmark inputs, layers first."""
    pre = state.taps[s]
    dec = {n: a[i].swapaxes(0, 1) for n, a in turn.taps.items()
           if n in ("experts", "landmark_q", "landmark_k", "landmark_v")}
    return {"experts": jnp.concatenate([pre["experts"][:, 0],
                                        dec["experts"]], axis=1),
            "landmark_k": pre["landmark_k"][:, 0],
            "landmark_v": pre["landmark_v"][:, 0],
            "landmark_q": dec["landmark_q"],
            "landmark_k_dec": dec["landmark_k"],
            "landmark_v_dec": dec["landmark_v"]}


def _heads(layers: list) -> list:
    """[(outputs, setup, final) per full layer] -> [(setup, final) per layer
    and KV head] as (k_land, UV, U1) in float64."""
    out = []
    for _, setup, final in layers:
        for h in range(setup["k_land"].shape[0]):
            pick = lambda st: tuple(np.asarray(st[n][h], np.float64)
                                    for n in ("k_land", "UV", "U1"))
            out.append((pick(setup), pick(final)))
    return out


def _queries(taps: dict, kv: int) -> list:
    """[per full layer and KV head] the program's decode queries (T·g, d)."""
    q = np.asarray(taps["landmark_q"], np.float64)       # (layers, T, H, d)
    L_, T, H, d = q.shape
    g = H // kv
    q = q.reshape(L_, T, kv, g, d).transpose(0, 2, 1, 3, 4)
    return [q[l, h].reshape(T * g, d) for l in range(L_) for h in range(kv)]


def _program(state: State, win: dict) -> dict:
    turn = win["sample"]
    kv = int(state.cell.config["num_key_value_heads"])
    states, queries = [], []
    for i, s in enumerate(int(x) for x in np.asarray(state.keep)):
        states.append(list(zip(_global_states(state.cache, s),
                               _global_states(turn.cache, s))))
        queries.append(_queries(_session_taps(state, turn, i, s), kv))
    return {"logits": turn.logits, "states": states, "queries": queries,
            "moe_dropped": win["counters"]["moe_dropped"]}


def reference_run(state: State, win: dict, state_dtype) -> dict:
    """The reference for the checked sessions, teacher-forced on the
    program's tokens, routing and landmark inputs, with its landmark
    factors held in ``state_dtype`` between steps."""
    _, P, _, _ = _sizes(state.cell.traffic)
    config = state.cell.config
    act = jnp.dtype(config["activation_dtype"])
    turn = win["sample"]
    logits, states, margins = [], [], []
    for i, s in enumerate(int(x) for x in np.asarray(state.keep)):
        taps = _session_taps(state, turn, i, s)
        tokens = jnp.concatenate([prompt(state.cell, s)[0], turn.tokens[s]])
        lg, margin = ref.forward(
            state.params, config, tokens, P, prefill_key(state.cell, s),
            state_dtype=state_dtype,
            act_dtype=None if act == jnp.float32 else act,
            experts=taps["experts"])
        logits.append(np.asarray(lg[1:]))          # the decode positions
        margins.append(float(jnp.max(margin)))
        states.append(_heads(ref.landmark_replay(
            config, prefill_key(state.cell, s), taps,
            state_dtype=state_dtype)))
    return {"logits": np.stack(logits), "states": states,
            "routing": max(margins)}


def _reference(state: State, win: dict) -> dict:
    if "reference" not in win:
        win["reference"] = reference_run(state, win, jnp.float32)
    return win["reference"]


def check(state: State, win: dict) -> list:
    prog = _program(state, win)
    reference = _reference(state, win)
    prog["routing"] = reference["routing"]
    return compare(prog, reference)


def control(state: State, win: dict) -> list:
    """The reference with its landmark factors held in bfloat16, the
    precision below the configuration's float32 for them, in the program's
    place."""
    low = reference_run(state, win, jnp.bfloat16)
    prog = dict(_program(state, win), logits=low["logits"],
                states=low["states"], routing=low["routing"], moe_dropped=0)
    return compare(prog, _reference(state, win))
