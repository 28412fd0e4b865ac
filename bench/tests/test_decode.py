"""The ``decode`` kind at a size the CPU holds: the program's CPU twin
(``SMOKE``, float32) decodes through the same kind code as the chip cell.
A sound run is ``correct``; the control (the reference with its landmark
factors held in bfloat16) and planted faults (the fold skipped, every token
routed to the wrong experts, a capacity-bound dispatch) fail; and
``required_work`` matches hand counts.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import io

import jax
import pytest

from bench import data
from bench import run as R
from bench.tests import tiny

NAME = "mellum2-a2.5b.decode-32k"
TRAFFIC = {"kind": "decode", "sessions": 3, "prompt": 24, "steps": 20,
           "checked_sessions": 2}


def _cell(seed: int = tiny.SEED) -> R.Cell:
    from repro.configs import get_smoke
    kind = R.kind_module(TRAFFIC)
    config = dict(kind.published(get_smoke("mellum2-12b-a2.5b")),
                  arch="mellum2-12b-a2.5b", smoke=True)
    return R.Cell(name=NAME, config=config, traffic=TRAFFIC, chips=1,
                  seed=seed, seconds=0.2, key=data.seed_key(seed),
                  devices=jax.devices()[:1], meter=R.CompileMeter())


def _run(cell) -> dict:
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    saved, R.LIMITS_DIR = R.LIMITS_DIR, tiny.LIMITS_DIR
    try:
        return R.run_cell(cell, bench, False, out=io.StringIO(),
                          err=io.StringIO())
    finally:
        R.LIMITS_DIR = saved


def test_sound_run_is_correct():
    result = _run(_cell())
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["checks"]["moe_dropped"]["value"] == 0


def test_control_fails():
    cell = _cell()
    kind = R.kind_module(cell.traffic)
    state = kind.setup(cell)
    win = kind.window(state, cell.seconds)
    compared = tiny.judge(NAME, kind.control(state, win))
    assert not all(ok for *_, ok in compared), compared


def test_skipped_fold_fails(monkeypatch):
    """The full layers read their factors but never fold the decoded
    tokens in (the defect of the landmark decode before it folded)."""
    from repro.kernels.landmark_attention import kernel as lm_kernel
    from repro.kernels.landmark_attention import ops as lm_ops

    def read_only(q, k_land, U, UV, U1, offset, k_new, v_new,
                  interpret=None):
        N, G, _ = q.shape
        c = U1.shape[-1]
        out = lm_kernel.landmark_attention_padded(
            lm_ops._pad_rows(q), k_land, UV, U1.reshape(N, 1, c),
            offset.reshape(N, 1, 1), interpret=True)
        return out[:, :G], (UV, U1, offset)

    monkeypatch.setattr(lm_ops, "landmark_step", read_only)
    result = _run(_cell())
    assert not result["correct"], result["checks"]
    fold = result["checks"]["fold"]
    assert fold["value"] > fold["limit"], result["checks"]


def test_wrong_routing_fails(monkeypatch):
    """Every token is routed to the experts one over from its top-k: the
    reference takes the program's routing, so only ``routing`` sees it."""
    from repro.models import moe as M
    route = M._route

    def shifted(params, cfg, xf, precision=None):
        w, idx, aux = route(params, cfg, xf, precision)
        return w, (idx + 1) % cfg.n_experts, aux

    monkeypatch.setattr(M, "_route", shifted)
    result = _run(_cell())
    assert not result["correct"], result["checks"]
    routing = result["checks"]["routing"]
    assert routing["value"] > routing["limit"], result["checks"]


def test_capacity_dispatch_fails(monkeypatch):
    """The served MoE bound to a capacity as the training dispatch is (the
    defect of the served path before it was dropless): assignments past an
    expert's capacity are dropped, and the output leaves the reference."""
    import dataclasses

    import jax.numpy as jnp

    from repro.models import moe as M

    def capped(params, cfg, xf, w, idx):
        cfg = dataclasses.replace(cfg, capacity_factor=0.25)
        out = M._dispatch_compute(params, cfg, xf, w, idx)
        E, k = cfg.n_experts, cfg.moe_top_k
        C = -(-max(1, int(cfg.capacity_factor * xf.shape[0] * k / E)) // 8) * 8
        se = jnp.sort(idx.reshape(-1))
        pos = jnp.arange(se.shape[0]) - jnp.searchsorted(se, se, side="left")
        sizes = jnp.bincount(idx.reshape(-1), length=E).astype(jnp.int32)
        return out, sizes, jnp.sum(pos < C, dtype=jnp.int32)

    monkeypatch.setattr(M, "_dropless_compute", capped)
    result = _run(_cell())
    assert not result["correct"], result["checks"]
    logits = result["checks"]["logits"]
    assert logits["value"] > logits["limit"], result["checks"]


# B, P, G; d, H, KV, hd, E, k, ff, V, W, c; sliding and full layers
FULL = (32, 32768, 256, 2304, 32, 4, 128, 64, 8, 896, 98304, 1024, 512, 6, 2)


def test_decode_hand_count():
    (B, P, G, d, H, KV, hd, E, k, ff, V, W, c, n_s, n_f) = FULL
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    w = {x["name"]: x for x in bench["workloads"]}[NAME]
    cfg = R.load_json(R.BENCH / "configs" / f"{w['config']}.json")
    tr = R.load_json(R.BENCH / "traffic" / f"{w['traffic']}.json")
    work = R.kind_module(tr).required_work(cfg, tr)
    L = n_s + n_f
    # per token: q, k, v, o projections; router; 8 SwiGLU experts; the
    # window's QKᵀ and PV on sliding layers; the head
    per_token = (L * (2 * d * H * hd + 2 * 2 * d * KV * hd + 2 * H * hd * d
                      + 2 * d * E + k * 3 * 2 * d * ff)
                 + n_s * 2 * 2 * H * W * hd + 2 * d * V)
    # per (session, KV head): k_t against the landmarks, Ũ r, (Ũ r) v_tᵀ;
    # per (session, query head): q against the landmarks, · UV, · U1
    landmark = B * KV * (2 * c * hd + 2 * c * c + 2 * c * hd) \
        + B * H * (2 * c * hd + 2 * c * hd + 2 * c)
    assert work["landmark_flops"] == landmark
    assert work["step_flops"] == B * per_token + n_f * landmark
    assert work["turn_flops"] == G * work["step_flops"]
    assert per_token == 1_688_469_504
    # bf16 weights: attention and norms of every layer, the head and the
    # final norm; the f32 router; bf16 rings; the f32 landmark factors that
    # do not change (k_land, Ũ) and the token's q, k, v and reads
    attn = L * ((d * H * hd * 2 + 2 * d * KV * hd) * 2 + d * E * 4
                + 2 * d * 2) + d * V * 2 + d * 2
    rings = n_s * B * W * KV * hd * 2 * 2
    lm_bytes = B * KV * 4 * (c * hd + c * c) \
        + B * 4 * (H * hd + 2 * KV * hd + H * hd)
    assert work["landmark_bytes"] == lm_bytes
    # the launch's own record counts the same bytes: one launch per full
    # layer and step over B·KV factor sets, 8 query rows each
    from repro.kernels.landmark_attention import kernel as lm_kernel
    assert lm_kernel.launch_work(B * KV, H // KV, c, hd, hd, True)[
        "bytes"] == lm_bytes
    assert work["static_step_bytes"] == attn + rings + n_f * lm_bytes \
        + B * d * 2
    assert work["expert_bytes"] == 3 * d * ff * 2
    # the issue's sizes: 0.34 GB of attention weights, 0.45 GB of head,
    # 0.4 GB of rings, 0.38 GiB of landmark state
    assert 0.33e9 < L * (d * H * hd * 2 + 2 * d * KV * hd) * 2 < 0.35e9
    assert rings == 402_653_184
    assert 0.37 * 2 ** 30 < n_f * B * KV * 4 * (2 * c * hd + c * c) \
        < 0.39 * 2 ** 30


@pytest.mark.parametrize("sizes,delivered,touched,dropped", [
    ([[[2, 0, 1], [0, 0, 3]]], [[3, 3]], [3], 0),
    ([[[1, 0, 0], [3, 0, 0]], [[0, 1, 1], [1, 1, 1]]], [[1, 3], [2, 3]],
     [2, 5], 3),
])
def test_touched_and_dropped_from_counts(sizes, delivered, touched, dropped):
    """Experts touched per step, from the program's dispatch counts
    (steps, layers, E), and assignments dropped, from the rows it delivered
    to the combine (steps, layers); 1 session at top-3."""
    kind = R.kind_module(TRAFFIC)
    taps = {"sizes": sizes, "delivered": delivered}
    assert kind.touched_per_step(taps) == touched
    assert kind.dropped(taps, 1, 3) == dropped
