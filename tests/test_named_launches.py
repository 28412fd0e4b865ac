"""Names, launch records and phase scopes, checked in lowered programs.

Every Pallas launch carries a stable name and, for the pairwise launches, a
record of the MXU work it issues (``kernel.launch_record``); the certified
build's phases run inside ``instrument.span`` scopes.  Both reach the
program's HLO: the record as the custom call's ``kernel_metadata`` frontend
attribute, the scopes in every op's ``op_name``.  These tests lower for TPU
here (no chip, no TPU compiler), with the Pallas interpret choice forced
off, and read what a device trace will read.
"""
import json
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import instrument, spsd
from repro.core.kernelop import PairwiseKernel
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.landmark_attention import kernel as lm_kernel
from repro.kernels.pairwise import kernel as pw_kernel
from repro.kernels.pairwise import ops as pw_ops
from repro.kernels.pairwise import specs

PHASES = ("spsd.select", "sweep.pallas_fused", "spsd.sketch_block",
          "spsd.fast_u", "spsd.certify")


def _tpu_lowered(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


def _launches(lowered):
    """(instruction name, launch record) of every Pallas custom call in a
    lowered program's HLO text."""
    hlo = lowered.as_text(dialect="hlo")
    out = []
    for chunk in re.split(r"\n(?=\s*(?:ROOT\s+)?[\w.\-]+ = )", hlo):
        if 'custom_call_target="tpu_custom_call"' not in chunk:
            continue
        rec = re.search(r"kernel_metadata=(\{[^{}]*\})", chunk)
        out.append((chunk.split("=")[0].strip(),
                    json.loads(rec.group(1)) if rec else None))
    return out


def _op_names(lowered):
    """The name paths of the lowered ops (what becomes their ``op_name``)."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


def _scoped(x):
    with instrument.span("spsd.phase"):
        y = jnp.tanh(x @ x.T)
    return y.sum()


@instrument.span("spsd.phase")
def _decorated_body(x):
    return jnp.tanh(x @ x.T)


def _decorated(x):
    return _decorated_body(x).sum()


def _plain(x):
    return jnp.tanh(x @ x.T).sum()


@pytest.mark.parametrize("fn", [_scoped, _decorated],
                         ids=["context", "decorator"])
def test_span_names_ops_and_leaves_the_module_unchanged(fn):
    x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    lowered = jax.jit(fn).lower(x)
    names = _op_names(lowered)
    assert any("/spsd.phase/dot_general" in n for n in names), names
    assert any("/spsd.phase/tanh" in n for n in names), names
    # stripped of locations (the op_name metadata), the module is the
    # one lowered without the span
    strip = lambda t: re.sub(r"@jit_\w+", "@jit_f", t)            # noqa: E731
    assert strip(lowered.as_text()) == strip(jax.jit(_plain).lower(x).as_text())


def test_certify_build_lowered_for_tpu_carries_records_and_scopes(
        monkeypatch):
    """A certify-shaped build (n = 2048, c = 64, s = 256, 16 probes, d = 18)
    holds one sweep launch whose record counts 2·n²·(d + 128) MXU FLOPs for
    K·Z (the 16 probes padded to 128) plus 2·n·128·d for C from the 64
    landmark points (padded to 128), one S^T K S block launch, and ops under
    all five phase scopes."""
    monkeypatch.setattr(pw_ops, "_interpret_mode", lambda: False)
    n, d, c, s, p = 2048, 18, 64, 256, 16
    spec = specs.rbf(4.27)

    def build(X, key):
        op = PairwiseKernel(X, spec, use_pallas=True)
        ap, err = spsd.fast_model_with_error(
            op, key, c=c, s=s, s_sketch="uniform", probes=p,
            selection="uniform")
        return ap.C, ap.U, err

    lowered = _tpu_lowered(build, jax.ShapeDtypeStruct((n, d), jnp.float32),
                           jax.random.key(0))
    launches = _launches(lowered)
    sweeps = [r for _, r in launches if r["kernel"] == "pairwise_matmat_multi"]
    assert len(sweeps) == 1
    assert sweeps[0]["mxu_flops"] == str(2 * n * n * (d + 128)
                                         + 2 * n * 128 * d)
    assert sweeps[0]["entries"] == str(n * (n + 128))
    assert sweeps[0]["precision"] == "f32"
    assert sweeps[0]["landmarks"] == "128"
    blocks = [r for _, r in launches if r["kernel"] == "pairwise_block"]
    assert len(blocks) == 1
    m = 384                              # s + c = 320 sketch rows, padded
    assert blocks[0]["entries"] == str(m * m)
    assert blocks[0]["mxu_flops"] == str(2 * m * m * d)
    assert len(launches) == 2
    names = _op_names(lowered)
    for phase in PHASES:
        assert any(f"/{phase}/" in n for n in names), phase


def test_decode_turn_lowered_for_tpu_carries_records_and_scopes(
        monkeypatch):
    """mellum2's CPU twin (3 sliding + 1 full layer), one decode turn of 4
    steps for 3 sessions: the full layer's step is one landmark launch
    whose record counts the fold and the read of 3 × 2 factor sets, and the
    turn, the prefill and the landmark build run under their spans."""
    from repro.configs import get_smoke
    from repro.kernels.landmark_attention import ops as lm_ops
    from repro.models.model import build_model
    monkeypatch.setattr(lm_ops, "_interpret_mode", lambda: False)
    cfg = get_smoke("mellum2-12b-a2.5b")
    m = build_model(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    B, P = 3, 16
    tokens = jax.ShapeDtypeStruct((B, P), jnp.int32)
    lowered = _tpu_lowered(
        lambda p, t, k: m.generate(p, {"tokens": t}, k,
                                   jnp.zeros((1,), jnp.int32), steps=4),
        params, tokens, jax.random.key(0))
    records = [r for _, r in _launches(lowered)
               if r and r["kernel"] == "landmark_attention"]
    assert len(records) == 1                 # in the loop body, once
    n, g, c, d = B * cfg.n_kv_heads, 8, cfg.landmark_c, cfg.head_dim
    work = lm_kernel.launch_work(n, g, c, d, d, fold=True)
    assert records[0] == {"kernel": "landmark_attention",
                          "flops": str(work["flops"]),
                          "bytes": str(work["bytes"]),
                          "inplace_bytes": str(work["inplace_bytes"]),
                          "fold": "1"}
    names = _op_names(lowered)
    for scope in ("serve.prefill", "landmark.build", "serve.decode"):
        assert any(f"{scope}/" in n for n in names), scope


def _pw_multi(X, V):
    return pw_kernel.pairwise_matmat_multi_padded(specs.rbf(1.0), X, X, (V,))


def _pw_slab(X, V):
    return pw_kernel.pairwise_matmat_multi_slab(
        specs.rbf(1.0), X, jnp.zeros((1,), jnp.int32), 1, (V,))


def _pw_block(X, V):
    del V
    return pw_kernel.pairwise_block_padded(specs.rbf(1.0), X, X)


def _flash(X, V):
    del V
    q = X.reshape(1, 1, 256, 16)
    return fa_kernel.flash_attention_padded(q, q, q, 256, 256, True, None)


def _landmark(X, V):
    return lm_kernel.landmark_attention_padded(
        X[None], X[None, :128], V[None, :128, :16], V[None, :1, :128],
        jnp.zeros((1, 1, 1)))


@pytest.mark.parametrize("name,fn", [
    ("pairwise_matmat_multi", _pw_multi),
    ("pairwise_matmat_slab", _pw_slab),
    ("pairwise_block", _pw_block),
    ("flash_attention", _flash),
    ("landmark_attention", _landmark),
])
def test_every_pallas_launch_carries_its_name(name, fn):
    lowered = _tpu_lowered(fn, jax.ShapeDtypeStruct((256, 16), jnp.float32),
                           jax.ShapeDtypeStruct((256, 128), jnp.float32))
    launches = _launches(lowered)
    assert [r["kernel"] for _, r in launches] == [name]
    assert f'kernel_name = "{name}"' in lowered.as_text()


@pytest.mark.parametrize("stat,nr,d,ms,landmarks,segments,mxu_flops", [
    # the sweep launch of susy-rbf.certify: n = 2^19, d = 18, 64 probes
    # padded to 128, and C from 512 landmark points
    ("rbf", 2 ** 19, 18, (128,), 512, 0,
     2 * 2 ** 38 * 146 + 2 * 2 ** 19 * 512 * 18),
    # mnist-rbf.certify: n = 2^18, d = 784
    ("rbf", 2 ** 18, 784, (128,), 512, 0,
     2 * 2 ** 36 * 912 + 2 * 2 ** 18 * 512 * 784),
    # the sign-split l1 route: two contractions of inner width 2·d·B
    ("laplacian", 256, 8, (128,), 0, 7,
     2 * 256 * 256 * (2 * 2 * 8 * 7 + 128)),
    # the VPU l1 loop issues only the right-hand-side contraction
    ("laplacian", 256, 8, (128,), 0, 0, 2 * 256 * 256 * 128),
], ids=["susy", "mnist", "signsplit", "vpu_loop"])
def test_launch_work_hand_values(stat, nr, d, ms, landmarks, segments,
                                 mxu_flops):
    spec = specs.get_spec(stat)
    route = "mxu_signsplit" if segments else None
    work = pw_kernel.launch_work(spec, nr, nr, d, sum(ms), route, segments,
                                 landmarks)
    assert work["mxu_flops"] == mxu_flops
    assert work["entries"] == nr * (nr + landmarks)
    edges = jnp.zeros((d, segments - 1)) if segments else None
    rec = pw_kernel.launch_record("k", spec, nr, nr, d, ms, edges, landmarks)
    expected = {"kernel": "k", "mxu_flops": str(mxu_flops),
                "entries": str(nr * (nr + landmarks)), "precision": "f32",
                "passes": "not counted", "tile": "128x128"}
    if landmarks:
        expected["landmarks"] = str(landmarks)
    assert rec == expected


@pytest.mark.parametrize("name,fn,nr,result,operands", [
    ("pairwise_matmat_multi", _pw_multi, 256, "f32[256,128]{1,0}",
     "f32[256,16]{1,0}, f32[256,16]{1,0}, f32[256,128]{1,0}"),
    ("pairwise_matmat_slab", _pw_slab, 128, "f32[128,128]{1,0}",
     "s32[1]{0}, f32[256,16]{1,0}, f32[256,16]{1,0}, f32[256,128]{1,0}"),
])
def test_launch_without_landmarks_is_unchanged(name, fn, nr, result,
                                               operands):
    """A fused launch given no landmark points (serving ``cross``, bundles
    without a gather) keeps its record and its ``pallas_call`` signature:
    the products alone out, no landmark operand, no ``landmarks`` field."""
    lowered = _tpu_lowered(fn, jax.ShapeDtypeStruct((256, 16), jnp.float32),
                           jax.ShapeDtypeStruct((256, 128), jnp.float32))
    [(_, rec)] = _launches(lowered)
    assert rec == {"kernel": name,
                   "mxu_flops": str(2 * nr * 256 * (16 + 128)),
                   "entries": str(nr * 256), "precision": "f32",
                   "passes": "not counted", "tile": "128x128"}
    sig = re.search(r"= (\S+) custom-call\(.*?operand_layout_constraints="
                    r"\{(.*?)\}, frontend", lowered.as_text(dialect="hlo"))
    assert sig.groups() == (result, operands)
