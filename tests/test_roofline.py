"""Unit tests for the roofline HLO miners and dry-run helpers."""

import pytest

from repro.configs import SHAPES, get_config
from repro.launch import roofline as rl
from repro.launch.dryrun import _reduced_cfg, scan_reps

HLO = """\
HloModule test, is_scheduled=true

%fused_computation (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %c = f32[] constant(2)
  %b = f32[128,128]{1,0} broadcast(%c), dimensions={}
  ROOT %m = f32[128,128]{1,0} multiply(%p0, %b)
}

ENTRY %main (a: bf16[128,256], b: bf16[256,128]) -> f32[128,128] {
  %a = bf16[128,256]{1,0} parameter(0)
  %b = bf16[256,128]{1,0} parameter(1)
  %dot.1 = f32[128,128]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[128,128]{1,0} all-gather(%dot.1), replica_groups={}, dimensions={0}
  %ar = f32[128,128]{1,0} all-reduce(%ag), to_apply=%add
  %fusion.1 = f32[128,128]{1,0} fusion(%ar), kind=kLoop, calls=%fused_computation
  ROOT %copy.1 = f32[128,128]{1,0} copy(%fusion.1)
}
"""

F32_128 = 128 * 128 * 4
BF16_A = 128 * 256 * 2


def test_collective_bytes():
    got = rl.collective_bytes(HLO)
    assert got["all-gather"] == F32_128
    assert got["all-reduce"] == F32_128
    assert got["all-to-all"] == 0


def test_hbm_bytes_counts_memory_ops_only():
    got = rl.hbm_bytes(HLO)
    # dot: result + 2 operands; ag/ar: result+operand each; copy: res+operand
    # kLoop fusion skipped (not wrapped_*); interior of %fused skipped
    expect = (F32_128 + 2 * BF16_A) + 2 * (2 * F32_128) + 2 * F32_128
    assert got == expect, (got, expect)


def test_shape_bytes():
    assert rl._shape_bytes("bf16", "4,8") == 64
    assert rl._shape_bytes("f32", "") == 4


def test_model_flops_conventions():
    cfg = get_config("yi-6b")
    tr = rl.model_flops(cfg, SHAPES["train_4k"])
    pf = rl.model_flops(cfg, SHAPES["prefill_32k"])
    n = cfg.active_param_count()
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert pf == pytest.approx(2 * n * 32 * 32768)
    w = get_config("whisper-large-v3")
    tw = rl.model_flops(w, SHAPES["train_4k"])
    assert tw == pytest.approx(3 * w.param_count() * 256 * (4096 + 512))


def test_reduced_cfg_and_scan_reps():
    cfg = get_config("deepseek-v3-671b")
    assert scan_reps(cfg) == 58
    r1 = _reduced_cfg(cfg, 1)
    assert r1.n_layers == 4 and not r1.scan_layers and r1.unroll_scans
    rg = get_config("recurrentgemma-2b")
    assert scan_reps(rg) == 8
    assert _reduced_cfg(rg, 2).n_layers == 3 + 2 * 3 + 2 - 3  # 3*2 + rem 2
    w = get_config("whisper-large-v3")
    assert scan_reps(w) == 32
    assert _reduced_cfg(w, 2).n_enc_layers == 2


def test_roofline_finalize_bottleneck():
    r = rl.Roofline(arch="a", shape="s", mesh="m", chips=256,
                    hlo_gflops=197_000.0, hlo_gbytes=10.0,
                    coll_gbytes=100_000.0, coll_by_kind={},
                    model_gflops=197_000.0 * 256,
                    bytes_per_chip=0.0).finalize()
    assert r.compute_s == pytest.approx(1.0)
    assert r.bottleneck == "collective"
    assert r.useful_flops_frac == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# hardware profiles + the pairwise-launch scoring model
# ---------------------------------------------------------------------------

def test_finalize_accepts_a_hardware_profile():
    """The peak rates are a parameter: the same counted terms score
    differently (and are labeled differently) under another profile."""
    toy = rl.HardwareProfile("toy", peak_flops=1e12, hbm_bw=1e11,
                             link_bw=1e10)
    kw = dict(arch="a", shape="s", mesh="m", chips=1,
              hlo_gflops=1000.0, hlo_gbytes=50.0, coll_gbytes=0.0,
              coll_by_kind={}, model_gflops=1000.0, bytes_per_chip=0.0)
    r = rl.Roofline(**kw).finalize(toy)
    assert r.profile_name == "toy"
    assert r.compute_s == pytest.approx(1.0)       # 1000 GFLOP / 1 TFLOP/s
    assert r.memory_s == pytest.approx(0.5)        # 50 GB / 100 GB/s
    # default stays v5e (the pre-profile behavior, relied on above)
    assert rl.Roofline(**kw).finalize().profile_name == "v5e"


def test_default_profile_is_honest_about_cpu():
    prof = rl.default_profile()
    import jax
    expected = rl.V5E if jax.default_backend() == "tpu" else rl.CPU_INTERPRET
    assert prof is expected
    # module aliases stay pinned to v5e for back-compat
    assert rl.PEAK_FLOPS == rl.V5E.peak_flops


@pytest.mark.parametrize("kind,expected", [("TPU v5 lite", "v5e"),
                                           ("TPU v99", None)])
def test_default_profile_keys_tpu_peaks_by_device_kind(monkeypatch, kind,
                                                       expected):
    """A TPU's peaks come from its ``device_kind``; an unlisted TPU raises
    instead of borrowing another chip's numbers."""
    import types

    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    if expected is None:
        with pytest.raises(ValueError, match="TPU v99"):
            rl.default_profile()
    else:
        assert rl.default_profile().name == expected


def test_pairwise_launch_model_flop_split():
    """The unit split is the point: sign-split moves l1dist work from the
    VPU bucket to the MXU bucket; the VPU loop has zero MXU stat FLOPs."""
    from repro.kernels.pairwise import specs as pw_specs
    from repro.kernels.pairwise.kernel import launch_work
    nr = nc = 256
    d, m, B = 8, 16, 7
    lap = pw_specs.suggested_spec("laplacian", d)
    mxu_form = launch_work(lap, nr, nc, d, m, l1_route="mxu_signsplit",
                           segments=B)
    vpu_form = launch_work(lap, nr, nc, d, m, l1_route="vpu_loop")
    entries = nr * nc
    inner = 2 * d * B
    assert mxu_form["mxu_flops"] == (4 * inner + 2 * m) * entries
    assert vpu_form["vpu_flops"] == (4 * d + 8) * entries
    assert vpu_form["mxu_flops"] == 2 * m * entries
    # dot: pure MXU statistic
    lin = pw_specs.suggested_spec("linear", d)
    lin_model = launch_work(lin, nr, nc, d, m)
    assert lin_model["mxu_flops"] == (2 * d + 2 * m) * entries
    # bf16 tiles halve the point bytes on the HBM floor
    rbf = pw_specs.suggested_spec("rbf", d)
    f32b = launch_work(rbf, nr, nc, d, m)["hbm_bytes"]
    bf16b = launch_work(rbf.with_precision("bf16_f32acc"), nr, nc, d,
                        m)["hbm_bytes"]
    assert bf16b < f32b


def test_achieved_vs_roofline_report():
    from repro.kernels.pairwise import specs as pw_specs
    toy = rl.HardwareProfile("toy", peak_flops=1e12, hbm_bw=1e11,
                             link_bw=1e10)
    spec = pw_specs.suggested_spec("rbf", 8)
    rep = rl.achieved_vs_roofline(spec, (256, 256, 8), None,
                                  measured_s=1.0, m_total=16, profile=toy)
    assert rep["kernel"] == "rbf" and rep["precision"] == "f32"
    assert rep["profile"] == "toy" and rep["chips"] == 1
    assert rep["bottleneck"] in ("compute", "memory")
    assert rep["roofline_s"] == pytest.approx(
        max(rep["compute_s"], rep["memory_s"]))
    assert rep["achieved_frac"] == pytest.approx(rep["roofline_s"])
    # a 4x faster launch achieves 4x the fraction
    rep4 = rl.achieved_vs_roofline(spec, (256, 256, 8), None,
                                   measured_s=0.25, m_total=16, profile=toy)
    assert rep4["achieved_frac"] == pytest.approx(4 * rep["achieved_frac"])
