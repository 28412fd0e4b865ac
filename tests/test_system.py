"""Whole-system sanity: public API imports, the registry covers the
assigned 40-cell matrix, and the benchmark's oracles stand apart from the
program."""
import ast
import importlib
import pathlib

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


def test_public_imports():
    for mod in [
        "repro.core.spsd", "repro.core.cur", "repro.core.sketch",
        "repro.core.eig", "repro.core.kernelop", "repro.core.leverage",
        "repro.core.adaptive", "repro.core.sketched_attention",
        "repro.models.model", "repro.models.transformer",
        "repro.models.attention", "repro.models.moe",
        "repro.models.recurrent", "repro.models.layers",
        "repro.optim", "repro.data", "repro.checkpoint", "repro.runtime",
        "repro.distributed", "repro.configs",
        "repro.launch.mesh", "repro.launch.steps", "repro.launch.roofline",
        "repro.kernels.flash_attention.ops",
        "repro.kernels.landmark_attention.ops",
        "repro.kernels.pairwise.ops",
        "repro.kernels.pairwise.specs",
        "repro.kernels.rbf_sketch.ops",
    ]:
        importlib.import_module(mod)


def test_cell_matrix():
    from repro.configs import ARCHS, cells, shapes_for, LONG_CONTEXT_OK
    assert len(ARCHS) == 11
    cs = list(cells())
    # 11 archs x 4 shapes - 8 long_500k skips = 36 runnable cells
    assert len(cs) == 36
    for a in ARCHS:
        names = [s.name for s in shapes_for(a)]
        assert "train_4k" in names and "prefill_32k" in names \
            and "decode_32k" in names
        assert ("long_500k" in names) == (a in LONG_CONTEXT_OK)


def test_param_counts_match_published():
    from repro.configs import get_config
    expect = {"xlstm-125m": (0.05e9, 0.2e9),
              "gemma3-12b": (10e9, 13e9),
              "minitron-4b": (3.5e9, 4.5e9),
              "yi-9b": (8e9, 9.5e9),
              "yi-6b": (5.5e9, 6.5e9),
              "deepseek-v3-671b": (650e9, 690e9),
              "qwen2-moe-a2.7b": (13e9, 15e9),
              "chameleon-34b": (32e9, 36e9),
              "whisper-large-v3": (1.4e9, 1.7e9),
              "recurrentgemma-2b": (2.5e9, 3.2e9),
              "mellum2-12b-a2.5b": (11.5e9, 12.5e9)}
    for a, (lo, hi) in expect.items():
        n = get_config(a).param_count()
        assert lo <= n <= hi, (a, n)


def test_deepseek_active_params():
    from repro.configs import get_config
    cfg = get_config("deepseek-v3-671b")
    na = cfg.active_param_count()
    assert 34e9 <= na <= 40e9, na


@pytest.mark.parametrize("name", ["reference.py", "reference_mellum2.py"])
def test_bench_reference_imports_nothing_of_the_program(name):
    """The plain references that decide ``correct`` (and that the tests
    check the program against) must not share code with the program."""
    tree = ast.parse((BENCH / name).read_text())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.append(node.module)
    assert not [m for m in found if m.split(".")[0] == "repro"], found
