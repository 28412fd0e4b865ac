"""``required_work`` against hand counts, for every cell, and its
independence of the route the program takes."""
from __future__ import annotations

import jax
import pytest

from bench import run as R
from bench.tests import tiny


def _kind_and_cfg(name):
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    w = {x["name"]: x for x in bench["workloads"]}[name]
    cfg = R.load_json(R.BENCH / "configs" / f"{w['config']}.json")
    tr = R.load_json(R.BENCH / "traffic" / f"{w['traffic']}.json")
    return R.kind_module(tr), cfg, tr


# n, d, c, s + c, probes
CERTIFY = {"susy-rbf.certify": (2 ** 19, 18, 512, 2560, 64),
           "mnist-rbf.certify": (2 ** 18, 784, 512, 2560, 64)}


@pytest.mark.parametrize("name", sorted(CERTIFY))
def test_certify_hand_count(name):
    n, d, c, m, p = CERTIFY[name]
    kind, cfg, tr = _kind_and_cfg(name)
    work = kind.required_work(cfg, tr)
    # all pairs: the distance cross term and the probes, nothing for C
    assert work["sweep_flops"] == 2.0 * n * n * d + 2.0 * n * n * p
    svd = 4 * m * c * c + 22 * c ** 3
    assert work["build_flops"] == (work["sweep_flops"] + 2 * n * c * d
                                   + 2 * m * m * d + svd
                                   + 2 * c * m * m + 2 * c * c * m
                                   + 4 * n * c * p + 2 * c * c * p)
    assert work["sweep_bytes"] == 4 * n * (d + p + p)
    if name == "susy-rbf.certify":
        assert work["sweep_flops"] == 164 * 2 ** 38     # 4.51e13


def test_count_does_not_depend_on_route():
    """The fused Pallas route and the panel route build the same model;
    the count is the algorithm's, taken from the shapes, so it is the same
    number for both, though the fused route's kernel also contracts c
    one-hot gather columns."""
    from repro.core import spsd
    from repro.core.instrument import CountingOperator
    from repro.core.kernelop import PairwiseKernel
    from repro.kernels.pairwise import specs

    cell = tiny.cell("susy-rbf.certify")
    kind = R.kind_module(cell.traffic)
    cfg = cell.config
    X = kind.setup(cell).X
    spec = specs.get_spec("rbf", sigma=float(cfg["sigma"]))
    routes, errs = {}, {}
    for use_pallas in (True, False):
        op = CountingOperator(PairwiseKernel(X, spec, use_pallas=use_pallas))
        _, err = spsd.fast_model_with_error(
            op, jax.random.PRNGKey(3), c=cfg["c"], s=cfg["s"],
            s_sketch="uniform", probes=cfg["probes"])
        routes[use_pallas], errs[use_pallas] = op.last_route, float(err)
    assert routes == {True: "pallas_fused", False: "panel"}
    assert errs[True] == pytest.approx(errs[False], rel=1e-3)
    work = [kind.required_work(dict(cfg, use_pallas=u), cell.traffic)
            for u in (True, False)]
    assert work[0] == work[1]
