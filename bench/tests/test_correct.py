"""``correct`` at a size the CPU holds: a sound run passes; the control
(the reference one precision below the configuration's, in the program's
place) and each fault the cells can have, planted under a whole run, fail.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.tests import tiny

CERTIFY = ["susy-rbf.certify", "mnist-rbf.certify"]


@pytest.mark.parametrize("name", CERTIFY)
def test_sound_run_is_correct(name):
    result = tiny.run(tiny.cell(name))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("name", CERTIFY)
def test_control_fails(name):
    cell = tiny.cell(name)
    kind = R.kind_module(cell.traffic)
    state = kind.setup(cell)
    win = kind.window(state, cell.seconds)
    compared = tiny.judge(name, kind.control(state, win))
    assert not all(ok for *_, ok in compared), compared


def _patch_build(monkeypatch, fault):
    from repro.core import spsd
    orig = spsd.fast_model_with_error

    def broken(K, key, *a, **kw):
        if fault == "state_unchanged":
            key = jax.random.PRNGKey(0)
        ap, err = orig(K, key, *a, **kw)
        if fault == "C_altered":
            ap = ap._replace(C=ap.C.at[7, 3].add(1e-3))
        if fault == "U_altered":
            ap = ap._replace(U=ap.U * 1.01)
        if fault == "error_altered":
            err = err * 1.01
        if fault == "U_and_error_altered":
            # U scaled and the certificate recomputed to match it, so that
            # the error still agrees with the model it certifies
            ap = ap._replace(U=ap.U * 1.01)
            Z = jax.random.rademacher(jax.random.fold_in(key, 777),
                                      (K.n, kw["probes"]), dtype=jnp.float32)
            KZ = K.matmat(Z)
            res = KZ - ap.matmat(Z)
            err = jnp.sum(res * res) / jnp.sum(KZ * KZ)
        if fault == "U_zero":
            ap = ap._replace(U=jnp.zeros_like(ap.U))
            err = jnp.ones_like(err)
        return ap, err

    monkeypatch.setattr(spsd, "fast_model_with_error", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "C_altered",
                                   "U_altered", "error_altered",
                                   "U_and_error_altered", "U_zero"])
def test_certify_fault_fails(monkeypatch, fault):
    _patch_build(monkeypatch, fault)
    result = tiny.run(tiny.cell("susy-rbf.certify"))
    assert not result["correct"], result["checks"]
    if fault in ("U_and_error_altered", "U_zero"):
        # the certificate agrees with the altered model: only the model's
        # action against the reference's can catch it
        model = result["checks"]["model"]
        assert model["value"] > model["limit"], result["checks"]
