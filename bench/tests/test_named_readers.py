"""The readers of the program's own launch records (``metrics/_named.py``,
``sweep_useful_share``, ``model_self_ms``), on hand-made HLO text and on a
trace recorded on a v5e.

The recording (``data/named.xplane.pb``, one TPU v5e): one tiny certify
build at ``tiny.py``'s sizes (``susy-rbf``: d = 18, n = 2048, c = 64,
s = 256, 16 probes) with named, recorded Pallas launches and phase scopes,
run inside a host span ``window``; ``data/named.json`` holds its sizes and
builds.  The recording's ``/host:metadata`` plane (the compiled modules'
protos, 880 KB, which no reader reads) is left out.
"""
from __future__ import annotations

import json
import os
import re
import types

import pytest

from bench import trace
from bench.kinds import certify
from bench.metrics import _named, model_self_ms, sweep_useful_share
from bench.metrics._common import sweep_pattern

DATA = os.path.join(os.path.dirname(__file__), "data")
NAMED = os.path.join(DATA, "named.xplane.pb")

#: a custom call as the TPU trace names it: its record's JSON spans lines
SWEEP_TEXT = (
    '%pairwise_matmat_multi.1 = (f32[2048,128]{1,0:T(8,128)}, '
    'f32[2048,128]{1,0:T(8,128)}) custom-call(f32[2048,18]{1,0} %x, '
    'f32[2048,18]{1,0} %x), custom_call_target="tpu_custom_call", '
    'frontend_attributes={kernel_metadata={\n"entries":"4194304",\n'
    '"kernel":"pairwise_matmat_multi",\n"mxu_flops":"2298478592",\n'
    '"passes":"not counted",\n"precision":"f32"\n}}')


def test_launch_record_from_the_trace_name():
    rec = _named.launch_record(SWEEP_TEXT)
    assert rec["kernel"] == "pairwise_matmat_multi"
    assert rec["mxu_flops"] == "2298478592"
    assert _named.launch_record("%fusion.2 = f32[] fusion(%a)") is None
    # the readers that match the sweep by its output shape still find it
    ctx = {"cell": types.SimpleNamespace(config={"n": 2048})}
    assert re.search(sweep_pattern(ctx), SWEEP_TEXT)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "named.json")) as f:
        meta = json.load(f)
    red = trace.reduce_trace(NAMED)
    ctx = {"trace": red, "chips": 1,
           "counters": {"builds": meta["builds"]},
           "work": certify.required_work(meta["config"], {})}
    return meta, red, ctx


def test_sweep_useful_share_is_required_over_issued_work(recorded):
    """Exactly (d + p) / (d + 128 + 128): the 64 gather columns and the 16
    probes each ride a 128-column right-hand side."""
    meta, red, ctx = recorded
    cfg = meta["config"]
    d, p = cfg["d"], cfg["probes"]
    found = _named.sweep_launches(red)
    assert [rec["kernel"] for *_, rec in found] == ["pairwise_matmat_multi"]
    assert sum(c for _, c, _, _ in found) == meta["builds"]
    assert sweep_useful_share.read(ctx) == pytest.approx(
        100.0 * (d + p) / (d + 128 + 128), rel=1e-12)


def test_model_self_ms_is_busy_minus_the_named_sweep(recorded):
    """Against the recording's own events: the union of the device's op
    intervals inside the window, less the events of the launch whose
    record names the sweep kernel."""
    from jax.profiler import ProfileData
    meta, red, ctx = recorded
    pd = ProfileData.from_file(NAMED)
    w0, w1 = [(e.start_ns, e.start_ns + e.duration_ns)
              for pl in pd.planes if pl.name.startswith("/host:")
              for ln in pl.lines for e in ln.events if e.name == "window"][0]
    plane = pd.find_plane_with_name("/device:TPU:0")
    ivals, sweep_ns = [], 0.0
    for ln in plane.lines:
        if ln.name != "XLA Ops":
            continue
        for e in ln.events:
            a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if b > a:
                ivals.append((a, b))
                if '"kernel":"pairwise_matmat_multi"' in e.name:
                    sweep_ns += b - a
    busy_ns = sum(b - a for a, b in trace._union(ivals))
    assert 0 < sweep_ns < busy_ns
    assert model_self_ms.read(ctx) == pytest.approx(
        1e-6 * (busy_ns - sweep_ns) / meta["builds"], rel=1e-9)


def test_readers_find_nothing_in_a_trace_without_records():
    """The parent program's trace: no records — None, and no raise."""
    red = trace.reduce_trace(os.path.join(DATA, "tiny.xplane.pb"))
    ctx = {"trace": red, "chips": 1, "counters": {"builds": 1},
           "work": {"sweep_flops": 1.0}}
    assert sweep_useful_share.read(ctx) is None
    assert model_self_ms.read(ctx) is None
