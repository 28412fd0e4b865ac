"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells.  A cell's
configuration is ``bench/configs/<config>.json``, its traffic
``bench/traffic/<traffic>.json``, whose ``kind`` names the module under
``bench/kinds/`` that sets it up, drives its window and checks what the
window produced; its limits are ``bench/limits/<cell>.json``.  A per-layer
metric is read by ``bench/metrics/<metric>.py``.  So a new configuration,
traffic mix or metric is new files and new entries, and no edit.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics, read from the trace and the program's counters.  The run
refuses (exit 2, no result line) without a TPU or with fewer chips than the
cell asks for.  The last line of standard output is the JSON result; the
numbers compared for ``correct``, each beside its limit, are the last lines
of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import data, peaks  # noqa: E402

#: the persistent compilation cache when JAX_COMPILATION_CACHE_DIR is unset
CACHE_DIR = ROOT / ".jax_cache"
#: where ``--trace 1`` writes its profile
TRACE_DIR = ROOT / ".bench_traces"
#: each cell's limits, ``<cell>.json``
LIMITS_DIR = BENCH / "limits"


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    key: object
    devices: list
    meter: CompileMeter


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """A module from its file path (metric names hold dots)."""
    name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_module(traffic: dict):
    return load_module(BENCH / "kinds" / f"{traffic['kind']}.py")


def reported(metrics: list, cell: str, e2e: set = None) -> list:
    """The metrics of ``metrics`` that ``cell`` reports: those that list it
    under ``workloads``, and those without the key, which every cell
    reporting their ``moves`` metric (or, end to end, every cell)
    reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif e2e is None or m["moves"] in e2e:
            out.append(m)
    return out


def judge(cell_name: str, checks) -> list:
    """(name, value, limit, within) for each compared number, against
    ``bench/limits/<cell>.json``; a number that is not finite fails."""
    limits = load_json(LIMITS_DIR / f"{cell_name}.json")
    return [(name, value, float(limits[name]),
             math.isfinite(value) and value <= float(limits[name]))
            for name, value in checks]


def enable_cache():
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the data and reference programs compile in well under the default
    # 1 s threshold; keep them too, so a second run finds them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(cell: Cell, bench: dict, trace: bool, out=sys.stdout,
             err=sys.stderr) -> dict:
    """Set up, drive the window, check, and return the result dict."""
    kind = kind_module(cell.traffic)
    state = kind.setup(cell)
    jax.effects_barrier()
    setup_s = time.perf_counter() - T_START
    compiles0 = cell.meter.compiles

    trace_dir = None
    if trace:
        trace_dir = TRACE_DIR / f"{cell.name}-{cell.seed}"
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans only, no per-call
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = kind.window(state, cell.seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    win_compiles = cell.meter.compiles - compiles0
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in cell.devices]

    compared = judge(cell.name, kind.check(state, win))
    correct = win["failed"] == 0 and all(ok for *_, ok in compared)

    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": max(mem)}
    counters = dict(win["counters"], compiles_in_window=win_compiles)
    e2e = dict(win["e2e"], setup_s=setup_s)
    cell_e2e = {m["name"] for m in reported(bench["end_to_end"], cell.name)}
    result = {"correct": bool(correct), "attempted": int(win["attempted"]),
              "failed": int(win["failed"])}
    breakdown = None
    if not trace:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in reported(bench["end_to_end"], cell.name)}
    else:
        from bench import trace as trace_lib
        red = trace_lib.reduce_trace(
            trace_lib.find_xplane(str(trace_dir)),
            [d.id for d in cell.devices])
        shutil.rmtree(trace_dir, ignore_errors=True)    # tens of MB each
        device.update(busy_s=red.busy_s_mean, window_s=red.window_s)
        ctx = {"cell": cell, "win": win, "counters": counters, "e2e": e2e,
               "trace": red, "chips": len(cell.devices),
               "peaks": peaks.peaks_for(dev.device_kind),
               "work": kind.required_work(cell.config, cell.traffic)}
        metrics = {}
        for m in reported(bench["per_layer"], cell.name, cell_e2e):
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
        print("trace ops: " + json.dumps(red.top_ops(20)), file=out)
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in compared}

    for line in win["lines"]:
        print(line, file=out)
    print(f"setup_s {setup_s!r}; compiles in window {win_compiles}; "
          f"backend compiles {cell.meter.compiles} ({cell.meter.seconds!r} s), "
          f"persistent cache hits {cell.meter.hits}; peak bytes per device "
          f"{mem}", file=out)
    out.flush()
    for n, v, lim, ok in compared:
        print(f"check {n} {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}",
              file=err)
    print(f"failed {win['failed']} of {win['attempted']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    w = cells[args.workload]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        print(f"refusing to run: {w['name']} needs {w['chips']} TPU chip(s), "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    enable_cache()
    cell = Cell(name=w["name"],
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                chips=int(w["chips"]), seed=args.seed, seconds=args.seconds,
                key=data.seed_key(args.seed), devices=devices[: w["chips"]],
                meter=CompileMeter())
    run_cell(cell, bench, bool(args.trace))
    return 0


if __name__ == "__main__":
    sys.exit(main())
