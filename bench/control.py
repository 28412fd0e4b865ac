"""Readings that set a cell's limits: the program's and the control's.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: set the cell up, run a window of
``--seconds``, and print the numbers ``check`` compares for the program
(the lower readings) and those ``control`` gives for the reference computed
one precision below the configuration's, put in the program's place (the
upper readings).  Each seed's line is JSON; the benchmark's own runs never
run the control.  Refuses without a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.run import (BENCH, ROOT, Cell, CompileMeter,  # noqa: E402
                       enable_cache, kind_module, load_json)

import jax  # noqa: E402

from bench import data  # noqa: E402


def readings(workload: str, seeds, seconds: float, devices) -> list:
    bench = load_json(ROOT / "BENCHMARK.json")
    w = {x["name"]: x for x in bench["workloads"]}[workload]
    config = load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    kind = kind_module(traffic)
    meter = CompileMeter()
    out = []
    for seed in seeds:
        cell = Cell(name=workload, config=config, traffic=traffic,
                    chips=int(w["chips"]), seed=seed, seconds=seconds,
                    key=data.seed_key(seed), devices=devices[: w["chips"]],
                    meter=meter)
        t0 = time.perf_counter()
        state = kind.setup(cell, warm=False)
        win = kind.window(state, seconds)
        row = {"seed": seed, "failed": win["failed"],
               "program": dict(kind.check(state, win)),
               "control": dict(kind.control(state, win)),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        out.append(row)
        del state, win
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("refusing to run: no TPU found", file=sys.stderr)
        return 2
    enable_cache()
    readings(args.workload, args.seeds, args.seconds, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
