"""Cells of the real benchmark at sizes the CPU holds, for the tests.

The limits at this size are ``data/limits/<cell>.json``, set like the
chip's from the program's and the control's readings at this size (13
seeds each, listed beside each limit in the file)."""
from __future__ import annotations

import io

import jax

from bench import data
from bench import run as R

SEED = 2 ** 33 + 7          # wider than 32 bits, as benchmark seeds may be

TINY_CONFIG = {"n": 2048, "c": 64, "s": 256, "probes": 16}
LIMITS_DIR = R.BENCH / "tests" / "data" / "limits"


def cell(name: str, seconds: float = 0.5, seed: int = SEED) -> R.Cell:
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    w = {x["name"]: x for x in bench["workloads"]}[name]
    config = R.load_json(R.BENCH / "configs" / f"{w['config']}.json")
    traffic = R.load_json(R.BENCH / "traffic" / f"{w['traffic']}.json")
    config.update(TINY_CONFIG)
    return R.Cell(name=name, config=config, traffic=traffic, chips=1,
                  seed=seed, seconds=seconds, key=data.seed_key(seed),
                  devices=jax.devices()[:1], meter=R.CompileMeter())


def judge(name: str, checks) -> list:
    """``run.judge`` against the limits at this size."""
    saved, R.LIMITS_DIR = R.LIMITS_DIR, LIMITS_DIR
    try:
        return R.judge(name, checks)
    finally:
        R.LIMITS_DIR = saved


def run(c: R.Cell) -> dict:
    """A whole run of the cell, past the look for a chip, judged against
    the limits at this size."""
    bench = R.load_json(R.ROOT / "BENCHMARK.json")
    saved, R.LIMITS_DIR = R.LIMITS_DIR, LIMITS_DIR
    try:
        return R.run_cell(c, bench, False, out=io.StringIO(),
                          err=io.StringIO())
    finally:
        R.LIMITS_DIR = saved
