"""Roofline-term extraction from a compiled (dry-run) executable.

Three terms per (arch, shape, mesh), in seconds:

    compute    = HLO_FLOPs / (chips * V5E.peak_flops)
    memory     = HLO_bytes / (chips * V5E.hbm_bw)
    collective = collective_bytes / (chips * V5E.link_bw)

Sources:
- ``compiled.cost_analysis()`` -> 'flops' and 'bytes accessed'.  The compiled
  module is the per-device SPMD program, so these are PER-CHIP numbers
  (verified against hand-computed 6ND for yi-6b: hlo_flops*chips ~ 6ND+remat).
- collective bytes are NOT in cost_analysis: we walk the optimized HLO text
  and sum the *shape bytes* of every all-gather / all-reduce / reduce-scatter
  / all-to-all / collective-permute op.  Bytes are computed from the result
  shape (for all-gather: the gathered output; for reduce-scatter: the input =
  output * group); this is the volume that crosses links per chip up to the
  ring-algorithm factor 2(g-1)/g ~ 2 which we fold into ``V5E.link_bw``.

The peaks are a ``HardwareProfile``; ``V5E`` holds the v5e per-chip ones.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Peak rates the roofline terms divide by — a PARAMETER, not a module
    global, so reports name the hardware they model instead of silently
    assuming v5e on whatever backend happens to be attached."""

    name: str
    peak_flops: float            # FLOP/s (dense matmul peak)
    hbm_bw: float                # bytes/s
    link_bw: float               # bytes/s per ICI link (ring effective)


#: v5e per-chip peaks (bf16 MXU; Google Cloud documentation, "TPU v5e")
V5E = HardwareProfile("v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# `%x = bf16[2,16,128]{...} all-gather(...)`; also tuple shapes
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\b("
    + "|".join(_COLLECTIVES) + r")\b")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# ---------------------------------------------------------------------------
# fusion-adjusted HBM bytes
# ---------------------------------------------------------------------------
# XLA:CPU leaves elementwise chains as hundreds of tiny kLoop fusions, so
# cost_analysis()'s 'bytes accessed' wildly overcounts what a TPU (which
# fuses elementwise work into its dot/reduce kernels) moves through HBM.
# This walker models the *perfect-fusion* asymptote — the same idealization
# the roofline's compute term makes for the MXU: count operand+result bytes
# only for memory-real ops (matmuls, reductions, gathers/scatters, cache
# updates, sorts, collectives); every elementwise op is assumed fused into
# its consumer.  Activations still get counted exactly once: they are
# operands of the dots/reduces that consume them.

_MEM_OPS = (
    "dot(", "dot-general(", "convolution(", "reduce(", "reduce-window(",
    "scatter(", "gather(", "dynamic-slice(", "dynamic-update-slice(",
    "sort(", "copy(",
    "all-gather(", "all-reduce(", "reduce-scatter(", "all-to-all(",
    "collective-permute(",
)

# CPU wraps single non-elementwise ops in fusions named wrapped_<op>...;
# count those wrappers by instruction-name prefix.
_WRAPPED_COUNTED = ("wrapped_reduce", "wrapped_scatter", "wrapped_gather",
                    "wrapped_sort", "wrapped_dot", "wrapped_convolution",
                    "wrapped_dynamic", "wrapped_copy")

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_LHS_SHAPES_RE = re.compile(r"([a-z]\d*[a-z]*\d*)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


_SKIP_COMPUTATIONS = ("%fused", "%wrapped", "%region")


def _computation_lines(hlo_text: str):
    """Yield (in_skipped_computation, line). Fusion bodies / reduce-apply
    regions are marked skipped: their interior ops live in VMEM on TPU."""
    skipped = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and ("(" in s and ")" in s):
            name = s.split()[0]
            skipped = any(name.startswith(p) for p in _SKIP_COMPUTATIONS)
        yield skipped, line
        if s == "}":
            skipped = False


def hbm_bytes(hlo_text: str) -> int:
    """Fusion-adjusted per-chip HBM traffic estimate from optimized HLO."""
    # pass 1: instruction name -> result bytes (module-wide)
    sizes: Dict[str, int] = {}
    for _, line in _computation_lines(hlo_text):
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        head = rhs.split("(", 1)[0]          # shapes before the opcode args
        total = 0
        for dt, dims in _LHS_SHAPES_RE.findall(head):
            if dt in _DTYPE_BYTES:
                total += _shape_bytes(dt, dims)
        if total:
            sizes[name] = total
    # pass 2: memory-real ops in non-fused computations: result + operands
    total = 0
    for skipped, line in _computation_lines(hlo_text):
        if skipped:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        opcode_part = rhs.split("(", 1)[0]
        counted = any(op[:-1] in opcode_part.split() for op in _MEM_OPS)
        if not counted and "fusion" in opcode_part.split():
            counted = any(name.startswith(p) for p in _WRAPPED_COUNTED)
        if not counted:
            continue
        total += sizes.get(name, 0)
        args = rhs.split("(", 1)[1] if "(" in rhs else ""
        args = args.split("),")[0]
        for op_name in _OPERAND_RE.findall(args):
            total += sizes.get(op_name, 0)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind from optimized HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        # tuple results: sum every shape on the lhs before the op name
        lhs = line.split(kind)[0]
        total = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt in _DTYPE_BYTES:
                total += _shape_bytes(dt, dims)
        out[kind] += total
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_gflops: float              # per-chip GFLOP (SPMD module)
    hlo_gbytes: float              # per-chip GB accessed (unfused bound)
    coll_gbytes: float             # per-chip collective GB (result shapes)
    coll_by_kind: Dict[str, float]
    model_gflops: float            # 6 * N_active * D (per step, all chips)
    bytes_per_chip: float          # from memory_analysis (peak, if available)
    hbm_gbytes: float = 0.0        # fusion-adjusted GB (memory-real ops)
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    useful_flops_frac: float = 0.0
    profile_name: str = "v5e"

    def finalize(self, profile: Optional[HardwareProfile] = None):
        prof = V5E if profile is None else profile
        self.profile_name = prof.name
        self.compute_s = self.hlo_gflops * 1e9 / prof.peak_flops
        gb = self.hbm_gbytes if self.hbm_gbytes > 0 else self.hlo_gbytes
        self.memory_s = gb * 1e9 / prof.hbm_bw
        self.collective_s = self.coll_gbytes * 1e9 / prof.link_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        if self.hlo_gflops > 0:
            self.useful_flops_frac = self.model_gflops / (
                self.hlo_gflops * self.chips)
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) per optimizer step; forward-only
    (2*N*D) for serving cells.  D = processed tokens for this cell."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.is_encdec:
            # each stream only crosses its half of the params:
            # 6*(N/2)*(enc tokens) + 6*(N/2)*(dec tokens)
            return 3.0 * n_active * shape.global_batch * (
                shape.seq_len + max(shape.seq_len // 8, 1))
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence; attention reads the cache but 6ND
    # convention counts matmul params only
    return 2.0 * n_active * shape.global_batch


def analyze(compiled, hlo_text: str, *, arch: str, shape, cfg, mesh_name: str,
            chips: int, memory_stats: Optional[dict] = None,
            profile: Optional[HardwareProfile] = None) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):                    # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    coll_total = sum(coll.values())
    mstats = memory_stats or {}
    r = Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_gflops=flops / 1e9, hlo_gbytes=byts / 1e9,
        coll_gbytes=coll_total / 1e9,
        coll_by_kind={k: v / 1e9 for k, v in coll.items()},
        model_gflops=model_flops(cfg, shape) / 1e9,
        bytes_per_chip=float(mstats.get("bytes_per_chip", 0.0)),
    )
    return r.finalize(profile)


# ---------------------------------------------------------------------------
# report aggregation
# ---------------------------------------------------------------------------

def format_table(rows: List[dict]) -> str:
    hdr = (f"{'arch':<18} {'shape':<12} {'mesh':<9} {'GB/chip':>8} "
           f"{'compute_s':>10} {'memory_s':>10} {'coll_s':>10} "
           f"{'bound':>7} {'useful%':>8}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:<18} {r['shape']:<12} {r['mesh']:<9} "
            f"{r['bytes_per_chip']/1e9:>8.2f} "
            f"{r['compute_s']:>10.4f} {r['memory_s']:>10.4f} "
            f"{r['collective_s']:>10.4f} {r['bottleneck']:>7.7s} "
            f"{100*r['useful_flops_frac']:>7.1f}%")
    return "\n".join(lines)


def main(argv=None):
    import argparse
    import glob
    p = argparse.ArgumentParser()
    p.add_argument("--glob", default="results/dryrun/*.json")
    args = p.parse_args(argv)
    rows = []
    for f in sorted(glob.glob(args.glob)):
        with open(f) as fh:
            rows.append(json.load(fh))
    print(format_table(rows))


if __name__ == "__main__":
    main()
