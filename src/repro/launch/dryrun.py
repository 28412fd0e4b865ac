import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract memory/cost/roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Each cell is lowered with explicit in/out shardings (ShapeDtypeStruct inputs
— nothing is allocated), compiled for the 16x16 single-pod mesh and/or the
2x16x16 multi-pod mesh, and the compiled artifact is mined for:

- memory_analysis()  -> bytes/chip (proves the cell fits 16 GB HBM)
- cost_analysis()    -> FLOPs + bytes accessed (roofline compute/memory terms)
- optimized HLO text -> per-collective byte volumes (roofline collective term)
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, shapes_for
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rl
from repro.launch.steps import build_cell


def scan_reps(cfg) -> int:
    if cfg.is_encdec:
        return cfg.n_enc_layers
    return (cfg.n_layers - cfg.first_k_dense) // len(cfg.layer_pattern)


def _reduced_cfg(cfg, extra_reps: int):
    """Unrolled config with ``extra_reps`` scanned superblocks (prefix and
    remainder kept) — used for the two-point layer-cost extrapolation,
    because XLA's cost_analysis counts a while-loop body exactly once.
    ``unroll_scans`` additionally unrolls the q-block attention and
    mlstm-chunk scans so they are fully counted too."""
    if cfg.is_encdec:
        return dataclasses.replace(
            cfg, n_layers=extra_reps, n_enc_layers=extra_reps,
            n_dec_layers=extra_reps, scan_layers=False, unroll_scans=True)
    plen = len(cfg.layer_pattern)
    rem = (cfg.n_layers - cfg.first_k_dense) % plen
    nl = cfg.first_k_dense + extra_reps * plen + rem
    return dataclasses.replace(cfg, n_layers=nl, scan_layers=False,
                               unroll_scans=True)


def _compile_cell(cfg, shape, mesh, accum=None):
    cell = build_cell(cfg, shape, mesh, accum=accum)
    donate = {"train": (0, 1), "prefill": (), "decode": (1,)}[cell.kind]
    jitted = jax.jit(cell.step_fn,
                     in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings,
                     donate_argnums=donate)
    lowered = jitted.lower(*cell.abstract_args)
    compiled = lowered.compile()
    return cell, compiled


def _costs(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = rl.collective_bytes(hlo)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "hbm": float(rl.hbm_bytes(hlo)),
            "coll": coll,
            "n_coll": sum(hlo.count(c + "(") for c in rl._COLLECTIVES)}


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    if v in ("True", "False"):
        return k, v == "True"
    try:
        return k, int(v)
    except ValueError:
        pass
    try:
        return k, float(v)
    except ValueError:
        return k, v


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, verbose: bool = True,
             overrides: dict | None = None, tag: str = "",
             mesh_shape: str | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split("x"))
        axes = {2: ("data", "model"),
                3: ("pod", "data", "model")}[len(dims)]
        mesh = mesh_lib.make_mesh(dims, axes)
        mesh_name = mesh_shape
        chips = 1
        for d in dims:
            chips *= d
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
        chips = 512 if multi_pod else 256
    t0 = time.time()

    # 1) full scanned compile: proves the cell lowers/shards + memory numbers
    with jax.set_mesh(mesh):
        cell, compiled = _compile_cell(cfg, shape, mesh)
    t_full = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_size_in_bytes": getattr(ma, "argument_size_in_bytes", 0),
            "output_size_in_bytes": getattr(ma, "output_size_in_bytes", 0),
            "temp_size_in_bytes": getattr(ma, "temp_size_in_bytes", 0),
            "alias_size_in_bytes": getattr(ma, "alias_size_in_bytes", 0),
            "generated_code_size_in_bytes": getattr(
                ma, "generated_code_size_in_bytes", 0),
        }
        # peak live bytes per chip ~ args + outputs + temps - donated aliases
        mem["bytes_per_chip"] = (mem["argument_size_in_bytes"]
                                 + mem["output_size_in_bytes"]
                                 + mem["temp_size_in_bytes"]
                                 - mem["alias_size_in_bytes"])
    except Exception as e:                                    # noqa: BLE001
        mem = {"error": str(e), "bytes_per_chip": 0}

    # 2) two-point unrolled extrapolation for per-chip cost terms
    #    (XLA counts a scan body once: corrected = A + (R-1) * (B - A)).
    #    The cost compiles unroll attention/mlstm chunk scans and force
    #    accum=1 so every FLOP of one optimizer step is visible.
    t1 = time.time()
    R = scan_reps(cfg)
    seq_linear = ("slstm" in cfg.layer_pattern and shape.kind != "decode"
                  and shape.seq_len > 2048)
    if seq_linear:
        # slstm's per-timestep lax.scan cannot be unrolled, and every other
        # cost in this (attention-free) arch is linear in S.  Probe at two
        # small sequence lengths S1, 2*S1 and decompose every quantity into
        #   A(S) = out_c + out_l*S + reps*(sup_l*S + body*S_steps)
        # where 'body' is each scan's counted-once residue (slstm: S steps).
        S1 = 1024
        sh1 = dataclasses.replace(shape, name=shape.name + "_s1",
                                  seq_len=S1)
        sh2 = dataclasses.replace(shape, name=shape.name + "_s2",
                                  seq_len=2 * S1)
        with jax.set_mesh(mesh):
            _, cA1 = _compile_cell(_reduced_cfg(cell.cfg, 1), sh1, mesh,
                                   accum=1)
            _, cB1 = _compile_cell(_reduced_cfg(cell.cfg, 2), sh1, mesh,
                                   accum=1)
            _, cA2 = _compile_cell(_reduced_cfg(cell.cfg, 1), sh2, mesh,
                                   accum=1)
            _, cB2 = _compile_cell(_reduced_cfg(cell.cfg, 2), sh2, mesh,
                                   accum=1)
        A1, B1, A2, B2 = (_costs(c) for c in (cA1, cB1, cA2, cB2))
        A, B = A1, B1                       # for reporting n_coll etc.
        S = shape.seq_len

        def ex(key, kind=None):
            g = (lambda d: d[key]) if kind is None \
                else (lambda d: d[key][kind])
            sup1, sup2 = g(B1) - g(A1), g(B2) - g(A2)
            body = max(2 * sup1 - sup2, 0.0)       # slstm residue (1 count)
            sup_lin = (sup2 - sup1) / S1           # per-token superblock
            out1, out2 = g(A1) - sup1, g(A2) - sup2
            out_lin = (out2 - out1) / S1
            out_const = max(2 * out1 - out2, 0.0)
            return (out_const + out_lin * S
                    + R * (sup_lin * S + body * S))
    else:
        with jax.set_mesh(mesh):
            _, cA = _compile_cell(_reduced_cfg(cell.cfg, 1), shape, mesh,
                                  accum=1)
            _, cB = _compile_cell(_reduced_cfg(cell.cfg, 2), shape, mesh,
                                  accum=1)
        A, B = _costs(cA), _costs(cB)

        def ex(key, kind=None):
            g = (lambda d: d[key]) if kind is None \
                else (lambda d: d[key][kind])
            return max(g(A) + (R - 1) * (g(B) - g(A)), 0.0)

    flops = ex("flops")
    byts = ex("bytes")
    hbm = ex("hbm")
    coll = {k: ex("coll", k) for k in A["coll"]}
    t_extra = time.time() - t1

    roof = rl.Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_gflops=flops / 1e9, hlo_gbytes=byts / 1e9, hbm_gbytes=hbm / 1e9,
        coll_gbytes=sum(coll.values()) / 1e9,
        coll_by_kind={k: v / 1e9 for k, v in coll.items()},
        model_gflops=rl.model_flops(cell.cfg, shape) / 1e9,
        bytes_per_chip=float(mem.get("bytes_per_chip", 0.0)),
    ).finalize()
    rec = roof.to_json()
    rec["memory_analysis"] = mem
    rec["kind"] = cell.kind
    rec["compile_full_s"] = round(t_full, 2)
    rec["compile_extrap_s"] = round(t_extra, 2)
    rec["collective_count_per_superblock"] = A["n_coll"]
    rec["scan_reps"] = R

    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] kind={cell.kind} "
              f"compile={t_full:.1f}s extrap={t_extra:.1f}s reps={R}")
        print(f"  memory_analysis: "
              f"args={mem.get('argument_size_in_bytes', 0)/1e9:.3f} GB  "
              f"out={mem.get('output_size_in_bytes', 0)/1e9:.3f} GB  "
              f"temp={mem.get('temp_size_in_bytes', 0)/1e9:.3f} GB  "
              f"-> {mem.get('bytes_per_chip', 0)/1e9:.3f} GB/chip")
        print(f"  cost_analysis: {roof.hlo_gflops:.1f} GFLOP  "
              f"{roof.hlo_gbytes:.1f} GB accessed (unfused) / "
              f"{roof.hbm_gbytes:.1f} GB (fusion-adj)  "
              f"collectives {roof.coll_gbytes:.3f} GB "
              f"{ {k: round(v, 3) for k, v in roof.coll_by_kind.items() if v} }")
        print(f"  roofline: compute={roof.compute_s*1e3:.2f} ms  "
              f"memory={roof.memory_s*1e3:.2f} ms  "
              f"collective={roof.collective_s*1e3:.2f} ms  "
              f"bound={roof.bottleneck}  useful={100*roof.useful_flops_frac:.1f}%")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        fname = f"{arch}__{shape_name}__{mesh_name}{suffix}.json".replace(
            "/", "_")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=ARCHS)
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--mesh", choices=["single", "multi", "both"],
                   default="single")
    p.add_argument("--all", action="store_true",
                   help="run every (arch x shape) cell")
    p.add_argument("--out", default=None, help="directory for JSON results")
    p.add_argument("--set", nargs="*", default=[], dest="overrides",
                   help="config overrides, e.g. seq_parallel_attn=True")
    p.add_argument("--tag", default="", help="suffix for result filenames")
    p.add_argument("--mesh-shape", default=None,
                   help="override mesh, e.g. 32x8 (axes data,model)")
    args = p.parse_args(argv)
    overrides = dict(_parse_override(kv) for kv in args.overrides)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for a in ARCHS:
            for s in shapes_for(a):
                cells.append((a, s.name))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, out_dir=args.out,
                         overrides=overrides, tag=args.tag,
                         mesh_shape=args.mesh_shape)
            except Exception:                                 # noqa: BLE001
                failures.append((arch, shape, mp))
                traceback.print_exc()
    if failures:
        print(f"FAILED cells: {failures}", file=sys.stderr)
        sys.exit(1)
    print("dry-run complete: all cells compiled")


if __name__ == "__main__":
    main()
