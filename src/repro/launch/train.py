"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

Wires every substrate layer together: config -> model -> data pipeline ->
pjit'd train step -> checkpoint manager (atomic, async, retained) ->
fault-tolerance hooks (preemption -> save-and-exit; restartable data state).
On this CPU container it is exercised with --smoke configs and a (1,1) or
(d,m) debug mesh; on real hardware the same file drives the production mesh
(--mesh 16x16).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke
from repro.data import make_pipeline
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.launch.steps import make_train_step, default_optimizer
from repro.models.model import build_model
from repro.runtime import PreemptionHandler


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return mesh_lib.make_mesh(dims, axes)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--mesh", default="1x1",
                   help="e.g. 1x1, 2x4, 16x16, 2x16x16")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--peak-lr", type=float, default=3e-4)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--compress-pod-grads", type=int, default=0,
                   help="CountSketch compression ratio for cross-pod "
                        "all-reduce (0 = off)")
    args = p.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = parse_mesh(args.mesh)
    model = build_model(cfg)
    opt = default_optimizer(cfg)
    step_fn = make_train_step(model, opt, peak_lr=args.peak_lr,
                              total=args.steps, warmup=max(args.steps // 10, 1),
                              accum=args.accum)

    pipe = make_pipeline("synthetic", vocab_size=cfg.vocab_size,
                         seq_len=args.seq_len, global_batch=args.global_batch)

    preempt = PreemptionHandler(install_signal=True)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        psh = shd.param_shardings(params, mesh, fsdp=cfg.fsdp)
        params = jax.device_put(params, psh)

        start = 0
        if mgr is not None:
            latest = mgr.latest_step()
            if latest is not None:
                state = mgr.restore(latest, {"params": params,
                                             "opt": opt_state})
                params = jax.device_put(state["params"], psh)
                opt_state = jax.tree.map(jnp.asarray, state["opt"],
                                         is_leaf=lambda x: hasattr(x, "shape"))
                opt_state = type(opt_state)(*opt_state) \
                    if not isinstance(opt_state, dict) else opt_state
                start = latest
                print(f"restored checkpoint @ step {latest}")

        jstep = jax.jit(step_fn, donate_argnums=(0, 1))
        t0 = time.time()
        losses = []
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, pipe.batch_at(step))
            params, opt_state, metrics = jstep(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                tput = (step - start + 1) * args.global_batch \
                    * args.seq_len / max(dt, 1e-9)
                print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  "
                      f"{tput:,.0f} tok/s")
            if mgr is not None and (
                    (step + 1) % args.ckpt_every == 0 or preempt.should_exit):
                mgr.save(step + 1, {"params": params, "opt": opt_state},
                         blocking=preempt.should_exit)
            if preempt.should_exit:
                print(f"preempted: checkpointed at step {step + 1}, exiting")
                break
        if mgr is not None:
            mgr.join()

    if len(losses) >= 20:
        first, last = np.mean(losses[:10]), np.mean(losses[-10:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
