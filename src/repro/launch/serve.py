"""Serving entry point: batched prefill, then greedy decode on the device, with
the paper's landmark (fast-SPSD) attention on the global layers of the
configurations that turn it on.

    PYTHONPATH=src python -m repro.launch.serve --arch mellum2-12b-a2.5b \
        --smoke --batch 4 --prompt-len 64 --gen 32

``generate`` is the model's own entry (``Model.generate``): prefill builds
the decode cache (for landmark configs also the fast-model factors of every
global layer — Algorithm 1 applied to the softmax Gram), then one
``lax.fori_loop`` program decodes every step, folding each decoded token
into the landmark factors.  It is compiled ahead of time, so the reported
tokens per second exclude compilation.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.launch.train import parse_mesh
from repro.models.model import build_model


def compile_generate(model, params, prompts: jnp.ndarray, gen: int, key):
    """The prefill-and-decode program for these shapes, compiled; call it
    as ``fn(params, {"tokens": prompts}, key, keep)``."""
    keep = jnp.zeros((1,), jnp.int32)
    return jax.jit(model.generate, static_argnames=("steps",)).lower(
        params, {"tokens": prompts}, key, keep, steps=gen).compile()


def generate(model, params, prompts: jnp.ndarray, gen: int, key):
    """prompts: (B, S) int32 -> (B, gen) greedy continuations."""
    fn = compile_generate(model, params, prompts, gen, key)
    return fn(params, {"tokens": prompts}, key,
              jnp.zeros((1,), jnp.int32)).tokens


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--mesh", default="1x1")
    p.add_argument("--landmark", action="store_true",
                   help="use fast-SPSD landmark decode on global layers")
    args = p.parse_args(argv)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.landmark:
        cfg = dataclasses.replace(cfg, use_landmark_decode=True)
    mesh = parse_mesh(args.mesh)
    model = build_model(cfg)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size, dtype=jnp.int32)
        key = jax.random.PRNGKey(2)
        t0 = time.perf_counter()
        fn = compile_generate(model, params, prompts, args.gen, key)
        t1 = time.perf_counter()
        out = fn(params, {"tokens": prompts}, key,
                 jnp.zeros((1,), jnp.int32)).tokens
        out.block_until_ready()
        dt = time.perf_counter() - t1
        print(f"compiled in {t1 - t0:.2f}s; generated {out.shape} in "
              f"{dt:.2f}s ({args.batch * args.gen / dt:.1f} tok/s, "
              "prefill included, compile excluded)")
        print("sample row:", np.asarray(out[0][:16]))
        assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))
        print("serve ok")


if __name__ == "__main__":
    main()
