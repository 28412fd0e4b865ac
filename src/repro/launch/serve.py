"""Serving driver: batched prefill + decode with the paper's landmark
(fast-SPSD) attention available for long contexts.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --smoke \
        --batch 4 --prompt-len 64 --gen 32 --landmark

The server keeps one decode cache per active batch; prefill builds it (for
landmark configs the prefill also builds the fast-model factors of every
global layer — Algorithm 1 applied to the softmax Gram, cost O(s^2 c) per
head). Greedy sampling; the loop is jit'd with donated cache.
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


def generate(model, params, prompts: jnp.ndarray, gen: int, key,
             max_len: int | None = None):
    """prompts: (B, S) int32 -> (B, gen) greedy continuations."""
    B, S = prompts.shape
    max_len = max_len or (S + gen)
    logits, cache = model.prefill(params, {"tokens": prompts}, key, max_len)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    toks = [tok]
    for i in range(gen - 1):
        logits, cache = decode(params, cache, tok[:, None],
                               jnp.asarray(S + i, jnp.int32))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
    return jnp.stack(toks, axis=1)


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
        t0 = time.time()
        out = generate(model, params, prompts, args.gen,
                       jax.random.PRNGKey(2))
        out.block_until_ready()
        dt = time.time() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s incl. compile)")
        print("sample row:", np.asarray(out[0][:16]))
        assert bool(jnp.all((out >= 0) & (out < cfg.vocab_size)))
        print("serve ok")


if __name__ == "__main__":
    main()
