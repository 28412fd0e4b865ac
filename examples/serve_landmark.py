"""Serving with the paper's fast-SPSD landmark attention.

    PYTHONPATH=src python examples/serve_landmark.py

Runs batched generation twice with a gemma3-family smoke model: once with
exact KV-cache attention, once with the landmark decode path on the global
layers (local layers keep their ring buffers).  At 500k-token contexts the
landmark path is what makes gemma3 decode sub-quadratic (long_500k cell);
here we check the two paths agree early in the context where both are exact.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.launch.serve import generate
from repro.models.model import build_model

base = get_smoke("gemma3-12b")
prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 96), 0,
                             base.vocab_size, dtype=jnp.int32)

outs = {}
for mode, opts in (("exact KV", dict(use_landmark_decode=False)),
                   ("landmark strided", dict(use_landmark_decode=True,
                                             landmark_selection="strided")),
                   ("landmark adaptive", dict(
                       use_landmark_decode=True,
                       landmark_selection="uniform_adaptive2"))):
    cfg = dataclasses.replace(base, landmark_c=48, landmark_theta=4, **opts)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    t0 = time.time()
    out = generate(model, params, prompts, gen=24, key=jax.random.PRNGKey(2))
    out.block_until_ready()
    outs[mode] = np.asarray(out)
    print(f"{mode:22s}: generated {out.shape} in {time.time() - t0:5.1f}s")

for mode in ("landmark strided", "landmark adaptive"):
    agree = float(np.mean(outs["exact KV"] == outs[mode]))
    print(f"token agreement exact vs {mode}: {100 * agree:.1f}% "
          f"(c=48 landmarks over 96-token context)")
print("landmark state per layer: O(c^2 + c*(2d+1)) floats vs KV cache "
      "O(S*2d) — "
      "independent of context length")
