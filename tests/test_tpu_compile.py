"""Ahead-of-time compiles of the main-path Pallas launches for a described
TPU v5e at real widths — what Mosaic and the TPU compiler refuse (dynamic
lane slices, VMEM overflows, misaligned blocks) shows up here, with no chip.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs these tests loads the TPU compiler, so the other
test workers collect the same tests and never contend for it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pairwise import kernel as pw_kernel
from repro.kernels.pairwise import ops as pw_ops
from repro.kernels.pairwise import specs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text          # the Pallas kernel is in there
    return text


def _edges(d, nseg):
    """A (d, nseg−1) sign-split edge table (values only matter at run time)."""
    return np.tile(np.arange(nseg - 1, dtype=np.float32) + 0.5, (d, 1))


N = 2048             # rows: grid extent only, the tile body is what compiles
M = (512, 128)       # right-hand-side widths: a sketch + probes
C = 512              # landmark points: the certified build's column gather


@pytest.mark.parametrize("precision", specs.PRECISIONS)
def test_rbf_multi_rhs_launch(one_chip, precision):
    spec = specs.rbf(4.0).with_precision(precision)
    X = jax.ShapeDtypeStruct((N, 16), jnp.float32, sharding=one_chip)
    Vs = tuple(jax.ShapeDtypeStruct((N, m), jnp.float32, sharding=one_chip)
               for m in M)
    _compile(lambda X, *Vs: pw_kernel.pairwise_matmat_multi_padded(
        spec, X, X, Vs), X, *Vs)


@pytest.mark.parametrize("precision", specs.PRECISIONS)
@pytest.mark.parametrize("d,nseg", [(16, 32), (112, 8)])
@pytest.mark.parametrize("route", ["vpu_loop", "mxu_signsplit"])
def test_laplacian_multi_rhs_launch(one_chip, route, d, nseg, precision):
    spec = specs.laplacian(1.0 / d).with_precision(precision)
    edges = _edges(d, nseg) if route == "mxu_signsplit" else None
    X = jax.ShapeDtypeStruct((N, d), jnp.float32, sharding=one_chip)
    Vs = tuple(jax.ShapeDtypeStruct((N, m), jnp.float32, sharding=one_chip)
               for m in M)
    _compile(lambda X, *Vs: pw_kernel.pairwise_matmat_multi_padded(
        spec, X, X, Vs, edges=edges), X, *Vs)


def test_prefetch_slab_launch(one_chip):
    spec = specs.rbf(4.0)
    X = jax.ShapeDtypeStruct((N, 16), jnp.float32, sharding=one_chip)
    Vs = tuple(jax.ShapeDtypeStruct((N, m), jnp.float32, sharding=one_chip)
               for m in M)
    off = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _compile(lambda X, off, *Vs: pw_kernel.pairwise_matmat_multi_slab(
        spec, X, off, N // 4 // pw_kernel.BLOCK_R, Vs), X, off, *Vs)


@pytest.mark.parametrize("precision", specs.PRECISIONS)
@pytest.mark.parametrize("kernel,d", [("rbf", 18), ("rbf", 784),
                                      ("laplacian-signsplit", 16)])
@pytest.mark.parametrize("launch", ["multi", "slab"])
def test_landmark_sweep_launch(one_chip, launch, kernel, d, precision):
    """The certified build's sweep: C from 512 landmark points and K·Z
    against 128 probe columns, in one launch, at SUSY's and MNIST's
    widths."""
    if kernel == "rbf":
        spec, edges = specs.rbf(4.0), None
    else:
        spec, edges = specs.laplacian(1.0 / d), _edges(d, 32)
    spec = spec.with_precision(precision)
    X = jax.ShapeDtypeStruct((N, d), jnp.float32, sharding=one_chip)
    Xl = jax.ShapeDtypeStruct((C, d), jnp.float32, sharding=one_chip)
    V = jax.ShapeDtypeStruct((N, 128), jnp.float32, sharding=one_chip)
    off = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    if launch == "multi":
        text = _compile(lambda X, Xl, V: pw_kernel.pairwise_matmat_multi_padded(
            spec, X, X, (V,), edges=edges, Xl=Xl), X, Xl, V)
    else:
        text = _compile(lambda X, Xl, off, V: pw_kernel.pairwise_matmat_multi_slab(
            spec, X, off, N // 4 // pw_kernel.BLOCK_R, (V,), edges=edges,
            Xl=Xl), X, Xl, off, V)
    rows = N if launch == "multi" else N // 4
    assert f"(f32[{rows},{C}]" in text          # C first, in the tuple


@pytest.mark.parametrize("kernel,n,d,nseg,c", [
    ("rbf", 2 ** 19, 18, 0, 512),                 # susy-rbf.certify
    ("rbf", 2 ** 18, 784, 0, 512),                # mnist-rbf.certify
    ("laplacian-signsplit", 2 ** 16, 18, 9, 512),
    ("laplacian-signsplit", 2 ** 16, 128, 32, 0),   # MAX_SLOTS slots
    ("laplacian-vpu", 2 ** 16, 784, 0, 0),
], ids=["susy", "mnist", "signsplit", "signsplit-max-slots", "vpu-d784"])
def test_sweep_launch_compiles_at_its_picked_tile(one_chip, kernel, n, d,
                                                  nseg, c):
    """The fused sweep at the tile ``sweep_tile`` picks for the certify
    cells' shapes and for the l1 routes at large n fits the VMEM the launch
    asks for, and its record names that tile."""
    spec = specs.rbf(4.0) if kernel == "rbf" else specs.laplacian(1.0 / d)
    edges = _edges(d, nseg) if nseg else None
    inner = d if edges is None else 2 * d * nseg
    tile = pw_kernel.sweep_tile(n, n, d, inner, (128,), c)
    X = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    V = jax.ShapeDtypeStruct((n, 128), jnp.float32, sharding=one_chip)
    if c:
        Xl = jax.ShapeDtypeStruct((c, d), jnp.float32, sharding=one_chip)
        text = _compile(lambda X, Xl, V: pw_kernel.pairwise_matmat_multi_padded(
            spec, X, X, (V,), edges=edges, Xl=Xl), X, Xl, V)
    else:
        text = _compile(lambda X, V: pw_kernel.pairwise_matmat_multi_padded(
            spec, X, X, (V,), edges=edges), X, V)
    assert f'"tile":"{tile[0]}x{tile[1]}"' in text.replace(" ", "")


@pytest.mark.parametrize("d", [16, 112, 784])
def test_serve_cross_launch(one_chip, d):
    """The KernelServer's one launch per bucket: a ragged query block
    against c=512 landmarks and three heads, with interpretation off."""
    spec = specs.rbf(4.0)
    Xq = jax.ShapeDtypeStruct((200, d), jnp.float32, sharding=one_chip)
    Xs = jax.ShapeDtypeStruct((512, d), jnp.float32, sharding=one_chip)
    heads = tuple(jax.ShapeDtypeStruct((512, m), jnp.float32,
                                       sharding=one_chip) for m in (26, 8, 512))
    _compile(lambda Xq, Xs, *hs: pw_ops.kernel_matmat_multi_rows(
        spec, Xq, Xs, hs, interpret=False), Xq, Xs, *heads)


@pytest.mark.parametrize("fold", [True, False])
def test_landmark_attention_launch(one_chip, fold):
    """mellum2's full-layer decode launch: 32 sessions × 4 KV heads, 8
    query heads a group, c = 512 landmarks, d = 128, the fold aliasing UV,
    U1 and the offset in place; and the read alone (m = 200 queries)."""
    from repro.kernels.landmark_attention import kernel as lm_kernel
    n, g, c, d = (128, 8, 512, 128) if fold else (1, 200, 512, 128)
    g = -(-g // 8) * 8

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    factors = (f32(n, g, d), f32(n, c, d), f32(n, c, d), f32(n, 1, c),
               f32(n, 1, 1))
    extra = (f32(n, c, c), f32(n, 1, d), f32(n, 1, d)) if fold else ()
    text = _compile(lambda *a: lm_kernel.landmark_attention_padded(*a),
                    *factors, *extra)
    assert '"kernel":"landmark_attention"' in text.replace(" ", "") \
        .replace("\n", "")


def _moe_shapes(cfg, sharding, lead=()):
    from repro.models import moe as M
    p = jax.eval_shape(lambda k: M.init_moe(k, cfg), jax.random.PRNGKey(0))
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        lead + a.shape, a.dtype, sharding=sharding), p)


@pytest.mark.parametrize("tokens", [32, 4096])
def test_dropless_moe_served(one_chip, tokens):
    """The served MoE at mellum2's widths (64 experts of 2304 × 896, top-8)
    compiles for the chip: a decode step's 32 tokens run densely over every
    expert, with no grouped product left; a prefill block's 4096 run the
    grouped products over the sorted assignments."""
    from repro.configs import get_config
    from repro.models import moe as M
    cfg = get_config("mellum2-12b-a2.5b")
    p = _moe_shapes(cfg, one_chip)
    x = jax.ShapeDtypeStruct((1, tokens, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: M.moe_ffn_served(p, cfg, x)).lower(
        p, x).compile().as_text()
    assert ("ragged" in text) == (tokens > M.DENSE_MAX_TOKENS)


def _loop_bodies(text):
    """{name: instruction lines} of the computations a ``while`` runs as its
    body, in compiled HLO text; a fusion's own computation is not one."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line)
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    return {n: comps[n] for n in bodies}


def test_dense_moe_reads_scanned_weights_in_place(one_chip):
    """A decode turn's MoE as ``stack_decode`` runs it: two pattern repeats
    of four MoE layers scanned over weights stacked per repeat, 32 tokens,
    inside a loop of steps, at mellum2's widths.  No top-level fusion or
    copy of a loop body writes a layer's whole expert bank
    (bf16[64,2304,896] / bf16[64,896,2304]): the dots read the repeat's
    slice of the stacked weights in place."""
    from repro.configs import get_config
    from repro.models import moe as M
    cfg = get_config("mellum2-12b-a2.5b")
    stacked = tuple(_moe_shapes(cfg, one_chip, lead=(2,)) for _ in range(4))
    x = jax.ShapeDtypeStruct((1, 32, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def turn(params, x):
        def repeat(h, layers):
            for p in layers:
                out, _ = M.moe_ffn_served(p, cfg, h)
                h = h + out
            return h, None

        return jax.lax.fori_loop(
            0, 4, lambda _, h: jax.lax.scan(repeat, h, params)[0], x)

    text = jax.jit(turn).lower(stacked, x).compile().as_text()
    bodies = _loop_bodies(text)
    assert bodies
    bank = re.compile(r"= bf16\[64,(2304,896|896,2304)\]\S* (fusion|copy)\(")
    copies = [ln.strip()[:120] for lines in bodies.values() for ln in lines
              if bank.search(ln)]
    assert not copies, copies
