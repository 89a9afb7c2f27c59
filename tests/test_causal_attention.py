"""``ops/causal_attention.py``: the causal tiles as two Pallas kernels,
interpreted off the chip.

(a) The kernel path is the dense banded softmax, output and the three
    gradients, at the geometries the decoder cells send: the global
    grouped-query layer's (7 query heads a key/value head), the window
    layers' (a window that is a multiple of the key block, one that no
    block divides, one wider than the sequence, one so short that a
    block's last rows find their window's first key in a LATER tile), in
    float32 and in the step's bfloat16.
(b) ``causal_attention_path`` reads the path off what the call can
    observe and says why; ``causal_blockwise_attention`` follows it.
(c) Latent attention's pair (``latent_attention``: q, kvb and the ONE
    shared key as the projections leave them, the key in two parts) is
    the plain arm — the key written out a head, the plain tiles — and
    the dense masked softmax: output, dq, d kvb, d kpe, with the key
    turned and unturned, one row and two, float32 and bfloat16;
    ``latent_attention_path`` reads ITS path off what the mixer can
    observe.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.ops import causal_attention as kernels
from dinov3_tpu.ops.attention import causal_blockwise_attention


def _dense_attention(q, k, v, window=None):
    n, g = q.shape[1], q.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    z = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    at = jnp.arange(n)
    seen = at[None, :] <= at[:, None]
    if window is not None:
        seen = seen & (at[None, :] > at[:, None] - window)
    z = jnp.where(seen, z, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(z, -1), v)


@pytest.mark.parametrize(
    "n, heads, kv_heads, d, dv, window, block_q, block_kv, dtype", [
        (512, 7, 1, 128, 128, None, 128, 256, jnp.float32),   # the global layer
        (768, 6, 2, 128, 128, 256, 128, 256, jnp.float32),    # window = a key block
        (768, 6, 2, 128, 128, 300, 128, 256, jnp.float32),    # ... no block divides
        (512, 6, 2, 128, 128, 1000, 128, 256, jnp.float32),   # ... wider than N
        # rows 256-383 start at key 127 (tile 0); row 383's window starts
        # at key 254, in tile 1: tile 0 is wholly masked for it
        (768, 2, 1, 128, 128, 130, 128, 128, jnp.float32),
        (512, 4, 2, 128, 256, 200, 256, 128, jnp.float32),    # v wider, blocks turned
        (768, 7, 1, 128, 128, 300, 128, 256, jnp.bfloat16),   # the step's type
    ], ids=["global", "window_block", "window_odd", "window_wide",
            "window_starts_a_tile_later", "wide_v", "bfloat16"])
def test_kernels_are_the_banded_softmax(
        n, heads, kv_heads, d, dv, window, block_q, block_kv, dtype):
    ks = jax.random.split(jax.random.key(n + heads), 3)
    q = jax.random.normal(ks[0], (2, n, heads, d), dtype)
    k = jax.random.normal(ks[1], (2, n, kv_heads, d), dtype)
    v = jax.random.normal(ks[2], (2, n, kv_heads, dv), dtype)

    def both(fn):  # the output, and a gradient that weighs every element
        return jax.jit(lambda *a: (fn(*a), *jax.grad(
            lambda *b: jnp.sum(jnp.sin(fn(*b).astype(jnp.float32))),
            argnums=(0, 1, 2))(*a)))(q, k, v)

    got = both(lambda *a: kernels.kernel_attention(
        *a, d ** -0.5, window, block_q, block_kv, True))
    want = both(lambda *a: _dense_attention(*a, window))
    assert [x.dtype for x in got] == [dtype] * 4
    if dtype == jnp.float32:
        np.testing.assert_allclose(got[0], want[0], atol=3e-6)
        for a, b in zip(got[1:], want[1:]):
            np.testing.assert_allclose(a, b, atol=5e-5)
    else:  # a rounding of the operands: the norm of the difference
        for a, b in zip(got, want):
            gap = jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)
            assert gap < 6e-3, gap


SHAPES = ((1, 1024, 28, 128), (1, 1024, 4, 128), (1, 1024, 4, 128))


@pytest.mark.parametrize("shapes, kwargs, path, why", [
    (SHAPES, {}, "tiles", "the backend is cpu, not a TPU"),
    (SHAPES, {"interpret": True}, "kernel", "interpreted"),
    (SHAPES, {"interpret": False}, "kernel", "compiled for the TPU"),
    (SHAPES, {"interpret": True, "window": 300}, "kernel", "interpreted"),
    # (a key a head at latent attention's 192: the pad arm is gone)
    (((2, 1024, 32, 192), (2, 1024, 32, 192), (2, 1024, 32, 128)),
     {"interpret": True}, "tiles",
     "the widths 192 + 128 are not multiples of 128, nor are the heads 64 + "
     "64 wide on an even number of key/value heads (32)"),
    (SHAPES[:2] + ((1, 1024, 4, 64),), {"interpret": True}, "tiles",
     "the widths 128 + 64 are not multiples of 128, nor are the heads 64 + 64 "
     "wide on an even number of key/value heads (4)"),
    (((2, 1024, 8, 64), (2, 1024, 2, 64), (2, 1024, 2, 64)),
     {"interpret": True}, "kernel", "interpreted"),
    (tuple((1, 1300) + s[2:] for s in SHAPES), {"interpret": True}, "tiles",
     "1300 tokens are not whole blocks of 512 queries and 1024 keys"),
    (SHAPES, {"interpret": True, "block_q": 32, "block_kv": 64}, "tiles",
     "blocks of 32 x 64 are not multiples of 128"),
    (tuple((1, 65536) + s[2:] for s in SHAPES), {"interpret": True}, "tiles",
     "dk and dv of 65536 tokens (128 MiB) do not fit the backward's VMEM"),
    (SHAPES, {"interpret": True, "dtype": jnp.float16}, "tiles",
     "float16 is neither bfloat16 nor float32"),
    (SHAPES, {"interpret": True, "reduce_dtype": jnp.bfloat16}, "tiles",
     "statistics in bfloat16: the kernels' are float32"),
], ids=["cpu", "interpret", "compile", "window", "mla", "width", "heads64",
        "length",
        "blocks", "vmem", "dtype", "reduce_dtype"])
def test_path_is_read_off_the_call(shapes, kwargs, path, why):
    assert kernels.causal_attention_path(shapes, **kwargs) == (path, why)


def test_entry_point_follows_the_path(monkeypatch):
    """Nobody sets the path: off the TPU the entry point's program holds
    no kernel; where ``causal_attention_path`` answers "kernel" (the test
    steers: the program has no option) it holds the forward kernel, and
    its gradient under a layer's remat the primal pass, the forward rule
    and ONE backward kernel, no loop beside them."""
    from test_lm_decoder import _loops_and_kernels

    q = jnp.zeros((1, 1024, 14, 128), jnp.bfloat16)
    k = v = jnp.zeros((1, 1024, 2, 128), jnp.bfloat16)

    def program(window):
        layer = jax.checkpoint(lambda *a: causal_blockwise_attention(
            *a, window=window))
        return sorted(_loops_and_kernels(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(layer(*a).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v).jaxpr, []))

    assert not [x for x in program(300) if x.startswith("causal_attn")]
    seen = []

    def says_kernel(*a, **kw):
        seen.append((a, kw))
        return "kernel", "the test says so"

    monkeypatch.setattr(kernels, "causal_attention_path", says_kernel)
    for window in (300, None):
        assert program(window) == [
            kernels.BACKWARD_KERNEL_NAME, kernels.KERNEL_NAME,
            kernels.KERNEL_NAME]
    # what the entry point shows the chooser: shapes, window, blocks, types
    assert seen[0][0] == ((q.shape, k.shape, v.shape), 300, None, 512, 1024,
                          jnp.bfloat16, jnp.float32)


# ---------------- (c) the latent pair ----------------

def _a_key_a_head(core, heads, theta):
    """``MLAMixer``'s plain arm around ``core(q, k, v)``: q [B, N, h *
    192], kvb [B, N, h * 256], kpe [B, N, 64] -> o [B, N, h * 128]."""
    from dinov3_tpu.ops.rope import rope_apply_interleaved, token_rope_pair_sincos

    def fn(q, kvb, kpe):
        b, n, _ = q.shape
        q, kvb = q.reshape(b, n, heads, 192), kvb.reshape(b, n, heads, 256)
        kpe = kpe[:, :, None, :]
        if theta is not None:
            table = token_rope_pair_sincos(n, 64, theta)
            q = rope_apply_interleaved(q, *table)
            kpe = rope_apply_interleaved(kpe, *table)
        k = jnp.concatenate([kvb[..., :128], jnp.broadcast_to(
            kpe, (b, n, heads, 64))], axis=-1)
        return core(q, k, kvb[..., 128:]).reshape(b, n, heads * 128)
    return fn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("theta", [None, 1e4], ids=["unturned", "turned"])
def test_latent_pair_is_the_plain_arm_and_the_banded_softmax(theta, rows, dtype):
    """4 heads (two pairs: the shared key's cotangent sums over both and
    over each pair's halves), 512 tokens in blocks of 128 queries and 256
    keys: four query blocks, two key tiles."""
    from dinov3_tpu.ops.attention import causal_tiles
    from dinov3_tpu.ops.rope import rope_apply_pairs, token_rope_pair_sincos

    n, heads = 512, 4
    ks = jax.random.split(jax.random.key(rows), 3)
    x = tuple(jax.random.normal(key, (rows, n, w), dtype)
              for key, w in zip(ks, (heads * 192, heads * 256, 64)))
    assert kernels.latent_attention_path(
        n, heads, (128, 64, 128), True, 128, 256, dtype) == (
            "kernel", "interpreted")

    def pair(q, kvb, kpe):
        if theta is not None:
            kpe = rope_apply_pairs(kpe, *token_rope_pair_sincos(n, 64, theta))
        return kernels.latent_attention(q, kvb, kpe, theta, 128, 256, True)

    def both(fn):  # the output, and a gradient that weighs every element
        return jax.jit(lambda *a: (fn(*a), *jax.grad(
            lambda *b: jnp.sum(jnp.sin(fn(*b).astype(jnp.float32))),
            argnums=(0, 1, 2))(*a)))(*x)

    with jax.default_matmul_precision("highest"):
        got = both(pair)
        tiles = both(_a_key_a_head(lambda *a: causal_tiles(
            *a, 128, 256, jnp.float32, None), heads, theta))
        dense = both(_a_key_a_head(_dense_attention, heads, theta))
    assert [a.dtype for a in got] == [dtype] * 4
    assert [a.shape for a in got] == [x[0].shape[:2] + (heads * 128,)] + [
        a.shape for a in x]
    for want in (tiles, dense):
        for name, a, w in zip(("o", "dq", "dkvb", "dkpe"), got, want):
            gap = float(jnp.linalg.norm(a.astype(jnp.float32) - w)
                        / jnp.linalg.norm(w))
            assert gap < (2e-6 if dtype == jnp.float32 else 8e-3), (name, gap)


@pytest.mark.parametrize("args, kwargs, path, why", [
    ((16384, 32, (128, 64, 128)), {}, "tiles", "the backend is cpu, not a TPU"),
    ((16384, 32, (128, 64, 128)), {"interpret": False}, "kernel",
     "compiled for the TPU"),
    ((8192, 32, (128, 64, 128)), {"interpret": True}, "kernel", "interpreted"),
    ((8192, 32, (128, 64, 128)), {"interpret": True, "dtype": jnp.float32},
     "kernel", "interpreted"),
    ((1024, 4, (16, 8, 16)), {"interpret": True}, "tiles",
     "4 heads of 16 | 8 | 16: the kernels take an even number of 128 | 64 | 128"),
    ((1024, 3, (128, 64, 128)), {"interpret": True}, "tiles",
     "3 heads of 128 | 64 | 128: the kernels take an even number of 128 | 64 "
     "| 128"),
    ((1300, 32, (128, 64, 128)), {"interpret": True}, "tiles",
     "1300 tokens are not whole blocks of 1024 queries and 1024 keys"),
    ((1024, 32, (128, 64, 128)),
     {"interpret": True, "block_q": 32, "block_kv": 64}, "tiles",
     "blocks of 32 x 64 are not multiples of 128"),
    ((20480, 32, (128, 64, 128)), {"interpret": True}, "tiles",
     "a pair of heads' dk and dv and the shared key's of 20480 tokens (70 MiB) "
     "do not fit the backward's VMEM"),
    ((16384, 32, (128, 64, 128)), {"interpret": True, "dtype": jnp.float32},
     "tiles",
     "a pair of heads' dk and dv and the shared key's of 16384 tokens (72 MiB) "
     "do not fit the backward's VMEM"),
    ((8192, 32, (128, 64, 128)), {"interpret": True, "dtype": jnp.float16},
     "tiles", "float16 is neither bfloat16 nor float32"),
    ((8192, 32, (128, 64, 128)),
     {"interpret": True, "reduce_dtype": jnp.bfloat16}, "tiles",
     "statistics in bfloat16: the kernels' are float32"),
], ids=["cpu", "compile", "interpret", "float32", "widths", "odd_heads",
        "length", "blocks", "vmem", "vmem_float32", "dtype", "reduce_dtype"])
def test_latent_path_is_read_off_the_mixer(args, kwargs, path, why):
    assert kernels.latent_attention_path(*args, **kwargs) == (path, why)
