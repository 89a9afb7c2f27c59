"""Compile the Pallas kernels for a DESCRIBED v5e chip, with no chip
attached (on-chip-measurement guide §2.3): flash attention forward and
backward, plain and segment-masked, at the 768 px (N=2309) and 1024 px
(N=4101) ViT-L token counts, the fused layernorm forward and
backward at ViT-L width, and the delta rule's chunk forward and
backward at the decoder cell's shapes (both gate forms: a decay a
channel, ONE decay a value head), the causal attention kernels
at both decoder cells' published shapes (under a selection too, and the
index loss's kernel beside them), and the delta-rule mixers'
chains (``ops/mixer_chains.py``) at both delta-rule cells', the gated
short convolution's chain and the causal pair at heads of 64 at the
``lfm2_moe`` cell's, the latent pair at both latent cells' (and the
``deepseek_v3`` cell's whole mixer around it: no plane a head),
the state-space scan's pair (``ops/ssd.py``) and the causal pair at
SIXTEEN query heads a key/value head at the ``nemotron_h`` cell's,
and the routed layers' experts' block
(``ops/grouped_matmul.py``) at the seven decoder cells' (the seventh's
un-gated, 14.5 lane tiles wide) — each with
``interpret=False``, each asserting
a Mosaic ``tpu_custom_call`` in the compiled text. What the chip's
compiler would refuse (a slice off the tiling, too much VMEM) fails
here, at no chip time. A compile that passes is not a chip run.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports this file. Keep these tests in this ONE file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip; the persistent compilation
    cache is off around the compiles — a compile for a described device
    is written to the cache but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from dinov3_tpu.parallel.context import get_current_mesh, set_current_mesh

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # no ambient mesh: an earlier test file of this worker may have left
    # its 8-device CPU mesh registered, and the layernorm would then open
    # a shard_map island on it instead of compiling for the one chip
    prev_mesh = get_current_mesh()
    set_current_mesh(None)
    yield SingleDeviceSharding(topo.devices[0])
    set_current_mesh(prev_mesh)
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _compiled_text(fn, sharding, *shapes_dtypes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_tokens", [2309, 4101])
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "seg"])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, n_tokens, masked,
                                          direction):
    from dinov3_tpu.ops.flash_attention import flash_attention

    qkv = ((2, n_tokens, 16, 64), jnp.bfloat16)
    seg = ((2, n_tokens), jnp.int32)

    def fwd(q, k, v, s=None):
        return flash_attention(q, k, v, interpret=False, seg=s)

    def bwd(q, k, v, do, s=None):
        return jax.vjp(lambda a, b, c: fwd(a, b, c, s), q, k, v)[1](do)

    fn, shapes = (fwd, [qkv] * 3) if direction == "fwd" else (bwd, [qkv] * 4)
    if masked:
        shapes = shapes + [seg]
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_fused_layernorm_compiles_for_v5e(one_chip, direction):
    from dinov3_tpu.ops.fused_norm import fused_layernorm

    x = ((24, 201, 1024), jnp.bfloat16)
    p = ((1024,), jnp.float32)

    def fwd(x, s, b):
        return fused_layernorm(x, s, b, eps=1e-6, interpret=False,
                               force=True)

    def bwd(x, s, b, dy):
        return jax.vjp(fwd, x, s, b)[1](dy)

    fn, shapes = (fwd, [x, p, p]) if direction == "fwd" else (
        bwd, [x, p, p, x])
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("states", [False, True], ids=["primal", "states"])
def test_kda_chunk_kernels_compile_for_v5e(one_chip, states):
    """``ops/kda.py``'s kernels at the decoder cell's shapes (2 sequences
    of 8,192 tokens, 32 heads of 128 x 128, bfloat16 q, k, v): the
    primal, and the gradient's program: the forward rule, which also
    writes each chunk's starting state, and the backward kernel behind
    it, which packs its bfloat16 gradients a pair of heads a word."""
    from dinov3_tpu.ops.kda import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        kda_chunked,
    )

    act = ((2, 8192, 32, 128), jnp.bfloat16)
    g, beta, o = (act[0], jnp.float32), ((2, 8192, 32), jnp.float32), \
        (act[0], jnp.float32)

    def fwd(*x):
        return kda_chunked(*x, q_scale=128 ** -0.5, interpret=False)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    fn, shapes = (bwd, [act] * 3 + [g, beta, o]) if states else (
        fwd, [act] * 3 + [g, beta])
    text = _compiled_text(fn, one_chip, *shapes)
    assert KERNEL_NAME in text
    assert text.count("tpu_custom_call") == (2 if states else 1)
    assert (BACKWARD_KERNEL_NAME in text) == states
    if states:
        # what the forward rule keeps: the [128, 2, 32, 128, 128] float32
        # starting states and the [2, 128, 16, 64, 128] inverses; no loop
        # over the chunks beside the kernels
        assert "f32[128,2,32,128,128]" in text
        assert "f32[2,128,16,64,128]" in text and " while(" not in text


@pytest.mark.parametrize("states", [False, True], ids=["primal", "states"])
def test_gdn_chunk_kernels_compile_for_v5e(one_chip, states):
    """``ops/kda.py``'s scalar-gate pair at the ``qwen3_next`` cell's
    shapes (2 sequences of 8,192 tokens, 32 value heads on 16 key heads
    of 128 x 128, bfloat16 q, k, v, ONE float32 log decay a value head):
    the primal, and the gradient's program — the forward rule with the
    kept states and inverses and the backward kernel, which packs a lone
    key head's bfloat16 dq and dk into its half of a word."""
    from dinov3_tpu.ops.kda import (
        KERNEL_NAME,
        SCALAR_BACKWARD_KERNEL_NAME,
        SCALAR_KERNEL_NAME,
        kda_chunked,
    )

    key, value = ((2, 8192, 16, 128), jnp.bfloat16), (
        (2, 8192, 32, 128), jnp.bfloat16)
    row, o = ((2, 8192, 32), jnp.float32), (value[0], jnp.float32)

    def fwd(*x):
        return kda_chunked(*x, q_scale=128 ** -0.5, interpret=False)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    shapes = [key, key, value, row, row]
    text = _compiled_text(*((bwd, one_chip, *shapes, o) if states else (
        fwd, one_chip, *shapes)))
    assert SCALAR_KERNEL_NAME in text and KERNEL_NAME not in text
    assert text.count("tpu_custom_call") == (2 if states else 1)
    assert (SCALAR_BACKWARD_KERNEL_NAME in text) == states
    # no plane of the gate broadcast over the key channels, no q or k at
    # the value heads, no loop over the chunks beside the kernels
    assert "f32[2,8192,32,128,128]" not in text and " while(" not in text
    if states:
        assert "f32[128,2,32,128,128]" in text
        assert "f32[2,128,16,64,128]" in text


@pytest.mark.parametrize("states", [False, True], ids=["primal", "states"])
def test_ssd_chunk_kernels_compile_for_v5e(one_chip, states):
    """``ops/ssd.py``'s pair at the ``nemotron_h`` cell's shapes (2
    sequences of 8,192 tokens, 64 heads of 64 on a state of 128 in 8
    groups, one bfloat16 plane [u | B | C] of 6144 lanes, a float32 step a
    head and token): the primal, and the gradient's program — the forward
    rule with each chunk's starting state and the backward kernel."""
    from dinov3_tpu.ops.ssd import (
        BACKWARD_KERNEL_NAME,
        KERNEL_CHUNK,
        KERNEL_NAME,
        ssd_chunked,
    )

    plane, step, rate = (((2, 8192, 6144), jnp.bfloat16),
                         ((2, 8192, 64), jnp.float32), ((64,), jnp.float32))

    def fwd(*x):
        return ssd_chunked(*x, 64, 64, 8, 128, interpret=False)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    text = _compiled_text(*((bwd, one_chip, plane, step, rate, (
        (2, 8192, 4096), jnp.bfloat16)) if states else (
            fwd, one_chip, plane, step, rate)))
    assert KERNEL_NAME in text
    assert text.count("tpu_custom_call") == (2 if states else 1)
    assert (BACKWARD_KERNEL_NAME in text) == states
    # no plane of B or C repeated over a group's heads, no loop over the
    # chunks beside the kernels; the kept states are a chunk's, a group's
    assert "[2,8192,64,128]" not in text and " while(" not in text
    kept = f"f32[2,{8192 // KERNEL_CHUNK},8,128,512]"
    assert (kept in text) == states


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("chain", ["conv", "norm"])
def test_ssm_chain_kernels_compile_for_v5e(one_chip, chain, direction):
    """``Mamba2Mixer``'s two chains at the ``nemotron_h`` cell's shapes (2
    sequences of 8,192 tokens; in_proj's plane [z 4096 | xBC 6144 | dt 64]
    and zeros up to 10,368 lanes, 8 norm groups of 512): one kernel a pass,
    its blocks cut from the planes where they lie — no copy and no fusion
    of a plane's size beside the forward call, and beside the backward
    only the zeros around what it wrote."""
    from dinov3_tpu.ops import mixer_chains as mc

    bf16, f32 = jnp.bfloat16, jnp.float32
    plane, xbc, y = (((2, 8192, n), bf16) for n in (10368, 6144, 4096))
    assert mc.ssm_chain_path(8192, 4096, 6144, 8, bf16,
                             interpret=False)[0] == "kernel"
    if chain == "conv":
        names = (mc.SSM_CONV_KERNEL_NAME, mc.SSM_CONV_BACKWARD_KERNEL_NAME)
        shapes, ct = [plane, ((4, 6144), f32), ((6144,), f32)], xbc

        def fwd(plane, taps, bias):
            return mc.ssm_conv_silu(plane, taps, bias, first=4096,
                                    interpret=False)
    else:
        names = (mc.SSM_NORM_KERNEL_NAME, mc.SSM_NORM_BACKWARD_KERNEL_NAME)
        shapes, ct = [y, xbc, plane, ((4096,), f32), ((4096,), f32)], y

        def fwd(y, xbc, plane, skip, scale):
            return mc.ssm_gate_norm(y, xbc, plane, skip, scale, 8, 1e-5,
                                    interpret=False)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    fn, shapes = (fwd, shapes) if direction == "fwd" else (bwd, shapes + [ct])
    text = _compiled_text(fn, one_chip, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert names[direction == "bwd"] in text
    entry = text[text.index("ENTRY"):]
    assert " copy(" not in entry and "[2,8192," not in "".join(
        line for line in entry.splitlines() if " fusion(" in line)
    assert entry.count(" pad(") == (0 if direction == "fwd" else
                                    1 if chain == "conv" else 2)


def test_mamba2_mixer_program_cuts_its_blocks_from_a_lane_tiled_plane_for_v5e(
        one_chip):
    """The whole mixer's gradient program with the scan and both chains on
    their kernels: in_proj's plane is ``[2, 8192, 10368]`` (81 lane tiles:
    at its published 10,304 lanes XLA lays it out token-minor and every
    kernel that reads it pays a transposing copy), no copy of a plane's
    size is in the program, every kernel is there once (the rule's forward
    kernels and the backward's), and the three parts of the plane's
    cotangent reach in_proj's products without being joined in HBM."""
    import flax.linen as nn

    from dinov3_tpu.models.decoder import Mamba2Mixer
    from dinov3_tpu.ops import mixer_chains as mc

    mixer = Mamba2Mixer(64, 64, 8, 128, core_interpret=False,
                        chains_interpret=False)
    x = jax.ShapeDtypeStruct((2, 8192, 2688), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        nn.meta.unbox(jax.eval_shape(mixer.init, jax.random.key(0), x)))
    assert params["params"]["in_proj"]["kernel"].shape == (2688, 10304)

    def loss(params, x):
        return jnp.sum(mixer.apply(params, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert "bf16[2,8192,10368]{2,1,0" in entry
    assert "[2,8192,10304]" not in entry
    assert not [line for line in entry.splitlines() if " copy(" in line
                and "[2,8192," in line and ",64]" not in line.split("copy(")[0]]
    for name in (mc.SSM_CONV_KERNEL_NAME, mc.SSM_CONV_BACKWARD_KERNEL_NAME,
                 mc.SSM_NORM_KERNEL_NAME, mc.SSM_NORM_BACKWARD_KERNEL_NAME):
        assert entry.count(f"%{name}") >= 1, name
    assert entry.count('custom_call_target="tpu_custom_call"') == 6


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("q, kv, dv, window", [
    ((1, 16384, 28, 128), 4, 128, 4096),
    ((1, 16384, 28, 128), 4, 128, None),
    ((2, 8192, 16, 256), 2, 256, None),
    ((2, 8192, 32, 128), 2, 128, None),
], ids=["window", "global", "gated", "gqa16"])
def test_causal_attention_kernels_compile_for_v5e(one_chip, q, kv, dv,
                                                  window, direction):
    """``ops/causal_attention.py`` at the shapes the decoder cells
    send ``causal_blockwise_attention``: the 16k cell's window and global
    grouped-query layers (28 query heads on 4 of 128) and the ``qwen3_next``
    cell's gated attention (16 query heads on 2 of 256, values 256 wide: 8
    heads a key tile) and the ``nemotron_h`` cell's attention block (32
    query heads on 2 of 128: SIXTEEN a key/value head in a grid step), at
    the shipped blocks. The
    gradient's program holds the forward rule and ONE backward kernel."""
    from dinov3_tpu.ops.causal_attention import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        causal_attention_path,
        kernel_attention,
    )

    k = (q[:2] + (kv, q[3]), jnp.bfloat16)
    v = (q[:2] + (kv, dv), jnp.bfloat16)
    do = (q[:3] + (dv,), jnp.bfloat16)
    q = (q, jnp.bfloat16)
    assert causal_attention_path(
        (q[0], k[0], v[0]), window, False)[0] == "kernel"

    def fwd(*x):
        return kernel_attention(*x, q[0][3] ** -0.5, window, 512, 1024, False)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    fn, shapes = (fwd, [q, k, v]) if direction == "fwd" else (
        bwd, [q, k, v, do])
    text = _compiled_text(fn, one_chip, *shapes)
    assert KERNEL_NAME in text
    assert text.count("tpu_custom_call") == (1 if direction == "fwd" else 2)
    assert (BACKWARD_KERNEL_NAME in text) == (direction == "bwd")
    assert " while(" not in text


def _no_plane_a_head(text: str) -> None:
    """No array of the ENTRY computation is a [.., 32, 192] or [.., 32,
    256] plane: q or a key a head written out, padded or laid out anew."""
    import re

    entry = text[text.index("ENTRY"):]
    assert not re.findall(r"\w+\[[\d,]*,32,(?:192|256)\]", entry)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("rows, tokens, theta", [
    (2, 8192, None), (1, 16384, 1e6)], ids=["mla", "mla16k"])
def test_latent_attention_kernels_compile_for_v5e(one_chip, rows, tokens,
                                                  theta, direction):
    """The latent pair at both latent cells' shapes (``kimi_linear``'s two
    rows of 8,192 tokens, the key unturned; ``deepseek_v3``'s ONE row of
    16,384, q turned in the kernels: a pair of heads' dk and dv resident
    beside the shared key's), 32 heads of 128 | 64 | 128, every operand as
    its projection leaves it: the program is the kernels and the shared
    key laid twice, kvb goes in whole, and nothing a head is written."""
    import re

    from dinov3_tpu.ops.causal_attention import (
        KERNEL_NAME,
        LATENT_BACKWARD_KERNEL_NAME,
        LATENT_KERNEL_NAME,
        latent_attention,
        latent_attention_path,
    )

    assert latent_attention_path(tokens, 32, (128, 64, 128), False) == (
        "kernel", "compiled for the TPU")
    shapes = [((rows, tokens, 32 * w), jnp.bfloat16) for w in (192, 256)] + [
        ((rows, tokens, 64), jnp.bfloat16)]

    def fwd(q, kvb, kpe):
        return latent_attention(q, kvb, kpe, theta)

    def bwd(q, kvb, kpe, do):
        return jax.vjp(fwd, q, kvb, kpe)[1](do)

    text = _compiled_text(*((fwd, one_chip, *shapes) if direction == "fwd" else (
        bwd, one_chip, *shapes, ((rows, tokens, 32 * 128), jnp.bfloat16))))
    assert LATENT_KERNEL_NAME in text and KERNEL_NAME not in text
    assert text.count("tpu_custom_call") == (1 if direction == "fwd" else 2)
    assert (LATENT_BACKWARD_KERNEL_NAME in text) == (direction == "bwd")
    assert " while(" not in text
    _no_plane_a_head(text)
    # q and kvb are the kernels' operands as the program received them
    calls = re.findall(r"custom-call\((%[\w.]+), (%[\w.]+),", text)
    assert calls and all(q.startswith("%q") and kvb.startswith("%kvb")
                         for q, kvb in calls), calls


def test_latent_mixer_program_holds_no_plane_a_head_for_v5e(one_chip):
    """``MLAMixer`` at the ``deepseek_v3`` cell's shape under a layer's
    remat, value and gradient (``core_interpret`` False: described as on
    the chip): between the projections and the kernels no pad, no
    concatenate and no copy of q or of a key a head — no such array is in
    the program — and the generic pair is not in it."""
    import flax.linen as nn

    from dinov3_tpu.models.decoder import MLAMixer
    from dinov3_tpu.ops.causal_attention import (
        KERNEL_NAME,
        LATENT_BACKWARD_KERNEL_NAME,
        LATENT_KERNEL_NAME,
    )

    mixer = MLAMixer(32, 512, 128, 64, 128, 1e-6, 1e6, core_interpret=False)
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        nn.meta.unbox(jax.eval_shape(mixer.init, jax.random.key(0), x)))
    layer = jax.checkpoint(mixer.apply)
    text = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    assert LATENT_KERNEL_NAME in text and KERNEL_NAME not in text
    assert LATENT_BACKWARD_KERNEL_NAME in text
    assert text.count("tpu_custom_call") == 2  # the forward rule, the backward
    _no_plane_a_head(text)
    assert "mla_rope" in text and "mla_core" in text


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_causal_attention_kernels_compile_under_a_selection_for_v5e(
        one_chip, direction):
    """The kernel pair under a per-query selection at the ``keye_vl2``
    cell's shape (1 x 16,384 tokens, 32 query heads on 4 of 128: 8 heads a
    key tile), the [B, N, N] int8 plane one more block a tile; the pass
    hands on the rows' log-sum-exp beside its output."""
    from dinov3_tpu.ops.causal_attention import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        causal_attention_path,
        kernel_attention_selected,
    )

    q = ((1, 16384, 32, 128), jnp.bfloat16)
    kv = ((1, 16384, 4, 128), jnp.bfloat16)
    sel = ((1, 16384, 16384), jnp.int8)
    assert causal_attention_path((q[0], kv[0], kv[0]), None, False)[0] == "kernel"

    def fwd(*x):  # (the output, the rows' log-sum-exp [1, 32, 16384])
        return kernel_attention_selected(*x, 128 ** -0.5, 512, 1024, False)

    def bwd(*x):
        return jax.vjp(lambda q, k, v: fwd(q, k, v, x[3])[0], *x[:3])[1](x[4])

    fn, shapes = (fwd, [q, kv, kv, sel]) if direction == "fwd" else (
        bwd, [q, kv, kv, sel, q])
    text = _compiled_text(fn, one_chip, *shapes)
    assert KERNEL_NAME in text
    assert text.count("tpu_custom_call") == (1 if direction == "fwd" else 2)
    assert (BACKWARD_KERNEL_NAME in text) == (direction == "bwd")
    assert " while(" not in text


@pytest.mark.parametrize("with_grad", [False, True], ids=["value", "grad"])
def test_index_loss_kernel_compiles_for_v5e(one_chip, with_grad):
    """The index loss as ONE kernel at the ``keye_vl2`` cell's shape (1 x
    16,384 tokens; an indexer of 16 heads of 64 on one key head beside 32
    query heads on 4 of 128, the core's log-sum-exp [1, 32, 16384]): the
    loss alone and with its gradient, no loop and no float32 plane as wide
    as the keys beside it."""
    from dinov3_tpu.ops.causal_attention import (
        INDEX_LOSS_GRAD_KERNEL_NAME,
        INDEX_LOSS_KERNEL_NAME,
        index_loss_path,
        index_loss_tiles,
    )

    n, bf16, f32 = 16384, jnp.bfloat16, jnp.float32
    q, kv = (1, n, 32, 128), (1, n, 4, 128)
    assert index_loss_path((q, kv, kv), 64, interpret=False)[0] == "kernel"
    text = _compiled_text(
        lambda *x: index_loss_tiles(*x, with_grad=with_grad), one_chip,
        ((1, n, 16, 64), bf16), ((1, n, 64), bf16), ((1, n, 16), f32),
        ((1, n, n), jnp.int8), (q, bf16), (kv, bf16), ((1, 32, n), f32))
    assert (INDEX_LOSS_GRAD_KERNEL_NAME if with_grad else INDEX_LOSS_KERNEL_NAME) in text
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    # neither a strip's [512, 16, keys] products nor a [T, T] target
    assert "f32[512,16," not in text and f"f32[1,{n},{n}]" not in text


def _chain_cases():
    """name -> (the chain at the shipped block, compiled, its argument
    shapes, its kernels' names): ``KDAMixer``'s at the 8k cell's shapes (2
    x 8,192 tokens, 32 heads of 128, the heads in order) and
    ``GDNMixer``'s at the third cell's (16 key heads under 32 value heads
    of 128, q, k, v and z read out of the ``[2, 8192, 16 x 768]``
    grouping where they lie)."""
    from dinov3_tpu.ops import mixer_chains as mc

    bf16, f32 = jnp.bfloat16, jnp.float32
    b, t, d, h, hk, r = 2, 8192, 128, 32, 16, 2
    per = 2 + 2 * r
    layout = lambda first, n: (first, n, per)  # noqa: E731
    plane, grouped = ((b, t, h * d), bf16), ((b, t, hk * per * d), bf16)
    o, taps = ((b, t, h, d), f32), lambda n: ((4, n * d), f32)  # noqa: E731
    conv, norm = ((mc.CONV_KERNEL_NAME, mc.CONV_BACKWARD_KERNEL_NAME),
                  (mc.NORM_KERNEL_NAME, mc.NORM_BACKWARD_KERNEL_NAME))
    return {
        "kda_conv": (lambda x, w: mc.conv_silu_norm(
            x, (w,), (mc.IN_ORDER,), (True,), d, interpret=False),
            [plane, taps(h)], conv),
        "kda_decay": (lambda f, a, bias: mc.log_decay(
            f, a, bias, interpret=False),
            [plane, ((h,), f32), ((h * d,), f32)],
            (mc.DECAY_KERNEL_NAME, mc.DECAY_BACKWARD_KERNEL_NAME)),
        "kda_norm": (lambda o, g, s: mc.gated_rms_norm(
            o, g, s, mc.IN_ORDER, "sigmoid", 1e-5, interpret=False),
            [o, plane, ((d,), f32)], norm),
        "gdn_conv": (lambda x, wq, wk, wv: mc.conv_silu_norm(
            x, (wq, wk, wv), (layout(0, 1), layout(1, 1), layout(2, r)),
            (True, True, False), d, eps=1e-3, interpret=False),
            [grouped, taps(hk), taps(hk), taps(h)], conv),
        "gdn_norm": (lambda o, g, s: mc.gated_rms_norm(
            o, g, s, layout(2 + r, r), "silu", 1e-6, interpret=False),
            [o, grouped, ((d,), f32)], norm),
    }


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize(
    "case", ["kda_conv", "kda_decay", "kda_norm", "gdn_conv", "gdn_norm"])
def test_mixer_chain_kernels_compile_for_v5e(one_chip, case, direction):
    """Each chain ALONE (a whole ``GDNMixer`` gradient compiles for two
    minutes here): the forward is one kernel, the backward one kernel
    (its rule keeps the inputs and runs no forward), and the ``[B, T, H,
    d]`` planes on either side are the kernels' own rows: no copy and no
    fusion of a plane's size beside the call."""
    fn, shapes, (forward, backward) = _chain_cases()[case]
    if direction == "bwd":
        out = jax.eval_shape(fn, *(jax.ShapeDtypeStruct(*s) for s in shapes))
        n, fwd = len(shapes), fn
        shapes = shapes + [(o.shape, o.dtype) for o in jax.tree.leaves(out)]
        fn = lambda *x: jax.vjp(fwd, *x[:n])[1](  # noqa: E731
            jax.tree.unflatten(jax.tree.structure(out), x[n:]))
    text = _compiled_text(fn, one_chip, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert (backward if direction == "bwd" else forward) in text
    entry = text[text.index("ENTRY"):]
    assert " copy(" not in entry and "[2,8192," not in "".join(
        line for line in entry.splitlines() if " fusion(" in line)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_gated_short_conv_kernels_compile_for_v5e(one_chip, direction):
    """``ShortConvMixer``'s chain at the ``lfm2_moe`` cell's shape (4 x
    8,192 tokens, [B ; C ; u] of 2,048 channels each in ONE ``[4, 8192,
    6144]`` plane): one kernel a pass, the plane read where it lies and
    the backward's [dB ; dC ; du] written as one plane: no copy and no
    fusion of a plane's size beside the call."""
    from dinov3_tpu.ops import mixer_chains as mc

    plane, taps = ((4, 8192, 6144), jnp.bfloat16), ((3, 2048), jnp.float32)
    assert mc.mixer_chain_path(8192, (2048,), (), jnp.bfloat16,
                               interpret=False)[0] == "kernel"

    def fwd(x, w):
        return mc.gated_short_conv(x, w, interpret=False)

    def bwd(x, w, dy):
        return jax.vjp(fwd, x, w)[1](dy)

    fn, shapes = (fwd, [plane, taps]) if direction == "fwd" else (
        bwd, [plane, taps, ((4, 8192, 2048), jnp.bfloat16)])
    text = _compiled_text(fn, one_chip, *shapes)
    assert text.count("tpu_custom_call") == 1
    assert (mc.GATED_CONV_BACKWARD_KERNEL_NAME if direction == "bwd"
            else mc.GATED_CONV_KERNEL_NAME) in text
    entry = text[text.index("ENTRY"):]
    assert " copy(" not in entry and "[4,8192," not in "".join(
        line for line in entry.splitlines() if " fusion(" in line)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_causal_attention_kernels_compile_at_heads_of_64_for_v5e(
        one_chip, direction):
    """The kernel pair at the ``lfm2_moe`` cell's shape (4 x 8,192 tokens,
    32 query heads on 8 key/value heads of 64 + 64: a PAIR of key/value
    heads a 128-lane block, 8 query heads a grid step), fed the
    projections' ``[B, T, heads * 64]`` planes as they lie: one kernel a
    pass (the gradient's program: the forward rule and ONE backward
    kernel), no key or value padded to 128 and no copy of a plane's size
    beside the calls."""
    from dinov3_tpu.ops.causal_attention import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        causal_attention_path,
        kernel_attention,
    )

    b, n, h, hk, d = 4, 8192, 32, 8, 64
    q, kv = ((b, n, h * d), jnp.bfloat16), ((b, n, hk * d), jnp.bfloat16)
    assert causal_attention_path(
        ((b, n, h, d), (b, n, hk, d), (b, n, hk, d)), None, False)[0] == "kernel"

    def fwd(q, k, v):
        return kernel_attention(
            q.reshape(b, n, h, d), k.reshape(b, n, hk, d),
            v.reshape(b, n, hk, d), d ** -0.5, None, 512, 1024, False
        ).reshape(b, n, h * d)

    def bwd(*x):
        return jax.vjp(fwd, *x[:-1])[1](x[-1])

    fn, shapes = (fwd, [q, kv, kv]) if direction == "fwd" else (
        bwd, [q, kv, kv, q])
    text = _compiled_text(fn, one_chip, *shapes)
    assert KERNEL_NAME in text
    assert text.count("tpu_custom_call") == (1 if direction == "fwd" else 2)
    assert (BACKWARD_KERNEL_NAME in text) == (direction == "bwd")
    entry = text[text.index("ENTRY"):]
    assert " while(" not in text and " copy(" not in entry
    assert ",128]" not in "".join(   # no [.., heads, 128] widened plane
        line for line in entry.splitlines() if " pad(" in line)


def test_ibot_row_ce_gradient_compiles_without_a_loop_for_v5e(one_chip):
    """Value-and-gradient of the streaming iBOT row CE at the ViT-S cell's
    ``[1920, 65536]`` float32 planes (PR 36): the forward is reductions
    over the whole plane and the backward the closed-form rule, so the
    program holds no loop and no ``dynamic-update-slice`` (the parent's
    two K-tile loops pinned a copy of every tile, and the transposed one
    carried the gradient plane and wrote it a tile at a time). The gradient
    is the output; the temporaries stay under two planes, and the rule BY
    ITSELF needs none of a plane's size: one fusion writes the gradient,
    neither q nor the softmax is a value of its own."""
    from dinov3_tpu.losses.ibot_loss import ibot_patch_loss_from_parts
    from dinov3_tpu.losses.streaming import (
        SinkhornFactors,
        _row_ce_sinkhorn_bwd,
        _row_ce_sinkhorn_stream,
    )

    M, K = 1920, 65536
    f32 = jnp.float32
    plane, rows = ((M, K), f32), ((M,), f32)
    factors = [plane, ((M, 1), f32), ((1, K), f32), ((), f32)]

    def loss(x, xs, r, c, log_b, w):
        parts = _row_ce_sinkhorn_stream(
            x, SinkhornFactors(xs, r, c, log_b, None), 0.1)
        return ibot_patch_loss_from_parts(*parts, w, 64)

    def rule(x, xs, r, c, log_b, lse, d_dot, d_lse):
        res = (x, SinkhornFactors(xs, r, c, log_b, None), lse)
        return _row_ce_sinkhorn_bwd(0.1, res, (d_dot, d_dot, d_lse))[0]

    def compiled(fn, *shapes_dtypes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes_dtypes]
        c = jax.jit(fn).lower(*args).compile()
        return c.as_text(), c.memory_analysis().temp_size_in_bytes

    for fn, shapes, temp_limit in (
            (jax.value_and_grad(loss), [plane, *factors, rows],
             2 * M * K * 4),
            (rule, [plane, *factors, rows, rows, rows], M * K * 4 // 100)):
        text, temp = compiled(fn, *shapes)
        assert " while(" not in text
        assert "dynamic-update-slice(" not in text
        assert temp < temp_limit


@pytest.mark.parametrize("cell, cap, held, d, h, gate", [
    ("smallthinker-ep4-pretrain-16k", 49152, 16, 2560, 768, "relu"),
    ("lfm2-ep8-pretrain-8k", 32768, 8, 2048, 1536, "silu"),
    ("qwen3-next-ep16-pretrain-8k", 40960, 32, 2048, 512, "silu"),
    ("keye-vl2-ep8-pretrain-16k", 32768, 16, 2048, 768, "silu"),
    ("kimi-linear-ep32-pretrain-8k", 8192, 8, 2304, 1024, "silu"),
    ("kanana2-ep8-pretrain-16k", 49152, 16, 2048, 768, "silu"),
    ("nemotron3-nano-ep16-pretrain-8k", 36864, 8, 2688, 1856, "relu2"),
])
def test_experts_block_compiles_for_v5e(one_chip, cell, cap, held, d, h, gate):
    """The held experts' block (``ops/grouped_matmul.py``) at the seven
    decoder cells' routed layers, both passes: two kernels forward and
    three backward; the float32 buffers XLA holds
    beside them stay under the combine's operand and its cotangent. The
    seventh's experts are UN-GATED (W1 [2688, 1856]: the hidden width ends
    in half a lane tile, which Mosaic pads and masks in VMEM)."""
    from dinov3_tpu.ops import grouped_matmul as gm

    assert gm.grouped_matmul_path(cap, d, h, jnp.bfloat16, interpret=False,
                                  gate=gate)[0] == "kernel"
    wide = h if gate == gm.UNGATED else 2 * h

    def both(rows, w12, w3, w_rows, sizes, ct):
        out, vjp = jax.vjp(lambda *a: gm.experts_block(
            *a, sizes, gate, gm.row_tile(cap), False),
            rows, w12, w3, w_rows)
        return out, vjp(ct)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((cap, d), jnp.bfloat16), ((held, d, wide), jnp.float32),
        ((held, h, d), jnp.float32), ((cap,), jnp.float32),
        ((held,), jnp.int32), ((cap, d), jnp.float32))]
    compiled = jax.jit(both).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 5
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * cap * d * 4


@pytest.mark.parametrize("cell, n, k, cap, d", [
    ("smallthinker-ep4-pretrain-16k", 16384, 6, 49152, 2560),
    ("qwen3-next-ep16-pretrain-8k", 16384, 10, 40960, 2048),
    ("kimi-linear-ep32-pretrain-8k", 16384, 8, 8192, 2304),
])
def test_row_movement_compiles_for_v5e(one_chip, cell, n, k, cap, d):
    """Dispatch, combine and the transpose of each (``ops/routed_rows.py``)
    as a TPU runs them, from abstract lists: the token-sorted sum is one
    kernel a pass, and the one float32 plane of the buffer's height that
    is made is the forward combine's operand in token order."""
    from dinov3_tpu.ops import routed_rows as rr

    form = rr.combine_form(n, cap, d, interpret=False)
    assert form == "sorted"
    shapes = jax.eval_shape(
        lambda o, kept: rr.row_lists(o, kept, n, k, form),
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.bool_))
    lists = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)

    def both(lists, x, src, dy, d_rows):
        rows, db = jax.vjp(lambda x: rr.dispatch_rows(x, lists, False), x)
        y, cb = jax.vjp(lambda s: rr.combine_rows(
            s, lists, n, jnp.bfloat16, False), src)
        # the experts' backward rounds the cotangent it is handed
        return rows, y, db(d_rows), cb(dy)[0].astype(jnp.bfloat16)

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        ((n, d), jnp.bfloat16), ((cap, d), jnp.float32),
        ((n, d), jnp.bfloat16), ((cap, d), jnp.bfloat16))]
    text = jax.jit(both).lower(lists, *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    entry = text[text.index("ENTRY"):]
    made = [ln for ln in entry.splitlines()
            if f" f32[{cap},{d}]" in ln.split("(")[0] and "parameter" not in ln]
    assert len(made) == 1, made
