"""``ops/mixer_chains.py``: the delta-rule mixers' elementwise chains as
Pallas kernels, interpreted here at small sizes against the plain chains
(``models/decoder.py``'s own arithmetic, written out below): value and
every gradient, over several time blocks so that the convolution's halo,
the gradient carried from block to block and the parameter sums over the
grid are all exercised. Then the two mixers at a test width, the chains
on their kernels against the same parameters on the plain path.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.models.decoder import causal_depthwise_conv
from dinov3_tpu.ops import mixer_chains as mc
from dinov3_tpu.ops.common import l2_normalize

B, T, D, BLOCK = 2, 128, 128, 32    # four time blocks
bf16, f32 = jnp.bfloat16, jnp.float32


def _draw(seed, *shapes):
    keys = jax.random.split(jax.random.key(seed), len(shapes))
    return [jax.random.normal(k, s, f32) for k, s in zip(keys, shapes)]


def _close(got, want, rel=2e-5):
    """Against the largest entry: both sides are float32 arithmetic in
    another order (a bfloat16 result: equal but for ties, one ulp)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * max(np.abs(want).max(), 1e-30)


def _value_and_grads(fn, args, cts):
    """(fn(*args), its cotangents' pull-back), compiled as one program
    (op by op the plain chains cost seconds of dispatch)."""
    return jax.jit(lambda a, c: (lambda out, vjp: (out, vjp(c)))(
        *jax.vjp(fn, *a)))(args, cts)


def _columns(x, layout, heads):
    """The heads' lane groups of x, joined in the heads' order."""
    first, n, per = layout
    return jnp.concatenate([
        x[..., g * D:(g + 1) * D]
        for g in ((h // n) * per + first + h % n for h in range(heads))], -1)


def _plain_conv(x, kernels, layouts, normalise, eps):
    b, t, _ = x.shape
    out = []
    for kernel, layout, unit in zip(kernels, layouts, normalise):
        heads = kernel.shape[1] // D
        y = jax.nn.silu(causal_depthwise_conv(
            _columns(x, layout, heads).astype(f32), kernel.astype(f32)))
        y = y.reshape(b, t, heads, D)
        out.append((l2_normalize(y, eps=eps) if unit else y).astype(x.dtype))
    return tuple(out)


# (lane groups of x, (layout, heads) an output, normalise, eps). KDA: one
# projection a call, the heads in order; GDN: q, k and v out of the
# published grouping [q | k | v v | z z] a key head, z fed to no head
CONV_CASES = {
    "unit_norm": (2, ((mc.IN_ORDER, 2),), (True,), 1e-12),
    "no_norm": (2, ((mc.IN_ORDER, 2),), (False,), 1e-12),
    "grouped": (12, (((0, 1, 6), 2), ((1, 1, 6), 2), ((2, 2, 6), 4)),
                (True, True, False), 1e-3),
}


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_conv_silu_norm_is_the_plain_chain(case):
    n_groups, outputs, normalise, eps = CONV_CASES[case]
    layouts = tuple(layout for layout, _ in outputs)
    x, *rest = _draw(0, (B, T, n_groups * D), *(
        s for _, heads in outputs for s in ((4, heads * D),
                                            (B, T, heads, D))))
    x, kernels, dys = x.astype(bf16), tuple(rest[::2]), tuple(
        dy.astype(bf16) for dy in rest[1::2])

    def kernel_path(x, kernels):
        return mc.conv_silu_norm(x, kernels, layouts, normalise, D, eps=eps,
                                 block=BLOCK, interpret=True)

    def plain(x, kernels):
        return _plain_conv(x, kernels, layouts, normalise, eps)

    got, (dx, dws) = _value_and_grads(kernel_path, (x, kernels), dys)
    want, (dx_want, dws_want) = _value_and_grads(plain, (x, kernels), dys)
    for g, w in zip(got, want):
        assert g.dtype == bf16
        _close(g, w, rel=1 / 128)          # a tie rounds either way
        assert np.mean(np.asarray(g) != np.asarray(w)) < 1e-3
    assert dx.dtype == bf16 and dws[0].dtype == f32
    _close(dx, dx_want, rel=1 / 128)
    for dw, dw_want in zip(dws, dws_want):
        _close(dw, dw_want)
    if case == "grouped":                  # nothing flows into z's columns
        assert not np.asarray(dx[..., 4 * D:6 * D]).any()


@pytest.mark.parametrize("act, grouped", [
    ("sigmoid", False), ("silu", True)], ids=["kda", "gdn"])
def test_gated_rms_norm_is_the_plain_chain(act, grouped):
    heads = 4
    layout = (4, 2, 6) if grouped else mc.IN_ORDER   # z z of [q k v v z z]
    o, gate, scale, dy = _draw(1, (B, T, heads, D),
                               (B, T, (12 if grouped else heads) * D),
                               (D,), (B, T, heads * D))
    gate, dy, scale, eps = gate.astype(bf16), dy.astype(bf16), 1 + scale, 1e-6

    def kernel_path(o, gate, scale):
        return mc.gated_rms_norm(o, gate, scale, layout, act, eps,
                                 block=BLOCK, interpret=True)

    def plain(o, gate, scale):
        z = _columns(gate, layout, heads)
        ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        y = o * jax.lax.rsqrt(ms + eps) * scale.astype(f32)
        y = y * getattr(jax.nn, act)(z.astype(f32).reshape(B, T, heads, D))
        return y.reshape(B, T, heads * D).astype(bf16)

    got, (do, dgate, dscale) = _value_and_grads(
        kernel_path, (o, gate, scale), dy)
    want, (do_w, dgate_w, dscale_w) = _value_and_grads(
        plain, (o, gate, scale), dy)
    assert got.dtype == bf16
    _close(got, want, rel=1 / 128)
    assert do.dtype == f32 and dgate.dtype == bf16 and dscale.dtype == f32
    _close(do, do_w)
    _close(dgate, dgate_w, rel=1 / 128)
    _close(dscale, dscale_w)


def test_log_decay_is_the_plain_chain():
    heads = 2
    f, a_log, dt_bias, dg = _draw(2, (B, T, heads * D), (heads,),
                                  (heads * D,), (B, T, heads, D))
    f = (3 * f).astype(bf16)

    def kernel_path(f, a_log, dt_bias):
        return mc.log_decay(f, a_log, dt_bias, block=BLOCK, interpret=True)

    def plain(f, a_log, dt_bias):
        return -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            (f.astype(f32) + dt_bias.astype(f32)).reshape(B, T, heads, D))

    got, grads = _value_and_grads(kernel_path, (f, a_log, dt_bias), dg)
    want, grads_want = _value_and_grads(plain, (f, a_log, dt_bias), dg)
    assert got.dtype == f32 and got.shape == (B, T, heads, D)
    _close(got, want)
    for g, w, rel in zip(grads, grads_want, (1 / 128, 2e-5, 2e-5)):
        assert g.dtype == w.dtype
        _close(g, w, rel=rel)


def _plain_ssm_conv(plane, taps, bias, first):
    """``Mamba2Mixer.conv_act``'s convolution, written out."""
    x = plane[..., first:first + taps.shape[1]].astype(f32)
    return jax.nn.silu(causal_depthwise_conv(x, taps.astype(f32))
                       + bias.astype(f32)).astype(plane.dtype)


@pytest.mark.parametrize("lanes, first", [(None, 512), (128, 512), (256, 0)],
                         ids=["2_lane_blocks", "8_lane_blocks", "a_slice"])
def test_ssm_conv_silu_is_the_plain_chain(lanes, first):
    """[z | xBC | dt] of 512 | 1,024 | 8 lanes, the joined channels cut
    from the plane where they lie (or handed as a plane of their own):
    value, the plane's gradient (zeros outside the joined channels), the
    taps' and the bias's, over four time blocks of two sequences."""
    joined, width = 1024, first + 1024 + (8 if first else 0)
    plane, taps, bias, dy = _draw(5, (B, T, width), (4, joined), (joined,),
                                  (B, T, joined))
    plane, dy = plane.astype(bf16), dy.astype(bf16)

    def kernel_path(plane, taps, bias):
        return mc.ssm_conv_silu(plane, taps, bias, first=first, block=BLOCK,
                                lanes=lanes, interpret=True)

    args = (plane, 0.5 * taps, 0.5 * bias)
    got, (dx, dw, db) = _value_and_grads(kernel_path, args, dy)
    want, (dx_w, dw_w, db_w) = _value_and_grads(
        functools.partial(_plain_ssm_conv, first=first), args, dy)
    assert got.dtype == bf16 and got.shape == (B, T, joined)
    _close(got, want, rel=1 / 128)
    assert np.mean(np.asarray(got) != np.asarray(want)) < 1e-3
    assert dx.dtype == bf16 and dx.shape == plane.shape
    _close(dx, dx_w, rel=1 / 128)
    assert dw.dtype == db.dtype == f32
    _close(dw, dw_w)
    _close(db, db_w)
    if first:                              # nothing flows into z's or dt's
        assert not np.asarray(dx[..., :first]).any()
        assert not np.asarray(dx[..., first + joined:]).any()


def _plain_ssm_norm(y, xbc, plane, skip, scale, groups, eps):
    """``Mamba2Mixer.gated_group_norm``, written out: the skip a HEAD,
    the gate before the norm, the norm over a group's channels."""
    b, t, inner = y.shape
    h = skip.shape[0]
    v = y.astype(f32).reshape(b, t, h, -1) + skip.astype(f32)[:, None] * (
        xbc[..., :inner].astype(f32).reshape(b, t, h, -1))
    v = v.reshape(b, t, inner) * jax.nn.silu(plane[..., :inner].astype(f32))
    v = v.reshape(b, t, groups, inner // groups)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True) + eps)
    return (v.reshape(b, t, inner) * scale.astype(f32)).astype(y.dtype)


@pytest.mark.parametrize("groups, lanes", [(4, None), (4, 128), (1, None),
                                           (2, 256)],
                         ids=["4_groups_a_block", "4_groups_4_blocks",
                              "1_group", "2_groups_2_blocks"])
def test_ssm_gate_norm_is_the_plain_chain(groups, lanes):
    """y of 512 channels (8 heads of 64), u the first 512 lanes of a
    1,024-lane plane, z the first 512 of a 1,544-lane one: value and the
    gradients of y, u (zeros past it), z (zeros past it), D a HEAD and the
    scale, the two sums over four time blocks of two sequences."""
    inner, heads, eps = 512, 8, 1e-5
    y, xbc, plane, skip, scale, dout = _draw(
        6, (B, T, inner), (B, T, 1024), (B, T, 1544), (heads,), (inner,),
        (B, T, inner))
    y, xbc, plane, dout = (a.astype(bf16) for a in (y, xbc, plane, dout))
    args = (y, xbc, plane, 1 + skip, 1 + 0.3 * scale)

    def kernel_path(y, xbc, plane, skip, scale):
        return mc.ssm_gate_norm(y, xbc, plane, jnp.repeat(skip, inner // heads),
                                scale, groups, eps, block=BLOCK, lanes=lanes,
                                interpret=True)

    got, grads = _value_and_grads(kernel_path, args, dout)
    want, grads_w = _value_and_grads(functools.partial(
        _plain_ssm_norm, groups=groups, eps=eps), args, dout)
    assert got.dtype == bf16
    _close(got, want, rel=1 / 128)
    assert np.mean(np.asarray(got) != np.asarray(want)) < 1e-3
    for g, w, a, rel in zip(grads, grads_w, args, (1 / 128,) * 3 + (2e-5,) * 2):
        assert g.dtype == a.dtype and g.shape == a.shape
        _close(g, w, rel=rel)
    assert not np.asarray(grads[1][..., inner:]).any()
    assert not np.asarray(grads[2][..., inner:]).any()


@pytest.mark.parametrize("kwargs, path, why", [
    (dict(ssm=True), "plain", "the backend is cpu, not a TPU"),
    (dict(ssm=True, interpret=True), "kernel", "interpreted"),
    (dict(ssm=True, interpret=False), "kernel", "compiled for the TPU"),
    (dict(ssm=True, dtype=f32, interpret=True), "plain", "float32"),
    (dict(ssm=True, joined=6144 + 64, interpret=True), "plain",
     "6208 joined channels"),
    (dict(ssm=True, groups=3, interpret=True), "plain", "in 3 norm groups"),
    (dict(ssm=True, groups=64, interpret=True), "plain",
     "not whole lane tiles"),
    (dict(ssm=True, length=8192 + 128, interpret=True), "plain",
     "not whole blocks of 256"),
    (dict(), "plain", "the backend is cpu, not a TPU"),
    (dict(interpret=True), "kernel", "interpreted"),
    (dict(interpret=False), "kernel", "compiled for the TPU"),
    (dict(head_dims=(64,), interpret=True), "plain", "(64,) wide"),
    (dict(head_dims=(128, 256), interpret=True), "plain", "(128, 256) wide"),
    (dict(length=8192 + 64, interpret=True), "plain", "not whole blocks"),
    (dict(heads=(16, 3), interpret=True), "plain", "a pair of heads"),
    (dict(dtype=f32, interpret=True), "plain", "float32"),
])
def test_mixer_chain_path_reads_the_operands(kwargs, path, why):
    """(``ssm``: ``ssm_chain_path``, ``Mamba2Mixer``'s twin of it, at the
    published 4,096 channels in 8 norm groups and 6,144 joined.)"""
    if kwargs.get("ssm"):
        args = dict(length=8192, inner=4096, joined=6144, groups=8, dtype=bf16)
        kwargs = {k: v for k, v in kwargs.items() if k != "ssm"}
        got, said = mc.ssm_chain_path(**{**args, **kwargs})
    else:
        args = dict(length=8192, head_dims=(128, 128), heads=(16, 32),
                    dtype=bf16)
        got, said = mc.mixer_chain_path(**{**args, **kwargs})
    assert got == path and why in said


def _mixer(kind, **kw):
    from dinov3_tpu.models.decoder import GDNMixer, KDAMixer

    if kind == "kda":
        return KDAMixer(num_heads=2, head_dim=D, **kw)
    return GDNMixer(key_heads=2, value_heads=4, key_dim=D, value_dim=D, **kw)


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_mixer_on_the_kernel_chains_is_itself_on_the_plain_ones(
        monkeypatch, kind):
    """One set of parameters (the plain path's tree: a leaf the kernel
    path did not read would get no gradient) through both: the output and
    every parameter's gradient. The
    delta rule between the chains is the token recurrence on both sides
    (the chunked scan at heads of 128 compiles for 12 s here)."""
    from dinov3_tpu.models import decoder
    from dinov3_tpu.ops.kda import kda_recurrent

    monkeypatch.setattr(
        decoder, "kda_chunked", lambda q, k, v, g, beta, q_scale:
        kda_recurrent(q.astype(f32) * q_scale, k, v, g, beta))
    x, dy = _draw(3, (1, 128, 64), (1, 128, 64))
    plain, kernels = _mixer(kind), _mixer(kind, chains_interpret=True)
    shapes = jax.eval_shape(plain.init, jax.random.key(4), x)
    # weights of a size that matters (the init's 0.02 makes every chain
    # nearly linear)
    leaves, tree = jax.tree.flatten(nn.meta.unbox(shapes))
    params = jax.tree.unflatten(tree, [
        0.3 * jax.random.normal(k, p.shape, p.dtype)
        for k, p in zip(jax.random.split(jax.random.key(4), len(leaves)),
                        leaves)])

    def loss(mixer, params):
        return jnp.vdot(mixer.apply(params, x).astype(f32), dy)

    got, got_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, kernels)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        functools.partial(loss, plain)))(params)
    _close(got, want, rel=1e-2)
    flat = jax.tree_util.tree_leaves_with_path(want_grads)
    assert len(flat) == {"kda": 15, "gdn": 7}[kind]
    for (path, w), g in zip(flat, jax.tree.leaves(got_grads)):
        assert np.abs(np.asarray(w)).max() > 0, path
        _close(g, w, rel=3e-2)
