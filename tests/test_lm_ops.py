"""The decoders' operators at a tiny size on the CPU, seeded inputs (moved
out of tests/test_lm_decoder.py, whose worker they held for minutes; names,
parameters and bodies as they were):

(a) the chunked delta rule (ops/kda.py) against the recurrence, forward and
    gradient, over lengths that are and are not multiples of the chunk, at
    any decay; its Pallas kernels (interpreted) against both, and which
    path a call takes; the same at a gate of rank 3 (ONE decay a value
    head, q and k at the key heads): the scalar-gate kernel pair against
    the recurrence and against the per-channel pair;
(b) latent attention's blockwise causal core (ops/attention.py, q/k wider
    than v) against a plain masked softmax.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lm_decoder import _loops_and_kernels


# ---------------- (a) the delta rule ----------------

def _kda_inputs(seed, b, t, h, dk, dv, rate=1.6):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (unit(jax.random.normal(ks[0], (b, t, h, dk))),
            unit(jax.random.normal(ks[1], (b, t, h, dk))),
            jax.random.normal(ks[2], (b, t, h, dv)),
            -rate * jax.random.uniform(ks[3], (b, t, h, dk)),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h))))


@pytest.mark.parametrize("t", [64, 192, 100, 37])
def test_chunked_delta_rule_is_the_recurrence(t):
    from dinov3_tpu.ops.kda import kda_chunked, kda_recurrent

    x = _kda_inputs(t, 2, t, 3, 16, 8)
    got = jax.jit(kda_chunked)(*x)
    want = jax.jit(kda_recurrent)(*x)
    assert got.shape == want.shape == (2, t, 3, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(*x)

    for g, w in zip(grads(kda_chunked), grads(kda_recurrent)):
        assert float(jnp.max(jnp.abs(g - w))) <= 2e-5 * float(
            jnp.max(jnp.abs(w))) + 1e-7


@pytest.mark.parametrize("decay", ["published", "fast", "spikes"])
def test_delta_rule_is_finite_and_exact_at_any_decay(decay):
    """The decay the published initial values reach (1.6 nats a token on
    every channel), one no learned value is kept from (30 nats a token:
    about one reference token a chunk, float32 overflowed past 2.7) and
    single tokens that wipe a channel (200 nats) between tokens that keep
    it: finite and the recurrence's, forward and gradient (the running
    log decay is float32: where a chunk decays by thousands of nats a
    difference of two of its values is good to 1e-4, not 1e-7); q_scale
    multiplies the output."""
    from dinov3_tpu.ops.kda import kda_chunked, kda_recurrent

    q, k, v, g, beta = _kda_inputs(3, 1, 128, 2, 16, 16)
    if decay == "published":
        g = jnp.full_like(g, -1.6)
    elif decay == "fast":
        g = jnp.where(jnp.arange(16) < 8, -30.0, g)
    else:
        g = jnp.where((jnp.arange(128) % 7 == 3)[None, :, None, None], -200.0, 0.1 * g)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    got = jax.jit(lambda *a: kda_chunked(*a, chunk=64, q_scale=0.25))(
        q, k, v, g, beta)
    want = 0.25 * kda_recurrent(q, k, v, g, beta)
    loose = 100.0 if decay == "spikes" else 1.0
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-6 * loose)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(q, k, *a))), argnums=(0, 1, 2)))(
                v, g, beta)

    for got_g, want_g in zip(grads(kda_chunked), grads(kda_recurrent)):
        assert bool(jnp.isfinite(got_g).all())
        assert float(jnp.max(jnp.abs(got_g - want_g))) <= loose * 2e-5 * float(
            jnp.max(jnp.abs(want_g))) + 1e-7
    with pytest.raises(ValueError, match="power of two"):
        kda_chunked(q, k, v, g, beta, chunk=48)


def _grad_gap(got, want):
    return float(jnp.max(jnp.abs(got - want))) - 2e-5 * float(
        jnp.max(jnp.abs(want))) - 1e-7


@pytest.mark.parametrize("t", [128, 192, 100])
def test_kda_kernel_is_the_recurrence_and_the_plain_path(t):
    """The two Pallas kernels (interpreted) at widths they take, over
    whole chunks, three chunks and a padded tail: the token recurrence's
    and the plain scan's output and all five gradients (the backward
    kernel works from the starting states the forward rule wrote)."""
    from dinov3_tpu.ops.kda import kda_chunked, kda_path, kda_recurrent

    assert kda_path(128, 128, interpret=True)[0] == "kernel"
    x = _kda_inputs(t, 2, t, 2, 128, 128)
    kernel = lambda *a: kda_chunked(*a, interpret=True)  # noqa: E731
    got = jax.jit(kernel)(*x)
    assert got.shape == (2, t, 2, 128) and got.dtype == jnp.float32
    for other in (kda_recurrent, kda_chunked):
        np.testing.assert_allclose(got, jax.jit(other)(*x), atol=2e-6)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(*x)

    got_g = grads(kernel)
    for other in (kda_recurrent, kda_chunked):
        for g, w in zip(got_g, grads(other)):
            assert _grad_gap(g, w) <= 0


@pytest.mark.parametrize("decay", ["published", "fast", "spikes"])
def test_kda_kernel_is_finite_and_exact_at_any_decay(decay):
    """``test_delta_rule_is_finite_and_exact_at_any_decay``'s three
    regimes through the two kernels: their levels keep every factor at
    most 1 as the plain path's do, backward as forward."""
    from dinov3_tpu.ops.kda import kda_chunked, kda_recurrent

    q, k, v, g, beta = _kda_inputs(3, 1, 128, 2, 128, 128)
    if decay == "published":
        g = jnp.full_like(g, -1.6)
    elif decay == "fast":
        g = jnp.where(jnp.arange(128) < 64, -30.0, g)
    else:
        g = jnp.where((jnp.arange(128) % 7 == 3)[None, :, None, None], -200.0, 0.1 * g)
    q, k = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16)
    kernel = lambda *a: kda_chunked(  # noqa: E731
        *a, q_scale=0.25, interpret=True)
    got = jax.jit(kernel)(q, k, v, g, beta)
    want = 0.25 * kda_recurrent(q, k, v, g, beta)
    loose = 100.0 if decay == "spikes" else 1.0
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=2e-6 * loose)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(q, k, *a))), argnums=(0, 1, 2)))(
                v, g, beta)

    for got_g, want_g in zip(grads(kernel),
                             grads(lambda *a: 0.25 * kda_recurrent(*a))):
        assert bool(jnp.isfinite(got_g).all())
        assert float(jnp.max(jnp.abs(got_g - want_g))) <= loose * 2e-5 * float(
            jnp.max(jnp.abs(want_g))) + 1e-7


@pytest.mark.parametrize("case", ["odd_heads", "bf16", "one_chunk"])
def test_kda_backward_kernel_cases(case):
    """What the backward kernel's blocking could get wrong: an odd head
    count (the head the kernels add gets no gradient out), bfloat16
    q, k, v (their gradients come back bfloat16, the recurrence's within a
    rounding), and three sequences of ONE chunk (the state's cotangent is
    zeroed a sequence, not a call)."""
    from dinov3_tpu.ops.kda import kda_chunked, kda_recurrent

    b, t, h = {"odd_heads": (1, 128, 3), "bf16": (2, 128, 2),
               "one_chunk": (3, 64, 2)}[case]
    x = _kda_inputs(7, b, t, h, 128, 128)
    if case == "bf16":
        x = tuple(a.astype(jnp.bfloat16) for a in x[:3]) + x[3:]

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(*x)

    got = grads(lambda *a: kda_chunked(*a, interpret=True))
    for g, w, a in zip(got, grads(kda_recurrent), x):
        assert g.shape == a.shape and g.dtype == a.dtype
        if g.dtype == jnp.bfloat16:      # half a unit in the last place
            assert float(jnp.max(jnp.abs(
                g.astype(jnp.float32) - w.astype(jnp.float32)))) <= 2 ** -8 * float(
                    jnp.max(jnp.abs(w.astype(jnp.float32))))
        else:
            assert _grad_gap(g, w) <= 0


def test_kda_gradient_program_is_two_forward_kernels_and_one_backward():
    """A rematerialised layer's gradient on the kernel path: the primal
    pass, the forward rule again (it writes the states) and ONE backward
    kernel, no loop over chunks beside them; on the scan path the three
    loops and no kernel."""
    from dinov3_tpu.ops.kda import (
        BACKWARD_KERNEL_NAME,
        KERNEL_NAME,
        kda_chunked,
    )

    x = _kda_inputs(0, 1, 128, 2, 128, 128)

    def program(**kw):
        layer = jax.checkpoint(lambda *a: kda_chunked(*a, **kw))
        return sorted(_loops_and_kernels(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(layer(*a)), argnums=(0, 1, 2, 3, 4)))(*x).jaxpr,
            []))

    assert program(interpret=True) == [
        BACKWARD_KERNEL_NAME, KERNEL_NAME, KERNEL_NAME]
    assert program() == ["scan"] * 3


def test_kda_dispatch_reads_the_path_off_the_input():
    """Nobody sets the path: off the TPU, or at widths off the lane
    tiling, or at another chunk, the plain scan runs (no kernel in the
    program); a test's ``interpret`` alone puts the kernel there."""
    from dinov3_tpu.ops.kda import KERNEL_NAME, kda_chunked, kda_path

    assert kda_path(16, 16)[0] == "scan" and "128" in kda_path(16, 16)[1]
    assert kda_path(16, 128, interpret=True)[0] == "scan"
    assert kda_path(128, 128, chunk=32, interpret=True)[0] == "scan"
    assert kda_path(128, 128) == ("scan", "the backend is cpu, not a TPU")
    assert kda_path(128, 128, interpret=True)[0] == "kernel"

    def program(dk, **kw):
        x = _kda_inputs(0, 1, 64, 1, dk, dk)
        return str(jax.make_jaxpr(lambda *a: kda_chunked(*a, **kw))(*x))

    assert KERNEL_NAME not in program(16, interpret=True)
    assert KERNEL_NAME not in program(128)
    assert KERNEL_NAME not in program(128, chunk=32, interpret=True)
    assert KERNEL_NAME in program(128, interpret=True)


# ---------------- (a') the delta rule at a scalar gate ----------------

def _scalar_inputs(seed, b, t, hk, hv, dtype=jnp.float32, rate=1.6):
    """``_kda_inputs`` at heads of 128 with q and k at ``hk`` key heads and
    ONE log decay a value head and token (a gate of rank 3)."""
    q, k, v, g, beta = _kda_inputs(seed, b, t, hv, 128, 128, rate)
    return (q[:, :, :hk].astype(dtype), k[:, :, :hk].astype(dtype),
            v.astype(dtype), g[..., 0], beta)


def _five_grads(fn, x):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(*x)


def _same_gradient(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    if got.dtype == jnp.bfloat16:        # a unit in the last place
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(got - want))) <= 2 ** -7 * float(
            jnp.max(jnp.abs(want)))
    else:
        assert _grad_gap(got, want) <= 0


@pytest.mark.parametrize("b, hk, hv, t, dtype", [
    (1, 1, 2, 128, "float32"), (1, 2, 2, 100, "float32"),
    (1, 2, 4, 192, "bfloat16"), (2, 3, 3, 64, "bfloat16")],
    ids=["r2", "r1_tail", "r2_bf16", "r1_odd_bf16"])
def test_scalar_gate_kernels_are_the_recurrence_and_the_per_channel_kernels(
        b, hk, hv, t, dtype):
    """The scalar-gate kernel pair (interpreted) at one and two value heads
    a key head, float32 and bfloat16 q, k, v, whole chunks, a padded tail,
    and an odd head count on two sequences of ONE chunk (the state and its
    cotangent are zeroed a sequence): the token recurrence's output and
    five gradients, and those of the per-channel kernels fed q and k
    repeated and the gate broadcast — their dq and dk summed over a key
    head's value heads, their dg over the key channels."""
    from dinov3_tpu.ops.kda import _per_channel, kda_chunked, kda_recurrent

    x = _scalar_inputs(t, b, t, hk, hv, jnp.dtype(dtype))
    kernel = lambda *a: kda_chunked(*a, q_scale=0.25, interpret=True)  # noqa: E731
    got = jax.jit(kernel)(*x)
    assert got.shape == (b, t, hv, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        got, 0.25 * jax.jit(kda_recurrent)(*x), atol=2e-6)
    got_g = _five_grads(kernel, x)
    for g, w in zip(got_g, _five_grads(
            lambda *a: 0.25 * kda_recurrent(*a), x)):
        _same_gradient(g, w)
    # the per-channel kernels at the repeated and broadcast operands, in
    # float32 so that the sums over heads and channels round once
    q, k, v, g, beta = (a.astype(jnp.float32) for a in x)
    wide = _per_channel(q, k, v, g)
    np.testing.assert_allclose(
        got, jax.jit(kernel)(wide[0], wide[1], v, wide[2], beta), atol=2e-6)
    dq, dk, dv, dg, dbeta = _five_grads(
        kernel, (wide[0], wide[1], v, wide[2], beta))
    heads = lambda a: a.reshape(b, t, hk, hv // hk, 128).sum(3)  # noqa: E731
    for g, w in zip(got_g, (heads(dq), heads(dk), dv, dg.sum(-1), dbeta)):
        _same_gradient(g, w.astype(g.dtype))


def test_scalar_gate_kernels_are_finite_at_the_published_decays():
    """21 nats a token on one head (16 x softplus(1), the fastest head of
    the published initial values) beside 1e-6 on its pair: above the
    diagonal G_t - G_s reaches 63 x 21, whose exponential is inf and inf x
    0 NaN, so the kernels mask the EXPONENT. Output and five gradients
    finite, and the recurrence's."""
    from dinov3_tpu.ops.kda import kda_chunked, kda_recurrent

    q, k, v, g, beta = _scalar_inputs(11, 1, 128, 1, 2, jnp.bfloat16)
    g = jnp.broadcast_to(jnp.array([-21.0, -1e-6]), g.shape)
    x = (q, k, v, g, beta)
    kernel = lambda *a: kda_chunked(*a, interpret=True)  # noqa: E731
    got = jax.jit(kernel)(*x)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, jax.jit(kda_recurrent)(*x), atol=2e-5)
    for g_, w in zip(_five_grads(kernel, x), _five_grads(kda_recurrent, x)):
        g_, w = g_.astype(jnp.float32), w.astype(jnp.float32)
        assert bool(jnp.isfinite(g_).all())
        assert float(jnp.max(jnp.abs(g_ - w))) <= 2 ** -7 * float(
            jnp.max(jnp.abs(w))) + 1e-6


@pytest.mark.parametrize("case, hk, hv, dk, kw, path, why", [
    ("kernel", 2, 4, 128, dict(interpret=True), "kernel", "scalar gate, interpreted"),
    ("described", 16, 32, 128, dict(interpret=False), "kernel",
     "scalar gate, compiled for the TPU"),
    ("one_a_key_head", 2, 2, 128, dict(interpret=True), "kernel", "scalar gate"),
    ("cpu", 2, 4, 128, dict(), "scan", "the backend is cpu"),
    ("narrow", 2, 4, 16, dict(interpret=True), "scan", "multiples of 128"),
    ("chunk", 2, 4, 128, dict(chunk=32, interpret=True), "scan", "chunk 32"),
    ("four_a_key_head", 1, 4, 128, dict(interpret=True), "scan",
     "4 value heads on 1 key heads"),
])
def test_scalar_gate_dispatch_reads_the_path_off_the_operands(
        case, hk, hv, dk, kw, path, why):
    """A gate of rank 3 takes the scalar-gate kernels where the per-channel
    ones would run and the head grouping is one or two value heads a key
    head; everywhere else q and k are repeated, the gate broadcast and the
    plain scan runs. ``kda_path`` says which and why, and the program
    holds the kernel's name or it does not; a gate of rank 4 never reaches
    the scalar pair."""
    from dinov3_tpu.ops.kda import (
        KERNEL_NAME,
        SCALAR_KERNEL_NAME,
        kda_chunked,
        kda_path,
    )

    found = kda_path(dk, dk, gate_heads=(hk, hv), **kw)
    assert found[0] == path and why in found[1], found
    if case == "described":     # nothing here can lower it
        return
    x = _scalar_inputs(0, 1, 64, hk, hv)
    x = tuple(a[..., :dk] if a.ndim == 4 else a for a in x)
    program = str(jax.make_jaxpr(lambda *a: kda_chunked(*a, **kw))(*x))
    assert (SCALAR_KERNEL_NAME in program) == (path == "kernel")
    assert KERNEL_NAME not in program
    wide = jnp.broadcast_to(x[3][..., None], x[3].shape + (dk,))
    program = str(jax.make_jaxpr(lambda *a: kda_chunked(*a, **kw))(
        x[2][..., :dk], x[2][..., :dk], x[2], wide, x[4]))
    assert SCALAR_KERNEL_NAME not in program


def test_scalar_gate_gradient_program_is_two_forward_kernels_and_one_backward():
    """``test_kda_gradient_program_is_two_forward_kernels_and_one_backward``
    at a gate of rank 3: under a layer's remat the primal, the forward
    rule and ONE backward kernel of the scalar pair, no loop over chunks;
    and a mismatched head grouping is refused by name."""
    from dinov3_tpu.ops.kda import (
        SCALAR_BACKWARD_KERNEL_NAME,
        SCALAR_KERNEL_NAME,
        kda_chunked,
    )

    x = _scalar_inputs(0, 1, 128, 1, 2)
    layer = jax.checkpoint(lambda *a: kda_chunked(*a, interpret=True))
    assert sorted(_loops_and_kernels(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(layer(*a)), argnums=(0, 1, 2, 3, 4)))(*x).jaxpr,
        [])) == [SCALAR_BACKWARD_KERNEL_NAME, SCALAR_KERNEL_NAME,
                 SCALAR_KERNEL_NAME]
    with pytest.raises(ValueError, match="3 value heads on 2 key heads"):
        kda_chunked(*_scalar_inputs(0, 1, 64, 2, 3))


# ---------------- (b) the causal blockwise core ----------------

@pytest.mark.parametrize("n, block_q", [(64, 32), (100, 48), (96, 128)])
def test_causal_blockwise_attention_is_masked_softmax(n, block_q):
    from dinov3_tpu.ops.attention import (
        causal_blockwise_attention,
        dispatch_attention,
        xla_attention,
    )

    ks = jax.random.split(jax.random.key(n), 3)
    q = jax.random.normal(ks[0], (2, n, 3, 24))
    k = jax.random.normal(ks[1], (2, n, 3, 24))
    v = jax.random.normal(ks[2], (2, n, 3, 16))  # narrower than q and k
    # each side ONE compiled program: op by op the tiles dispatch for
    # half a minute
    blockwise = lambda *a: causal_blockwise_attention(  # noqa: E731
        *a, block_q=block_q)
    dense = lambda *a: xla_attention(*a, causal=True)  # noqa: E731
    want = jax.jit(dense)(q, k, v)
    np.testing.assert_allclose(jax.jit(blockwise)(q, k, v), want, atol=2e-6)
    via = jax.jit(lambda *a: dispatch_attention(*a, causal=True))(
        q, k, v)  # the shipped blocks
    np.testing.assert_allclose(via, want, atol=2e-6)
    f = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(f(blockwise), f(dense)):
        np.testing.assert_allclose(g, w, atol=5e-6)
    with pytest.raises(ValueError, match="segment"):
        dispatch_attention(q, k, v, causal=True, seg=jnp.zeros((2, n), jnp.int32))
