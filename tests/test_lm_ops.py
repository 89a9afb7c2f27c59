"""The decoders' operators at a tiny size on the CPU, seeded inputs (moved
out of tests/test_lm_decoder.py, whose worker they held for minutes; names,
parameters and bodies as they were):

(a) the chunked delta rule (ops/kda.py) against the recurrence, forward and
    gradient, over lengths that are and are not multiples of the chunk, at
    any decay; its Pallas kernels (interpreted) against both, and which
    path a call takes;
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
