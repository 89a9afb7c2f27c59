"""The index loss of a key-selecting layer on its kernel path (PR 40).

(a) ``ops/causal_attention.py index_loss_tiles``, interpreted here at
    small blocks, against the plain strips of ``ops/sparse_index.py``
    (which stay the path of every backend but the TPU): the loss alone
    (the primal: running statistics) and with its closed-form gradient
    (the forward rule: a band walked twice), over rows that keep fewer
    than ``topk`` keys, ties at the threshold, ``p == 0`` at kept keys, two
    sequences, bands of every length.
(b) The core under a selection hands on its rows' log-sum-exp: the
    forward rule's own residual, bit for bit, and no gradient through it;
    under the causal triangle the pair is ``kernel_attention``'s, bit for
    bit (on the chip; to rounding here).
(c) A layer on the kernel path (the test steers ``causal_attention_path``:
    the program has no option) holds ONE ``causal_attn_fwd`` a pass, the
    index loss's kernel a pass, and no float32 plane as wide as the keys
    beside the selection's own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.ops import causal_attention as kernels
from dinov3_tpu.ops import sparse_index as si

BLOCKS = (128, 256)


def _case(name):
    """(qi, ki, a, plane, q, k, v) of one case, float32."""
    b, t, topk = (2, 256, 64) if name == "two_sequences" else (1, 512, 128)
    ks = jax.random.split(jax.random.key(len(name)), 6)
    qi = jax.random.normal(ks[0], (b, t, 2, 16))
    ki = jax.random.normal(ks[1], (b, t, 16))
    a = jax.random.normal(ks[2], (b, t, 2))
    q = jax.random.normal(ks[3], (b, t, 2, 128))
    k, v = (jax.random.normal(key, (b, t, 1, 128)) for key in ks[4:])
    if name == "ties":  # 32 distinct index keys: every threshold is tied
        ki = jnp.tile(ki[:, :32], (1, t // 32, 1))
    if name == "p_is_zero":  # scores so far apart that most of a row's
        q = q * 40.0         # kept keys get exp(.) = 0 from every head
    # the selection by a stable sort (ties to the lower key), on the host
    scores = np.sum(np.maximum(np.einsum("bthd,bsd->bths", qi, ki), 0.0)
                    * np.asarray(a)[..., None], axis=2)
    causal = np.tri(t, dtype=bool)
    rank = np.argsort(np.argsort(
        -np.where(causal, scores, -np.inf), axis=-1, kind="stable"), axis=-1)
    plane = causal & (rank < np.minimum(np.arange(t) + 1, topk)[:, None])
    return qi, ki, a, jnp.asarray(plane, jnp.int8), q, k, v


@pytest.mark.parametrize("name", [
    "rows_short_of_topk", "ties", "p_is_zero", "two_sequences"])
def test_index_loss_kernel_is_the_plain_strips(name):
    qi, ki, a, plane, q, k, v = _case(name)
    with jax.default_matmul_precision("highest"):
        _, lse = kernels.kernel_attention_selected(
            q, k, v, plane, 128 ** -0.5, *BLOCKS, True)
        alone, none = jax.jit(lambda *x: kernels.index_loss_tiles(
            *x, plane, q, k, lse, False, *BLOCKS, True))(qi, ki, a)
        loss, grads = jax.jit(lambda *x: kernels.index_loss_tiles(
            *x, plane, q, k, lse, True, *BLOCKS, True))(qi, ki, a)
        # strips grouped two at a time: no group but the first starts at 0
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda *x: si.index_loss(*x, plane, q, k, None, 64, 2),
            argnums=(0, 1, 2)))(qi, ki, a)
    assert none == () and float(want) > 0
    if name == "p_is_zero":
        kept = np.asarray(plane[0, -1] != 0)
        z = jnp.einsum("hd,khd->hk", q[0, -1], jnp.repeat(k[0], 2, 1)) * 128 ** -0.5
        p = jnp.mean(jnp.exp(z - lse[0, :, -1:]), 0)
        assert np.sum(np.asarray(p)[kept] == 0.0) > 8
    assert float(alone) == pytest.approx(float(want), rel=1e-6)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for got, ref in zip(grads, want_grads):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        gap = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
        assert gap < 4.4e-4, gap


def test_index_loss_takes_the_kernel_where_the_core_hands_on_its_lse():
    """``index_loss`` itself: given the core's log-sum-exp the shipped
    blocks' kernel (interpreted), given None the strips; one loss, one
    gradient through the ``custom_vjp``, under a layer's remat."""
    qi, ki, a, plane, q, k, v = (
        jnp.tile(x, (1, 2) + (1,) * (x.ndim - 2)) for x in _case("rows_short_of_topk"))
    plane = jnp.tril(jnp.tile(plane, (1, 1, 2)))  # 1,024 tokens: whole blocks
    _, lse = kernels.kernel_attention_selected(
        q, k, v, plane, 128 ** -0.5, 512, 1024, True)

    def both(rows_lse):  # under a layer's remat: the primal, then the rule
        return jax.jit(jax.value_and_grad(jax.checkpoint(
            lambda *x: si.index_loss(*x, plane, q, k, rows_lse, si.CHUNK, 2, True)),
            argnums=(0, 1, 2)))(qi, ki, a)

    with jax.default_matmul_precision("highest"):
        (loss, grads), (want, want_grads) = both(lse), both(None)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for got, ref in zip(grads, want_grads):
        assert float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)) < 4.4e-4
    assert not kernels.index_loss_fits((1, 64, 2, 96), (1, 64, 2, 16))
    assert not kernels.index_loss_fits((1, 64, 2, 128), (1, 64, 2, 24))


def test_the_core_hands_on_its_own_log_sum_exp():
    qi, ki, a, plane, q, k, v = _case("rows_short_of_topk")
    args = (128 ** -0.5, *BLOCKS, True)
    (o, lse), res = kernels._kernel_attention_selected_fwd(q, k, v, plane, *args)
    got = kernels.kernel_attention_selected(q, k, v, plane, *args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(o))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(lse))
    np.testing.assert_array_equal(
        np.asarray(lse), np.asarray(res[4]).reshape(lse.shape))
    # against whole rows
    z = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) * 128 ** -0.5
    want = jax.nn.logsumexp(jnp.where(plane[:, None] != 0, z, -jnp.inf), -1)
    np.testing.assert_allclose(lse, want, atol=2e-5)
    # no gradient goes back through it
    w = jax.random.normal(jax.random.key(9), o.shape)
    for g in jax.jit(jax.grad(lambda *x: jnp.sum(kernels.kernel_attention_selected(
            *x, plane, *args)[1]), argnums=(0, 1, 2)))(q, k, v):
        assert not np.any(np.asarray(g))
    # every causal pair kept: the dense kernel pair (bit for bit where one
    # compiler makes both, chip_smoke.py --phases dsa; interpreted here,
    # XLA:CPU contracts the two programs' multiply-adds differently)
    triangle = jnp.tril(jnp.ones(plane.shape, jnp.int8))
    dense = lambda *x: kernels.kernel_attention(  # noqa: E731
        *x, args[0], None, *args[1:])
    under = lambda *x: kernels.kernel_attention_selected(  # noqa: E731
        *x, triangle, *args)[0]
    both = lambda fn: jax.jit(lambda *x: (fn(*x), jax.grad(  # noqa: E731
        lambda *y: jnp.sum(fn(*y) * w), (0, 1, 2))(*x)))(q, k, v)
    for x, y in zip(jax.tree.leaves(both(dense)), jax.tree.leaves(both(under))):
        np.testing.assert_allclose(x, y, atol=2e-6)


def test_a_layer_on_the_kernel_path_runs_the_attention_once_a_pass(monkeypatch):
    from test_lm_decoder import _loops_and_kernels
    from test_lm_dsa import _mixer, tiny_cfg

    from dinov3_tpu.models import DecoderConfig

    dc = DecoderConfig.from_cfg(tiny_cfg([
        "lm.head_dim=128", "lm.num_attention_heads=4",
        "lm.sa_config.indexer_head_dim=16", "lm.sa_config.q_chunk_size=256",
        "lm.sa_config.kv_chunk_size=256"]))
    mixer = _mixer(dc)
    t = 1024
    x = jnp.zeros((1, t, 64), jnp.float32)
    params = jax.eval_shape(mixer.init, jax.random.key(0), x)["params"]

    def program():
        layer = jax.checkpoint(lambda p, x: mixer.apply({"params": p}, x))

        def total(p, x):
            y, aux = layer(p, x)
            return jnp.sum(y) + aux["index_loss"]

        return jax.make_jaxpr(jax.grad(total))(params, x).jaxpr

    def planes(jaxpr, found):
        """float32 arrays outside the kernels as wide as the keys and as
        large as half a [T, T]."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            found += [v.aval.shape for v in eqn.outvars
                      if v.aval.dtype == jnp.float32 and v.aval.shape[-1:] == (t,)
                      and v.aval.size >= t * t // 2]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                planes(sub, found)
        return found

    plain = program()
    assert not [n for n in _loops_and_kernels(plain, []) if n != "scan" and n != "while"]
    monkeypatch.setattr(kernels, "causal_attention_path",
                        lambda *a, **k: ("kernel", "the test says so"))
    steered = program()
    names = _loops_and_kernels(steered, [])
    assert sorted(n for n in names if n not in ("scan", "while")) == sorted([
        kernels.KERNEL_NAME, kernels.INDEX_LOSS_KERNEL_NAME,       # the pass
        kernels.KERNEL_NAME, kernels.BACKWARD_KERNEL_NAME,         # its backward
        kernels.INDEX_LOSS_GRAD_KERNEL_NAME])
    # what is left as wide as the keys is the selection's: the score strips
    # [rows, H_I, keys] of the thresholds and of the plane, in both passes
    hi = dc.index_num_heads
    wide = planes(steered, [])
    assert wide and all(s[-2:] == (hi, t) for s in wide), set(wide)
    assert len(planes(plain, [])) > len(wide)
    assert kernels.index_loss_path(
        ((1, t, 4, 128),) * 3, 16) == ("kernel", "the test says so")
    monkeypatch.undo()
    path, why = kernels.index_loss_path(((1, t, 4, 128),) * 3, 16)
    assert path == "strips" and "no log-sum-exp" in why and "not a TPU" in why
    path, why = kernels.index_loss_path(((1, t, 4, 128),) * 3, 24, interpret=False)
    assert path == "strips" and "index heads of 24" in why
