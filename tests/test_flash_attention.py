"""Flash-attention kernel vs the unfused XLA path (interpret mode on the
CPU test mesh; the identical kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import pytest

from dinov3_tpu.ops.attention import xla_attention
from dinov3_tpu.ops.flash_attention import flash_attention


def _rand_qkv(rng, B, N, h, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (B, N, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize(
    "B,N,h,d",
    [
        (2, 128, 2, 64),    # aligned
        (1, 201, 3, 64),    # ViT-S/16 global crop: 196 patches + cls + 4 reg
        (2, 41, 2, 32),     # local crop, N << lane width
        (1, 640, 2, 64),    # multiple k blocks after padding
    ],
)
def test_forward_matches_xla(rng, B, N, h, d):
    q, k, v = _rand_qkv(rng, B, N, h, d)
    out = flash_attention(q, k, v, interpret=True)
    ref = xla_attention(q, k, v)
    assert out.shape == (B, N, h, d)
    assert jnp.allclose(out, ref, atol=2e-5, rtol=2e-5), (
        jnp.abs(out - ref).max()
    )


def test_gradients_match_xla(rng):
    B, N, h, d = 2, 137, 2, 32
    q, k, v = _rand_qkv(rng, B, N, h, d)
    tangent = jax.random.normal(jax.random.fold_in(rng, 7), (B, N, h, d))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, interpret=True) * tangent)

    def loss_ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v) * tangent)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        err = jnp.abs(gf - gr).max()
        assert jnp.allclose(gf, gr, atol=5e-5, rtol=5e-5), (name, err)


def test_bf16_inputs_fp32_softmax(rng):
    B, N, h, d = 1, 130, 2, 64
    q, k, v = _rand_qkv(rng, B, N, h, d, jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = xla_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32))
    assert jnp.allclose(out.astype(jnp.float32), ref, atol=3e-2), (
        jnp.abs(out.astype(jnp.float32) - ref).max()
    )


def test_jit_and_vit_shapes(rng):
    # jit-compiles once per static shape, runs under value_and_grad
    q, k, v = _rand_qkv(rng, 2, 261, 4, 64)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    assert jnp.isfinite(f(q, k, v))


def test_flash_block_caps_honored():
    """kernels.flash_block_q/kv cap the kernel block sizes (they were
    previously declared in the schema but never consumed)."""
    from dinov3_tpu.ops.flash_attention import _block_sizes

    assert _block_sizes(1024, 128, 256) == (128, 256)
    assert _block_sizes(1024) == (512, 512)
    assert _block_sizes(1152) == (128, 128)  # 1152 = 9*128

    # and the caps thread from config to the attention module
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.models import backbone_kwargs_from_cfg

    cfg = get_default_config()
    apply_dot_overrides(cfg, ["student.arch=vit_test",
                              "kernels.flash_block_q=256"])
    kw = backbone_kwargs_from_cfg(cfg)
    assert kw["flash_block_q"] == 256 and kw["flash_block_kv"] == 512


def test_auto_dispatch_threshold(monkeypatch):
    """The auto dispatch keeps every *measured* regime on dense XLA.

    Full-step evidence (round 5, before PR 1, one v5e chip): dense beats
    flash at N=201 (224px) and N=1029 (512px, 9.99 vs 7.65 img/s/chip),
    so auto must choose xla there; flash stays reachable at 2309+
    (768px) where its O(N) memory is the point. The backend is
    monkeypatched — this pins the threshold logic, not the TPU.
    """
    from dinov3_tpu.ops import attention as att

    chosen = {}

    def fake_xla(q, k, v, *a, **kw):
        chosen["impl"] = "xla"
        return q

    def fake_flash(q, k, v, **kw):
        chosen["impl"] = "pallas"
        return q

    monkeypatch.setattr(att, "xla_attention", fake_xla)
    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    import dinov3_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", fake_flash)

    for N, want in [(201, "xla"), (1029, "xla"), (1054, "xla"),
                    (2309, "pallas"), (4096, "pallas")]:
        q = jnp.zeros((1, N, 2, 32), jnp.bfloat16)
        att.dispatch_attention(q, q, q, impl="auto")
        assert chosen["impl"] == want, (N, chosen["impl"], want)

    # kernels.flash_min_seq override still wins over the builtin
    q = jnp.zeros((1, 1029, 2, 32), jnp.bfloat16)
    att.dispatch_attention(q, q, q, impl="auto", flash_min_seq=512)
    assert chosen["impl"] == "pallas"
