"""Ring attention (sequence/context parallelism) vs dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dinov3_tpu.ops.attention import xla_attention
from dinov3_tpu.parallel.ring_attention import ring_attention


def _mesh(eight_devices, seq):
    rest = 8 // seq
    arr = np.array(eight_devices).reshape(1, rest, 1, 1, seq, 1, 1)
    return Mesh(arr, ("dcn_data", "data", "pipe", "fsdp", "seq", "tensor",
                      "expert"))


def _qkv(rng, B, N, h, d, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (B, N, h, d), dtype) for k in ks)


@pytest.mark.parametrize("seq,N", [(4, 128), (4, 201), (8, 64), (2, 41)])
def test_ring_matches_dense(eight_devices, rng, seq, N):
    mesh = _mesh(eight_devices, seq)
    B, h, d = 2, 2, 16
    q, k, v = _qkv(rng, B, N, h, d)

    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))
    out = f(q, k, v)
    ref = xla_attention(q, k, v)
    assert out.shape == (B, N, h, d)
    err = jnp.abs(out - ref).max()
    assert jnp.allclose(out, ref, atol=1e-5, rtol=1e-5), err


def test_ring_gradients_match_dense(eight_devices, rng):
    mesh = _mesh(eight_devices, 4)
    B, N, h, d = 1, 50, 2, 8  # N=50 not divisible by 4 -> padded path
    q, k, v = _qkv(rng, B, N, h, d)
    tangent = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, h, d))

    g_ring = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ring_attention(q, k, v, mesh) * tangent),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(xla_attention(q, k, v) * tangent),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gr, gd, name in zip(g_ring, g_ref, "qkv"):
        err = jnp.abs(gr - gd).max()
        assert jnp.allclose(gr, gd, atol=2e-5, rtol=2e-5), (name, err)


def test_ring_with_sharded_inputs(eight_devices, rng):
    """Inputs already sharded over (data, seq) stay exact."""
    mesh = _mesh(eight_devices, 4)
    B, N, h, d = 4, 64, 2, 8
    q, k, v = _qkv(rng, B, N, h, d)
    sh = NamedSharding(mesh, P(("dcn_data", "data", "fsdp"), "seq", None, None))
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(qs, ks, vs)
    ref = xla_attention(q, k, v)
    assert jnp.allclose(out, ref, atol=1e-5, rtol=1e-5)


def _blocky_seg(B, N):
    """[B, N] int32 segment ids: a few contiguous blocks per row, with
    different block boundaries per batch row (crop-packing shape)."""
    rows = [jnp.arange(N) * (3 + b) // N for b in range(B)]
    return jnp.stack(rows).astype(jnp.int32)


@pytest.mark.parametrize("seq,N", [(4, 128), (4, 201), (2, 41)])
def test_ring_segment_mask_matches_dense(eight_devices, rng, seq, N):
    """Packed-crop block-diagonal masking: ring with rotating segment-id
    chunks must match the dense ``xla_attention(seg=...)`` oracle,
    including on the padded path (N not divisible by seq)."""
    mesh = _mesh(eight_devices, seq)
    B, h, d = 2, 2, 16
    q, k, v = _qkv(rng, B, N, h, d)
    seg = _blocky_seg(B, N)

    out = jax.jit(lambda q, k, v, s: ring_attention(q, k, v, mesh, seg=s))(
        q, k, v, seg)
    ref = xla_attention(q, k, v, seg=seg)
    err = jnp.abs(out - ref).max()
    assert jnp.allclose(out, ref, atol=1e-5, rtol=1e-5), err
    # the mask must actually bite: segmented != unsegmented
    assert not jnp.allclose(out, xla_attention(q, k, v), atol=1e-3)


def test_ring_segment_gradients_match_dense(eight_devices, rng):
    """custom_vjp backward with the segment ids co-rotating: dq/dk/dv
    match dense, and the integer seg input takes no cotangent."""
    mesh = _mesh(eight_devices, 4)
    B, N, h, d = 2, 50, 2, 8  # N=50 -> padded path with seg padding
    q, k, v = _qkv(rng, B, N, h, d)
    seg = _blocky_seg(B, N)
    tangent = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, h, d))

    g_ring = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, seg=seg) * tangent),
        argnums=(0, 1, 2),
    ))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(xla_attention(q, k, v, seg=seg) * tangent),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gr, gd, name in zip(g_ring, g_ref, "qkv"):
        err = jnp.abs(gr - gd).max()
        assert jnp.allclose(gr, gd, atol=2e-5, rtol=2e-5), (name, err)


def test_ring_collectives_scope_attributed(eight_devices, rng):
    """Anatomy-ledger census: every collective-permute the ring emits
    (fwd AND custom_vjp bwd) indexes under the ``ring_permute`` scope in
    the compiled HLO, and an executed profiler trace joins against it
    with zero unattributed collective time — the dp x seq twin of the
    bucketed-overlap round-trip in test_anatomy.py."""
    import shutil
    import tempfile

    from dinov3_tpu.telemetry.anatomy import (
        anatomy_ledger,
        build_op_index,
        ledger_summary,
    )

    mesh = _mesh(eight_devices, 4)
    B, N, h, d = 2, 64, 2, 8
    q, k, v = _qkv(rng, B, N, h, d)
    seg = _blocky_seg(B, N)
    tangent = jax.random.normal(jax.random.fold_in(rng, 3), (B, N, h, d))

    f = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            ring_attention(q, k, v, mesh, seg=seg) * tangent),
        argnums=(0, 1, 2),
    ))
    compiled = f.lower(q, k, v).compile()
    hlo = compiled.as_text()

    idx = build_op_index(hlo)
    colls = {n: i for n, i in idx.items() if i["category"] == "collective"}
    assert colls, "ring twin compiled away its collective-permutes"
    scopes = {i["scope"] for i in colls.values()}
    assert any((s or "").startswith("ring_permute") for s in scopes), scopes
    # no ring collective may index outside a ring_* scope
    stray = {n: i["scope"] for n, i in colls.items()
             if not (i["scope"] or "").startswith("ring_")}
    assert not stray, stray

    jax.block_until_ready(compiled(q, k, v))  # warmup outside the window
    tdir = tempfile.mkdtemp(prefix="ring_anat_", dir="/tmp")
    try:
        jax.profiler.start_trace(tdir)
        for _ in range(2):
            jax.block_until_ready(compiled(q, k, v))
        jax.profiler.stop_trace()

        ledger = anatomy_ledger(tdir, hlo_text=hlo, n_steps=2)
        assert ledger["hlo_joined"] is True
        assert ledger["unattributed_collective_ms"] == 0.0
        summary = ledger_summary(ledger)
        led_scopes = set(summary["collectives"])
        assert any(s.startswith("ring_") for s in led_scopes), led_scopes
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


@pytest.mark.parametrize("floor,n_tokens,rings", [
    (64, 64, True), (64, 16, False),
    (None, 1024, True), (None, 1023, False),
], ids=["floor64-n64", "floor64-n16", "default-n1024", "default-n1023"])
def test_ring_min_seq_dispatch_per_pass(
        eight_devices, monkeypatch, floor, n_tokens, rings):
    """Per-pass dispatch inside SelfAttention: on a dp x seq mesh a
    pass at or above the floor (``ops.attention.RING_MIN_SEQ``, read
    when the pass is traced) compiles to a ring program
    (collective-permutes present) while a shorter pass on the SAME
    module stays dense with seq-replicated activations (none). The
    ``default`` cases pin the shipped floor of 1024 tokens."""
    import flax.linen as nn

    from dinov3_tpu.ops import attention
    from dinov3_tpu.parallel.context import set_current_mesh

    if floor is None:
        assert attention.RING_MIN_SEQ == 1024
    else:
        monkeypatch.setattr(attention, "RING_MIN_SEQ", floor)
    mesh = _mesh(eight_devices, 2)
    D, h = 32, 2
    attn = attention.SelfAttention(
        dim=D, num_heads=h, seq_parallel=True,
        attn_impl="xla", dtype=jnp.float32, param_dtype=jnp.float32,
    )
    x = jax.random.normal(jax.random.key(0), (2, n_tokens, D))
    params = nn.meta.unbox(attn.init(jax.random.key(2), x[:, :16]))

    set_current_mesh(mesh)
    try:
        hlo = jax.jit(
            lambda p, x: attn.apply(p, x)
        ).lower(params, x).compile().as_text()
        assert ("collective-permute" in hlo) == rings
    finally:
        set_current_mesh(None)


def test_seq_parallel_train_step(eight_devices):
    """Full fused train step on a dp2 x fsdp2 x seq2 mesh."""
    import jax.numpy as jnp

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.train import build_train_setup, put_batch

    cfg = get_default_config()
    apply_dot_overrides(cfg, [
        "student.arch=vit_test", "student.patch_size=4",
        "student.drop_path_rate=0.0",
        "crops.global_crops_size=16", "crops.local_crops_size=8",
        "crops.local_crops_number=2",
        "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
        "dino.head_bottleneck_dim=16",
        "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
        "ibot.head_bottleneck_dim=16",
        "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
        "optim.scaling_rule=none",
        "parallel.data=2", "parallel.fsdp=2", "parallel.seq=2",
        "parallel.zero3=false",
    ])
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, 4, seed=0).items()}
    try:
        setup = build_train_setup(cfg, batch)
        assert setup.mesh.shape["seq"] == 2
        dbatch = put_batch(batch, setup.batch_shardings)
        state, metrics = setup.step_fn(
            setup.state, dbatch, setup.scalars(0), jax.random.key(0)
        )
        assert jnp.isfinite(metrics["total_loss"])
        assert int(state.step) == 1
    finally:
        set_current_mesh(None)
