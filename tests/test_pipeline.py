"""Pipeline parallelism (parallel/pipeline.py) on the 8-device CPU mesh.

The reference has no PP (SURVEY.md §2.5 "PP — absent"); these tests pin the
TPU-native addition: a GPipe schedule over stage-stacked block params must
be numerically identical to running the same blocks sequentially, and the
full SSL train step must run under a (data, pipe, fsdp) mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config
from dinov3_tpu.data import make_synthetic_batch
from dinov3_tpu.models import build_backbone
from dinov3_tpu.parallel import build_mesh, set_current_mesh
from dinov3_tpu.parallel.mesh import MeshSpec
from dinov3_tpu.train import build_train_setup, put_batch

SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "student.drop_path_rate=0.0", "student.layerscale=1.0e-5",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2",
    "dino.head_n_prototypes=32", "dino.head_hidden_dim=24",
    "dino.head_bottleneck_dim=8",
    "ibot.head_n_prototypes=32", "ibot.head_hidden_dim=24",
    "ibot.head_bottleneck_dim=8",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1", "optim.freeze_last_layer_epochs=1",
    "compute_precision.compute_dtype=fp32",
    "optim.scaling_rule=none",
]


def _cfg(extra=()):
    cfg = get_default_config()
    apply_dot_overrides(cfg, list(SMOL) + list(extra))
    return cfg


@pytest.fixture(autouse=True)
def _clear_mesh():
    yield
    set_current_mesh(None)


def test_pipelined_forward_matches_sequential(eight_devices):
    """Same init seed => pipelined forward == plain per-block forward.

    vit_test has 2 blocks; run 2 stages x 2 microbatches on a pipe=2 mesh.
    The stacked [S, L/S, ...] params are reshaped from the sequential
    blocks' params so both models compute with identical weights.
    """
    mesh = build_mesh(MeshSpec(data=2, pipe=2, fsdp=2), devices=eight_devices)
    set_current_mesh(mesh)

    cfg = _cfg()
    seq_model = build_backbone(cfg, teacher=True)
    apply_dot_overrides(cfg, ["parallel.pipe=2"])
    pipe_model = build_backbone(cfg, teacher=True)
    assert pipe_model.pipeline_stages == 2

    import flax.linen as nn

    x = jax.random.normal(jax.random.key(1), (4, 16, 16, 3), jnp.float32)
    seq_params = nn.meta.unbox(seq_model.init(jax.random.key(0), x))["params"]
    pipe_params = nn.meta.unbox(pipe_model.init(jax.random.key(0), x))["params"]

    # graft the sequential blocks' weights into the stage-stacked layout:
    # blocks_{i} -> stage axis s = i // (L/S), within-stage scan axis i % (L/S)
    from flax.core import unfreeze

    pipe_params = unfreeze(pipe_params)
    grafted = jax.tree.map(
        lambda a, b: jnp.stack([a[None], b[None]]),  # [S=2, L/S=1, ...]
        seq_params["blocks_0"], seq_params["blocks_1"],
    )
    target = pipe_params["pipeline"]["tick"]["stages"]["blocks"]["block"]
    same = jax.tree.map(lambda a, b: a.shape == b.shape, grafted, target)
    assert all(jax.tree.leaves(same))
    pipe_params["pipeline"]["tick"]["stages"]["blocks"]["block"] = grafted
    for k, v in seq_params.items():
        if not k.startswith("blocks_"):
            pipe_params[k] = v

    out_seq = seq_model.apply({"params": seq_params}, x)
    with mesh:
        out_pipe = jax.jit(
            lambda p, x: pipe_model.apply({"params": p}, x)
        )(pipe_params, x)
    tol = 2e-5
    np.testing.assert_allclose(
        np.asarray(out_seq["x_norm_clstoken"], np.float32),
        np.asarray(out_pipe["x_norm_clstoken"], np.float32),
        rtol=tol, atol=tol,
    )
    np.testing.assert_allclose(
        np.asarray(out_seq["x_norm_patchtokens"], np.float32),
        np.asarray(out_pipe["x_norm_patchtokens"], np.float32),
        rtol=tol, atol=tol,
    )


def test_microbatch_counts(eight_devices):
    """M > S and M == B paths produce the same result."""
    mesh = build_mesh(MeshSpec(data=2, pipe=2, fsdp=2), devices=eight_devices)
    set_current_mesh(mesh)
    cfg = _cfg(["parallel.pipe=2"])
    x = jax.random.normal(jax.random.key(1), (4, 16, 16, 3), jnp.float32)

    outs = []
    for m in (2, 4):
        apply_dot_overrides(cfg, [f"parallel.pipe_microbatches={m}"])
        model = build_backbone(cfg, teacher=True)
        import flax.linen as nn

        params = nn.meta.unbox(model.init(jax.random.key(0), x))
        with mesh:
            out = jax.jit(lambda p, x: model.apply(p, x))(params, x)
        outs.append(np.asarray(out["x_norm_clstoken"], np.float32))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)


def test_pipelined_train_step(eight_devices):
    """Full fused SSL step under (data=2, pipe=2, fsdp=2): finite loss over
    two steps (donation path) and stage-stacked params sharded over pipe."""
    cfg = _cfg(["parallel.data=2", "parallel.pipe=2", "parallel.fsdp=2",
                "parallel.zero3=false"])
    B = 8
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, B, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=eight_devices)
    assert setup.mesh.shape["pipe"] == 2

    # the stage axis of stacked block params must be sharded over pipe
    blk_sh = setup.state_shardings.params["student"]["backbone"]["pipeline"]
    def has_pipe(s):
        return any(
            "pipe" in (ax if isinstance(ax, tuple) else (ax,))
            for ax in s.spec if ax is not None
        )
    assert all(has_pipe(s) for s in jax.tree.leaves(blk_sh)), blk_sh
    blk = setup.state.params["student"]["backbone"]["pipeline"]["tick"]["stages"]
    leaf = jax.tree.leaves(blk)[0]
    assert leaf.shape[0] == 2  # n_stages leading axis

    dbatch = put_batch(batch, setup.batch_shardings)
    state, metrics = setup.step_fn(
        setup.state, dbatch, setup.scalars(0), jax.random.key(0)
    )
    assert np.isfinite(float(metrics["total_loss"]))
    assert int(state.step) == 1
    state, metrics2 = setup.step_fn(
        state, dbatch, setup.scalars(1), jax.random.key(0)
    )
    assert np.isfinite(float(metrics2["total_loss"]))


def test_pipeline_composes_with_ring_attention(eight_devices):
    """pipe=2 x seq=2 x data=2 in one program: GPipe stages whose attention
    runs ring attention over the seq axis (the pipeline's UNCONSTRAINED
    buffer dims must not force the token dim replicated)."""
    cfg = _cfg(["parallel.data=2", "parallel.pipe=2", "parallel.seq=2"])
    B = 8
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, B, seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=eight_devices)
    assert setup.mesh.shape["pipe"] == 2 and setup.mesh.shape["seq"] == 2
    dbatch = put_batch(batch, setup.batch_shardings)
    state, metrics = setup.step_fn(
        setup.state, dbatch, setup.scalars(0), jax.random.key(0)
    )
    assert np.isfinite(float(metrics["total_loss"]))


def test_pipeline_get_intermediate_layers_matches_unrolled(eight_devices):
    """get_intermediate_layers on a pipelined model (stage-owned collect
    buffers) must match the unrolled model given the same weights, for a
    mid-stage layer AND a stage-boundary layer — VERDICT r2 #5 deleted the
    NotImplementedError guard."""
    import flax.linen as nn

    from dinov3_tpu.models.vision_transformer import DinoVisionTransformer
    from dinov3_tpu.parallel.pipeline import unstack_pipeline_params

    mesh = build_mesh(MeshSpec(data=2, pipe=2, fsdp=2), devices=eight_devices)
    set_current_mesh(mesh)

    cfg = _cfg(["student.arch=vit_test4", "parallel.pipe=2"])
    pipe_model = build_backbone(cfg, teacher=True)
    assert pipe_model.pipeline_stages == 2 and pipe_model.n_blocks == 4

    x = jax.random.normal(jax.random.key(2), (4, 16, 16, 3), jnp.float32)
    pipe_params = nn.meta.unbox(pipe_model.init(jax.random.key(0), x))["params"]

    cfg_seq = _cfg(["student.arch=vit_test4"])
    seq_model = build_backbone(cfg_seq, teacher=True)
    seq_params = unstack_pipeline_params(pipe_params, n_stages=2, n_blocks=4)
    assert "blocks_3" in seq_params and "pipeline" not in seq_params

    kw = dict(n=[1, 3], return_class_token=True,
              method=DinoVisionTransformer.get_intermediate_layers)
    with mesh:
        outs_pipe = jax.jit(
            lambda p, x: pipe_model.apply({"params": p}, x, **kw)
        )(pipe_params, x)
    outs_seq = seq_model.apply({"params": seq_params}, x, **kw)
    assert len(outs_pipe) == len(outs_seq) == 2
    tol = 2e-5
    for (pp, cp), (ps, cs) in zip(outs_pipe, outs_seq):
        np.testing.assert_allclose(np.asarray(pp), np.asarray(ps),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(cp), np.asarray(cs),
                                   rtol=tol, atol=tol)


def test_pipeline_param_relayout_roundtrip(eight_devices):
    """stack_params_for_pipeline is the exact inverse of
    unstack_pipeline_params (warm-start path for pipelined runs)."""
    import flax.linen as nn

    from dinov3_tpu.parallel.pipeline import (
        stack_params_for_pipeline,
        unstack_pipeline_params,
    )

    mesh = build_mesh(MeshSpec(data=-1, pipe=2), devices=eight_devices)
    set_current_mesh(mesh)
    cfg = _cfg(["student.arch=vit_test4", "parallel.pipe=2"])
    model = build_backbone(cfg, teacher=True)
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    params = nn.meta.unbox(model.init(jax.random.key(0), x))["params"]

    seq = unstack_pipeline_params(params, n_stages=2, n_blocks=4)
    back = stack_params_for_pipeline(seq, n_stages=2, n_blocks=4)
    orig_stack = params["pipeline"]["tick"]["stages"]["blocks"]["block"]
    back_stack = back["pipeline"]["tick"]["stages"]["blocks"]["block"]
    same = jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), orig_stack, back_stack
    )
    assert all(jax.tree.leaves(same))
