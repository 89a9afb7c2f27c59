"""Low-precision training arms (train.low_precision: ops/lowp.py +
train/setup.py wiring + the lowp flax collection through the block
stack) vs the bf16 default.

The fp8/int8 arms quantize the attn/mlp block matmul KERNELS
per-tensor with delayed scaling (amax-history rings in the train
state, advanced after the optimizer/EMA update) and ride the ZeRO-3
in-loop weight stream with 1-byte codes; masters, Adam moments,
norms/biases and the EMA teacher storage stay untouched. These tests
pin:

- the delayed-scaling state math (symmetric scale/quantize, history
  ring init/roll, the scale-site remap of Dense kernels);
- the bf16 default arm as a BITWISE no-op: an explicit
  ``arm=bf16`` config (with a non-default ring length it must ignore)
  produces the identical program — losses and post-step params equal
  to the config without any low_precision overrides;
- multi-step loss trajectories tracking bf16 within the documented
  tolerance (fp8 on the dp x fsdp zero3 mesh; int8 dp-only under
  ``slow`` — int8 also executes in the committed COST_LP_r21.json run
  and CI's ``cost_lowp.py --smoke``), with live amax rings and the
  setup drift probe under ``train.low_precision.divergence_tol``;
- the streamed-gather census: identical ``zero3_stream`` collective
  counts across arms, >= 1.8x fewer streamed bytes on the quantized
  arm, zero unattributed collectives, and the ``lowp_dequant``
  epilogue stamped into the quantized program only;
- cross-arm checkpoints: a bf16 checkpoint restored into an fp8 run
  reseeds fresh rings from the RESTORED masters; fp8 -> fp8 restores
  rings bitwise; an fp8 checkpoint restores into a bf16 run with the
  rings ignored;
- the ``warn_lowp_divergence`` guardrail (fire/silent), the arm
  conflict raises (fp8_enabled / moe / pipe>1 / convnext / typo'd
  arm), the serve-quant numerics staying bitwise after delegating to
  ops/lowp.py, and the committed COST_LP_r21.json acceptance numbers.
"""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from dinov3_tpu.configs import apply_dot_overrides, get_default_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOL = [
    "student.arch=vit_test", "student.patch_size=4",
    "crops.global_crops_size=16", "crops.local_crops_size=8",
    "crops.local_crops_number=2", "train.batch_size_per_device=2",
    "optim.scaling_rule=none", "train.scan_layers=true",
    "dino.head_n_prototypes=64", "dino.head_hidden_dim=32",
    "dino.head_bottleneck_dim=16",
    "ibot.head_n_prototypes=64", "ibot.head_hidden_dim=32",
    "ibot.head_bottleneck_dim=16",
    "train.OFFICIAL_EPOCH_LENGTH=4", "optim.epochs=4",
    "optim.warmup_epochs=1",
]
MESH = ["parallel.data=2", "parallel.fsdp=4", "parallel.zero3=true"]
# documented per-step relative loss-trajectory band of the quantized
# arms vs bf16 at the SMOL scale (COST_LP_r21.json measures 0.6%/1.4%
# at 8 steps; 5% is the alerting band)
LOSS_RTOL = 0.05


def _setup(extra, batch_size, devices):
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    cfg = get_default_config()
    apply_dot_overrides(cfg, SMOL + list(extra))
    batch = {k: jnp.asarray(v) for k, v in
             make_synthetic_batch(cfg, batch_size, seed=0).items()}
    return build_train_setup(cfg, batch, devices=devices), batch


def _flat(tree):
    return jtu.tree_flatten_with_path(tree)[0]


def assert_trees_bitwise(a, b, what, limit=None):
    fa, fb = _flat(a), _flat(b)
    assert len(fa) == len(fb)
    for (pa, la), (_, lb) in (zip(fa, fb) if limit is None
                              else zip(fa[:limit], fb[:limit])):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}: {jtu.keystr(pa)} differs")


def _run(setup, batch, n_steps):
    from dinov3_tpu.train import put_batch

    d = put_batch(batch, setup.batch_shardings)
    state, losses = setup.state, []
    for i in range(n_steps):
        state, m = setup.step_fn(state, d, setup.scalars(i),
                                 jax.random.key(0))
        losses.append(float(m["total_loss"]))
    return state, losses


@pytest.fixture(scope="module")
def arms(eight_devices):
    """One setup + 2 executed steps per precision arm on the dp x fsdp
    zero3 mesh — shared by the trajectory / census / checkpoint tests.
    The fast set runs the bf16 control + the fp8 treatment only (each
    arm is a full setup + compile, real wall-clock on this suite); the
    int8 arm executes in the slow dp-only test below, in the committed
    COST_LP_r21.json acceptance, and in CI's `cost_lowp.py --smoke`."""
    out = {}
    for arm, extra in (("bf16", []),
                       ("fp8", ["train.low_precision.arm=fp8"])):
        setup, batch = _setup(MESH + extra, 8, eight_devices)
        final, losses = _run(setup, batch, 2)
        out[arm] = {"setup": setup, "batch": batch,
                    "final": final, "losses": losses}
    return out


# ---------------- delayed-scaling state math ----------------

def test_symmetric_scale_and_quantize_math():
    from dinov3_tpu.ops.lowp import (
        qspec,
        scale_from_history,
        symmetric_quantize,
        symmetric_scale,
    )

    # zero amax -> scale 1.0 (exact divide, dequant returns exact zeros)
    assert float(symmetric_scale(jnp.float32(0.0), 127.0)) == 1.0
    assert float(symmetric_scale(jnp.float32(254.0), 127.0)) == 2.0
    # fp8 e4m3 qmax is 448, int8 is 127, and their accumulators
    assert qspec("fp8").qmax == 448.0
    assert qspec("fp8").acc_dtype == jnp.float32
    assert qspec("int8").qmax == 127.0
    assert qspec("int8").acc_dtype == jnp.int32
    # int8 codes: round-half-to-even then clip to the symmetric range
    q = symmetric_quantize(
        jnp.float32([2.5, -2.5, 3.5, 300.0]), jnp.float32(1.0), 127,
        jnp.int8)
    assert q.dtype == jnp.int8
    assert q.tolist() == [2, -2, 4, 127]
    # fp8 codes: no integer rounding, straight cast into e4m3
    qf = symmetric_quantize(
        jnp.float32([1.0, -448.0]), jnp.float32(1.0), 448.0,
        jnp.float8_e4m3fn)
    assert qf.dtype == jnp.float8_e4m3fn
    assert qf.astype(jnp.float32).tolist() == [1.0, -448.0]
    # delayed scale: margin * max(history) / qmax
    hist = jnp.float32([1.0, 254.0, 2.0])
    assert float(scale_from_history(hist, 127.0, 1.0)) == 2.0
    assert float(scale_from_history(hist, 127.0, 2.0)) == 4.0
    # all-zero history degrades to the safe 1.0
    assert float(scale_from_history(jnp.zeros(4), 127.0, 1.0)) == 1.0


def test_kernel_path_and_scale_site():
    from dinov3_tpu.ops.lowp import lowp_kernel_path, lowp_scale_site

    def path(*keys):
        return tuple(jtu.DictKey(k) for k in keys)

    # attn/mlp matmul kernels quantize; their biases ride the bf16
    # stream; norms and the router were never castable
    assert lowp_kernel_path(path("blocks", "attn", "qkv_kernel"))
    assert lowp_kernel_path(path("blocks", "mlp", "fc1", "kernel"))
    assert not lowp_kernel_path(path("blocks", "attn", "qkv_bias"))
    assert not lowp_kernel_path(path("blocks", "norm1", "scale"))
    assert not lowp_kernel_path(path("blocks", "mlp", "router", "kernel"))
    assert not lowp_kernel_path(path("patch_embed", "kernel"))
    # Dense kernels fold into the parent module's collection slot;
    # direct attn kernels keep their name in place
    assert lowp_scale_site(path("blocks", "mlp", "fc1", "kernel")) == (
        ("blocks", "mlp"), "fc1_kernel")
    assert lowp_scale_site(path("blocks", "attn", "qkv_kernel")) == (
        ("blocks", "attn"), "qkv_kernel")


def test_history_init_and_ring_roll():
    from dinov3_tpu.ops.lowp import (
        lowp_amax_tree,
        lowp_history_init,
        lowp_history_step,
    )

    params = {
        # scanned stack: [L, in, out] kernels reduce to per-layer [L]
        "blocks": {"attn": {"qkv_kernel": jnp.float32(
            np.arange(2 * 3 * 6).reshape(2, 3, 6) - 10.0)}},
        # unrolled kernel reduces to a scalar
        "head": {"mlp": {"fc1": {"kernel": jnp.float32([[1.0, -7.0]])}}},
        # non-kernel leaves never enter the tree
        "norm": {"scale": jnp.ones((4,))},
    }
    amax = lowp_amax_tree(params)
    assert amax["blocks"]["attn"]["qkv_kernel"].shape == (2,)
    assert float(amax["head"]["mlp"]["fc1_kernel"]) == 7.0
    assert "norm" not in amax
    # init fills EVERY slot with the current amax (not zeros)
    hist = lowp_history_init(params, 4)
    h = hist["blocks"]["attn"]["qkv_kernel"]
    assert h.shape == (2, 4) and h.dtype == jnp.float32
    assert np.array_equal(np.asarray(h), np.asarray(
        jnp.broadcast_to(amax["blocks"]["attn"]["qkv_kernel"][:, None],
                         (2, 4))))
    # the roll drops the oldest slot and appends the NEW masters' amax
    new_params = jax.tree.map(lambda x: x * 2.0, params)
    rolled = lowp_history_step(hist, new_params)
    r = np.asarray(rolled["head"]["mlp"]["fc1_kernel"])
    assert r.shape == (4,)
    assert r.tolist() == [7.0, 7.0, 7.0, 14.0]


# ---------------- the bf16 arm is bitwise inert ----------------

def test_bf16_arm_bitwise_noop(arms, eight_devices):
    """An explicit ``arm=bf16`` config — including a non-default ring
    length the bf16 arm must ignore — runs the identical program: no
    rings, no drift probe, losses and post-step params bitwise equal
    to the config without any low_precision overrides."""
    base = arms["bf16"]
    assert base["setup"].lowp_arm == "bf16"
    assert base["setup"].lowp_drift is None
    assert base["setup"].state.lowp is None
    setup, batch = _setup(
        MESH + ["train.low_precision.arm=bf16",
                "train.low_precision.amax_history_len=4"],
        8, eight_devices)
    assert setup.state.lowp is None
    final, losses = _run(setup, batch, 2)
    assert losses == base["losses"]
    assert_trees_bitwise(final.params, base["final"].params,
                         "bf16-arm params", limit=32)


# ---------------- quantized trajectories + state ----------------

def test_lowp_trajectories_dp_fsdp(arms):
    """fp8 on the dp x fsdp zero3 mesh: live amax rings advanced per
    step, setup drift probe under the tolerance gate, and the loss
    trajectory inside the documented band around bf16."""
    from dinov3_tpu.ops.lowp import lowp_amax_tree

    bf16 = arms["bf16"]["losses"]
    for name in ("fp8",):
        setup, final = arms[name]["setup"], arms[name]["final"]
        assert setup.lowp_arm == name
        # the drift probe ran at setup and sits under the gate
        assert setup.lowp_drift is not None
        assert 0.0 < setup.lowp_drift["max"] < 0.2
        # rings live in the train state and advanced with the masters:
        # the newest slot is the CURRENT (post-update) masters' amax
        assert final.lowp is not None
        for k in ("student", "teacher"):
            want = lowp_amax_tree(final.params[k]["backbone"])
            got_last = jax.tree.map(lambda h: h[..., -1], final.lowp[k])
            assert_trees_bitwise(got_last, want, f"{name} {k} ring amax")
        rel = [abs(a - b) / abs(b)
               for a, b in zip(arms[name]["losses"], bf16)]
        assert all(np.isfinite(r) for r in rel)
        assert max(rel) < LOSS_RTOL, (name, rel)


@pytest.mark.slow
def test_lowp_trajectory_dp_only(eight_devices):
    """The int8 arm on a pure-dp zero3 mesh (no fsdp axis): same
    trajectory band — the code gathers ride whatever zero3 stream the
    mesh shape produces."""
    s_b, batch = _setup(["parallel.data=8", "parallel.zero3=true"],
                        16, eight_devices)
    s_q, _ = _setup(["parallel.data=8", "parallel.zero3=true",
                     "train.low_precision.arm=int8"], 16, eight_devices)
    _, l_b = _run(s_b, batch, 2)
    _, l_q = _run(s_q, batch, 2)
    rel = [abs(a - b) / abs(b) for a, b in zip(l_q, l_b)]
    assert max(rel) < LOSS_RTOL, rel


# ---------------- streamed-gather census ----------------

def test_streamed_gather_census(arms):
    """The quantized arm's compiled step: identical zero3_stream
    collective COUNTS vs bf16, >= 1.8x fewer streamed BYTES (1-byte
    codes vs the bf16 stream), zero unattributed collectives, and the
    lowp_dequant epilogue stamped into the quantized program only."""
    from dinov3_tpu.train import put_batch
    from dinov3_tpu.utils import hlo_collective_census

    def compiled_text(rec):
        setup = rec["setup"]
        d = put_batch(rec["batch"], setup.batch_shardings)
        return setup.step_fn.lower(
            setup.state, d, setup.scalars(0), jax.random.key(0)
        ).compile().as_text()

    txt_b = compiled_text(arms["bf16"])
    txt_q = compiled_text(arms["fp8"])
    cen_b = hlo_collective_census(txt_b)
    cen_q = hlo_collective_census(txt_q)
    assert cen_b["unattributed"] == 0 and cen_q["unattributed"] == 0
    sb = cen_b["by_scope"]["zero3_stream"]
    sq = cen_q["by_scope"]["zero3_stream"]
    assert sq["ops"] == sb["ops"] > 0
    assert sb["bytes"] / sq["bytes"] >= 1.8, (sb, sq)
    # engagement: the dequant epilogue exists ONLY in the quantized arm
    assert "lowp_dequant" in txt_q
    assert "lowp_dequant" not in txt_b
    assert "lowp_amax" in txt_q


# ---------------- cross-arm checkpoints ----------------

def test_cross_arm_checkpoint(tmp_path, arms):
    """bf16 -> fp8 reseeds fresh rings from the RESTORED masters;
    fp8 -> fp8 restores the rings bitwise; fp8 -> bf16 ignores them."""
    from dinov3_tpu.checkpoint import Checkpointer
    from dinov3_tpu.ops.lowp import lowp_history_init
    from dinov3_tpu.train import put_batch

    s_b, s_q = arms["bf16"]["setup"], arms["fp8"]["setup"]
    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(1, arms["bf16"]["final"])        # no rings in this one
    ck.save(2, arms["fp8"]["final"])         # live rings in this one
    ck.wait_until_finished()

    # bf16 checkpoint into an fp8 run: masters restore bitwise and the
    # rings reseed from THOSE masters (every slot the restored amax)
    restored = ck.restore(s_q.state, 1)
    assert_trees_bitwise(restored.params, arms["bf16"]["final"].params,
                         "bf16 -> fp8 params", limit=32)
    assert restored.lowp is not None
    H = int(jax.tree.leaves(s_q.state.lowp)[0].shape[-1])
    for k in ("student", "teacher"):
        want = lowp_history_init(restored.params[k]["backbone"], H)
        assert_trees_bitwise(restored.lowp[k], want,
                             f"reseeded {k} rings")
    d = put_batch(arms["fp8"]["batch"], s_q.batch_shardings)
    st, m = s_q.step_fn(restored, d, s_q.scalars(1), jax.random.key(0))
    assert np.isfinite(float(m["total_loss"]))

    # fp8 checkpoint back into an fp8 run: rings round-trip bitwise
    same = ck.restore(s_q.state, 2)
    assert_trees_bitwise(same.lowp, arms["fp8"]["final"].lowp,
                         "fp8 -> fp8 rings")

    # fp8 checkpoint into a bf16 run: rings ignored, masters bitwise
    back = ck.restore(s_b.state, 2)
    assert back.lowp is None
    assert_trees_bitwise(back.params, arms["fp8"]["final"].params,
                         "fp8 -> bf16 params", limit=32)


# ---------------- guardrail / conflicts / registration ----------------

def test_warn_lowp_divergence_fire_and_silent():
    from dinov3_tpu.configs.config import warn_lowp_divergence

    with pytest.warns(UserWarning, match="lowp divergence axis"):
        msg = warn_lowp_divergence(0.5, tol=0.2, axis="unit test")
    assert msg is not None and "unit test" in msg
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert warn_lowp_divergence(0.01, tol=0.2) is None
    assert not caught


def test_arm_conflicts_raise(eight_devices):
    from dinov3_tpu.configs.config import lowp_cfg
    from dinov3_tpu.models import build_backbone

    # a typo'd arm must never silently train bf16
    cfg = get_default_config()
    apply_dot_overrides(cfg, ["train.low_precision.arm=fp16"])
    with pytest.raises(ValueError, match="low_precision.arm"):
        lowp_cfg(cfg)
    # the legacy fp8 hook and the lowp arms would quantize the same
    # matmuls; moe experts are not stream-castable Dense kernels; the
    # pipelined stack bypasses the per-block stream; convnext has no
    # block kernels at all
    for extra, match in (
        (["student.fp8_enabled=true"], "fp8_enabled"),
        (["student.ffn_layer=moe", "student.moe_num_experts=2"], "moe"),
        (["parallel.pipe=2"], "pipe"),
    ):
        with pytest.raises(ValueError, match=match):
            _setup(["train.low_precision.arm=fp8"] + extra, 16,
                   eight_devices)
    cfg = get_default_config()
    apply_dot_overrides(
        cfg, ["student.arch=convnext_tiny",
              "train.low_precision.arm=int8"])
    with pytest.raises(ValueError, match="ViT backbone"):
        build_backbone(cfg)


def test_serve_quant_numerics_unchanged():
    """serve/quant.py delegates its scale/round/clip math to
    ops/lowp.py — the (q, scale) pair must stay bitwise what the
    pre-refactor numpy expressions produced."""
    from dinov3_tpu.serve.quant import quantize_leaf

    w = np.random.default_rng(0).standard_normal((16, 8)).astype(
        np.float32) * 0.02
    w[:, 3] = 0.0  # a zero output channel exercises the scale-1.0 path
    leaf = quantize_leaf(w)
    amax = np.max(np.abs(w), axis=-2, keepdims=True)
    scale = np.where(amax > 0, amax / np.float32(127.0),
                     np.float32(1.0)).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    assert np.array_equal(np.asarray(leaf.q), q)
    assert np.array_equal(np.asarray(leaf.scale), scale)
    assert np.all(np.asarray(leaf.q)[:, 3] == 0)


# ---------------- committed artifact ----------------

def test_cost_lp_artifact_acceptance():
    """COST_LP_r21.json: streamed bytes down >= 1.8x at identical
    stream counts, unattributed collectives AND unattributed trace ms
    pinned 0, trajectories inside the documented band, bf16 bitwise
    control, drift probes under the gate."""
    with open(os.path.join(REPO, "COST_LP_r21.json")) as f:
        rec = json.load(f)
    assert rec["bf16_bitwise_control"] is True
    ops = rec["stream_ops"]
    assert ops["fp8"] == ops["int8"] == ops["bf16"] > 0
    for arm in ("fp8", "int8"):
        assert rec["stream_bytes"]["bf16"] / rec["stream_bytes"][arm] >= 1.8
        assert rec["trajectory_rel_max"][arm] < rec["loss_rtol_bound"]
        a = rec["arms"][arm]
        assert a["unattributed"] == 0
        assert a["anatomy"]["unattributed_collective_ms"] == 0
        assert a["lowp_dequant_scope_lines"] > 0
        assert a["drift_probe"]["max"] < rec["divergence_tol"]
    assert rec["arms"]["bf16"]["lowp_dequant_scope_lines"] == 0
    assert rec["arms"]["bf16"]["unattributed"] == 0
