"""The plain reference against the program's ViT in float32 at test width,
and its controls: the lower precisions have to read several times what
bfloat16 reads."""

from __future__ import annotations

import numpy as np
import pytest

ARCH = {"patch_size": 16, "num_heads": 2, "rope_base": 100.0}


@pytest.fixture(scope="module")
def case():
    import jax
    import jax.numpy as jnp

    import output_check
    import weights
    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.models import build_backbone

    def build(scan: bool, compute: str = "fp32"):
        cfg = get_default_config()
        apply_dot_overrides(cfg, [
            "student.arch=vit_test", "student.patch_size=16",
            f"train.scan_layers={'true' if scan else 'false'}",
            f"compute_precision.compute_dtype={compute}"])
        model = build_backbone(cfg, teacher=True)
        x = jnp.asarray(np.random.default_rng(3).standard_normal((3, 64, 96, 3)),
                        jnp.float32)
        tree = weights.fill(output_check.abstract_backbone(model, x), 2**31 + 5,
                            jnp.float32)
        out = model.apply({"params": tree}, x)
        got = (np.asarray(out["x_norm_clstoken"], np.float32),
               np.asarray(out["x_norm_patchtokens"], np.float32).mean(axis=1))
        return tree, x, got

    return build


@pytest.mark.parametrize("scan", [False, True])
def test_reference_matches_the_program_in_float32(case, scan):
    import output_check

    tree, x, got = case(scan)
    want = output_check.reference_features(tree, [x], ARCH)
    g = output_check.gaps([got], want)
    # float32 both sides; the program keeps its attention probabilities in
    # bfloat16 whatever the compute type, which the CLS token feels most
    assert g["cls_rel_l2"] < 2e-3 and g["pooled_rel_l2"] < 2e-4, g


def test_controls_read_several_times_bfloat16(case):
    """At the cells' own size the readings are in the configurations'
    files; here, at test width, the order has to hold: program in bf16 <
    int8 < fp8, and fp8 at least three times the program."""
    import output_check

    tree, x, got = case(False, "bf16")
    want = output_check.reference_features(tree, [x], ARCH)
    program = output_check.gaps([got], want)
    low = {p: output_check.gaps(output_check.reference_features(tree, [x], ARCH, p),
                                want) for p in ("bf16", "int8", "fp8")}
    for k in ("cls_rel_l2", "pooled_rel_l2"):
        assert low["bf16"][k] < low["int8"][k] < low["fp8"][k], (k, low)
        assert low["fp8"][k] > 3 * program[k], (k, program, low)


def test_weights_come_from_the_seed(case):
    import jax
    import jax.numpy as jnp

    import weights

    abstract = {"a": {"kernel": jax.ShapeDtypeStruct((4, 4), jnp.float32),
                      "gamma": jax.ShapeDtypeStruct((4,), jnp.float32)}}
    one = weights.fill(abstract, 2**31 + 9, jnp.bfloat16)
    two = weights.fill(abstract, 2**31 + 9, jnp.bfloat16)
    other = weights.fill(abstract, 2**31 + 10, jnp.bfloat16)
    assert one["a"]["kernel"].dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(one["a"]["kernel"], np.float32),
                          np.asarray(two["a"]["kernel"], np.float32))
    assert not np.array_equal(np.asarray(one["a"]["kernel"], np.float32),
                              np.asarray(other["a"]["kernel"], np.float32))
    assert np.all(np.asarray(one["a"]["gamma"], np.float32) == 1.0)
