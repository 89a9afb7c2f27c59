"""ZeRO-3 weight-streaming accounting: the committed evidence behind
COST_Z3_r12.json and MEM_r12.json (PR-1..6 discipline — measure the
exact shipped code paths).

Three instruments, all on the 8-simulated-device CPU mesh:

- **Per-device state accounting (ViT-L, compile-only)**: both arms are
  built ABSTRACTLY (``build_train_setup(init_state=False)``) and
  per-device bytes come from the ``NamedSharding``s the setup assigned
  (``telemetry.memory.layout_split`` — replicated leaves count fully
  per device, sharded leaves 1/dp). Control strips ONLY the engine
  (``parallel.zero3=false`` — the pre-PR-7 default: replicated fp32
  masters + EMA teacher, ZeRO-1 flat adam moments); treatment is the
  zero3 arm (everything weight-shaped born sharded). Both arms
  ``train.scan_layers=true`` so the comparison isolates the layout, not
  the stack form. The ``layout_split`` replicated-fraction pin keeps
  the zero3 arm from silently reporting the replicated footprint.
- **Collective/weight-stream census**: the exact compiled default step
  of each arm (the telemetry step, as benched) through
  ``utils.hlo_collective_census`` — per-class ops/bytes, the named-scope
  attribution (every zero3 gather lands in ``zero3_stream``/
  ``zero3_gather``, never "unattributed"), and the in-loop all-gather
  story. The double-buffered prefetch schedule is censused on the
  EXPLICIT twin (``models/streaming.streamed_block_scan``, the
  ``make_bucketed_update_schedule`` convention): a ViT-L block stack in
  the bf16 stream layout, compiled standalone, whose in-loop gathers
  are ``zero3_prefetch``-scoped — issued one full block of compute
  ahead of their consumer. The twin takes the bf16 stack as a program
  INPUT so the censused gather bytes are the stream dtype's by
  construction (inside the full step this backend's partitioner
  re-places the master->bf16 convert across the gather and moves fp32
  bytes; the TPU collective pipeline narrows them — the phW on-chip
  records carry the truth).
- **ViT-7B unlock dryrun**: ``configs/train/vit7b16_zero3.yaml``
  compiles end-to-end on the same 8 simulated devices
  (``build_train_setup(init_state=False)`` -> lower -> compile), with
  the per-device state accounting committed next to it. This is the
  deliverable of ROADMAP item 1: the state that CANNOT exist replicated
  (6.7B fp32 masters x2 = ~54 GB/device before moments) fits as
  ~1/8 shards.

Writes COST_Z3_r12.json (argv[1], default ./COST_Z3_r12.json) and
MEM_r12.json (argv[2], default ./MEM_r12.json); prints the COST record
to stdout.

Usage: JAX_PLATFORMS=cpu python scripts/cost_zero3.py \
           [cost_out] [mem_out] [--skip-7b]
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DP = 8
os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += f" --xla_force_host_platform_device_count={DP}"

COST_OUT = sys.argv[1] if len(sys.argv) > 1 and not sys.argv[1].startswith(
    "--") else "COST_Z3_r12.json"
MEM_OUT = sys.argv[2] if len(sys.argv) > 2 and not sys.argv[2].startswith(
    "--") else "MEM_r12.json"
SKIP_7B = "--skip-7b" in sys.argv


def _log(msg):
    print(f"[cost_zero3] {msg}", file=sys.stderr, flush=True)


def _bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tree_split(tree, shardings):
    from dinov3_tpu.telemetry.memory import layout_split

    return layout_split(tree, shardings)


def build_arm(zero3: bool):
    """ViT-L dp=8 abstract setup + compiled default (telemetry) step."""
    import jax

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.telemetry.ring import make_ring
    from dinov3_tpu.train import build_train_setup

    bench = _bench()
    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0) + [
        "train.scan_layers=true",
        f"parallel.zero3={'true' if zero3 else 'false'}",
    ])
    B = 12 * DP
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch_np.items()}
    setup = build_train_setup(cfg, batch_np, init_state=False)
    assert setup.zero3 == zero3

    s = setup.state
    sh = setup.state_shardings
    split = {
        "params_student": tree_split(s.params["student"],
                                     sh.params["student"]),
        "params_teacher": tree_split(s.params["teacher"],
                                     sh.params["teacher"]),
        "opt_state": tree_split(s.opt_state, sh.opt_state),
        "center_state": tree_split(s.center_state, sh.center_state),
    }

    plan = setup.telemetry()
    ring_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        make_ring(len(plan.metric_names), plan.ring_len))
    scalars = {
        "teacher_temp": jax.ShapeDtypeStruct((), jax.numpy.float32),
        "momentum": jax.ShapeDtypeStruct((), jax.numpy.float32),
    }
    _log(f"compiling ViT-L dp={DP} default step (zero3={zero3})...")
    compiled = plan.step_fn.lower(
        s, ring_abs, batch, scalars, jax.random.key(0)).compile()
    return setup, split, compiled, batch, ring_abs


def twin_prefetch_census():
    """The explicit double-buffered stream twin at ViT-L block shapes:
    bf16 stack as a program input, compiled standalone; returns its
    collective census + per-pass stream-byte ledger."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dinov3_tpu.configs import apply_dot_overrides, get_default_config
    from dinov3_tpu.models import build_backbone
    from dinov3_tpu.models.streaming import (
        cast_stream_leaves,
        make_block_apply,
        streamed_block_scan,
    )
    from dinov3_tpu.ops.block import SelfAttentionBlock
    from dinov3_tpu.parallel.context import set_current_mesh
    from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
    from dinov3_tpu.parallel.sharding import zero3_leaf_spec
    from dinov3_tpu.utils import hlo_collective_census

    bench = _bench()
    cfg = get_default_config()
    apply_dot_overrides(cfg, bench.build_step_overrides("vit_large", 0))
    mesh = build_mesh(MeshSpec(data=DP))
    set_current_mesh(mesh)
    model = build_backbone(cfg)
    kwargs = model._block_kwargs()
    kwargs["drop_path_rate"] = 0.0  # pass-granularity eval-mode program
    L = model.n_blocks
    D = model.embed_dim
    N = 197  # 196 patch tokens + CLS at 224px/p16

    block = SelfAttentionBlock(**kwargs)
    x_abs = jax.ShapeDtypeStruct((2 * DP, N, D), jnp.bfloat16)
    one_block = jax.eval_shape(
        lambda r: block.init(r, jnp.zeros((1, N, D), jnp.bfloat16)),
        jax.random.key(0))["params"]
    import flax.linen as nn

    one_block = nn.meta.unbox(one_block)
    stack = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((L,) + tuple(p.shape), p.dtype),
        one_block)
    stack = cast_stream_leaves(stack, jnp.bfloat16)

    def stack_sharding(p):
        spec = zero3_leaf_spec(p.shape, ("layers",) + (None,) *
                               (len(p.shape) - 1), mesh)
        return NamedSharding(mesh, spec if spec is not None else P())

    stack_sh = jax.tree.map(stack_sharding, stack)
    rope = None  # block math w/o rope: the stream bytes are the subject
    apply_fn = make_block_apply(kwargs, rope=rope)

    def run(stack_params, x):
        return streamed_block_scan(apply_fn, stack_params, x, L, mesh)

    with mesh:
        _log("compiling explicit double-buffered stream twin...")
        compiled = jax.jit(
            run, in_shardings=(stack_sh, NamedSharding(mesh, P("data"))),
        ).lower(stack, x_abs).compile()
    census = hlo_collective_census(compiled.as_text())

    stream_bytes = sum(
        math.prod(p.shape) * p.dtype.itemsize
        for p in jax.tree.leaves(stack))
    n_leaves = len(jax.tree.leaves(stack))
    return {
        "collective_census": census,
        "stack_stream_bytes_per_fwd_pass": stream_bytes,
        "stack_param_leaves": n_leaves,
        "n_blocks": L,
        "note": (
            "explicit twin (models/streaming.py): bf16 stack is a "
            "program input sharded per zero3_leaf_spec; every in-loop "
            "all-gather is zero3_prefetch-scoped = issued one block of "
            "compute ahead of its consumer; the priming gather of "
            "block 0 is zero3_gather-scoped outside the loop. "
            "stack_stream_bytes_per_fwd_pass = full bf16 stack moved "
            "once per direction (the engine re-gathers in backward "
            "under remat)."
        ),
    }


def vit7b_dryrun():
    """Compile the ViT-7B zero3 recipe end-to-end on 8 simulated
    devices from the abstract state; commit the per-device accounting."""
    import jax

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(os.path.join(repo, "configs/train/vit7b16_zero3.yaml"))
    B = int(cfg.train.batch_size_per_device) * DP
    batch_np = make_synthetic_batch(cfg, B, seed=0)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch_np.items()}
    _log("building ViT-7B abstract setup (zero3)...")
    setup = build_train_setup(cfg, batch_np, init_state=False)
    assert setup.zero3

    s, sh = setup.state, setup.state_shardings
    split = {
        "params_student": tree_split(s.params["student"],
                                     sh.params["student"]),
        "params_teacher": tree_split(s.params["teacher"],
                                     sh.params["teacher"]),
        "opt_state": tree_split(s.opt_state, sh.opt_state),
    }
    # the pin: a "zero3" 7B artifact whose masters report replicated is
    # an accounting bug, not a result
    for k in ("params_student", "params_teacher"):
        frac = split[k]["replicated_fraction"]
        assert frac < 0.05, f"7B {k} replicated_fraction={frac:.3f}"

    scalars = {
        "teacher_temp": jax.ShapeDtypeStruct((), jax.numpy.float32),
        "momentum": jax.ShapeDtypeStruct((), jax.numpy.float32),
    }
    _log("compiling ViT-7B dp=8 step (compile-only dryrun; this is the "
         "unlock deliverable)...")
    compiled = setup.step_fn.lower(
        s, batch, scalars, jax.random.key(0)).compile()
    mem_an = None
    try:
        an = compiled.memory_analysis()
        if an is not None:
            mem_an = {
                k: int(getattr(an, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes")
                if hasattr(an, k)
            } or None
    except Exception as e:  # noqa: BLE001 - backend without the analysis
        mem_an = {"error": str(e)[:200]}
    n_params = sum(
        math.prod(l.shape)
        for l in jax.tree.leaves(s.params["student"]))
    return {
        "config": "configs/train/vit7b16_zero3.yaml",
        "arch": "vit_7b",
        "dp": DP,
        "n_student_params": n_params,
        "compiled": True,
        "per_device_state": split,
        "state_bytes_per_device_total": sum(
            v["per_device_bytes"] for v in split.values()),
        "replicated_equivalent_bytes_per_device": sum(
            v["full_bytes"] for v in split.values()),
        "xla_memory_analysis": mem_an,
    }


def main():
    from dinov3_tpu.utils import hlo_collective_census

    arms = {}
    mem_arms = {}
    for name, z in (("zero3", True), ("replicated", False)):
        setup, split, compiled, batch, ring_abs = build_arm(z)
        text = compiled.as_text()
        census = hlo_collective_census(text)
        masters = (split["params_student"]["per_device_bytes"]
                   + split["params_teacher"]["per_device_bytes"])
        arms[name] = {
            "per_device_state": split,
            "master_bytes_per_device": masters,
            "state_bytes_per_device_total": sum(
                v["per_device_bytes"] for v in split.values()),
            "collective_census": census,
        }
        mem_an = None
        try:
            an = compiled.memory_analysis()
            if an is not None:
                mem_an = {
                    k: int(getattr(an, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes", "temp_size_in_bytes",
                              "alias_size_in_bytes")
                    if hasattr(an, k)
                } or None
        except Exception as e:  # noqa: BLE001
            mem_an = {"error": str(e)[:200]}
        mem_arms[name] = {
            "bytes_in_use_per_device": {
                **{k: v["per_device_bytes"] for k, v in split.items()},
                "state_total": sum(
                    v["per_device_bytes"] for v in split.values()),
            },
            "replicated_fraction": {
                k: round(v["replicated_fraction"], 4)
                for k, v in split.items()},
            "xla_memory_analysis": mem_an,
        }
        del setup, compiled

    # the zero3 arm pin: masters must actually be sharded in the artifact
    for k in ("params_student", "params_teacher"):
        frac = arms["zero3"]["per_device_state"][k]["replicated_fraction"]
        assert frac < 0.05, f"zero3 {k} replicated_fraction={frac:.3f}"
    z3 = arms["zero3"]
    rep = arms["replicated"]
    # every all-gather of the zero3 step attributed (by class always;
    # the scope table must carry the engine categories)
    assert z3["collective_census"]["unattributed"] == 0
    master_red = 100.0 * (1 - z3["master_bytes_per_device"]
                          / rep["master_bytes_per_device"])

    twin = twin_prefetch_census()
    pf = twin["collective_census"]["prefetch_overlap"]
    assert pf["prefetch_scoped_ops"] >= twin["stack_param_leaves"], (
        "twin prefetch gathers missing from census", pf)

    rec = {
        "arch": "vit_large",
        "dp": DP,
        "per_chip_batch": 12,
        "arms": arms,
        "master_weight_state_reduction_pct": round(master_red, 1),
        "state_total_reduction_pct": round(
            100.0 * (1 - z3["state_bytes_per_device_total"]
                     / rep["state_bytes_per_device_total"]), 1),
        "prefetch_twin": twin,
        "source": "shardings+hlo_census (8 simulated CPU devices, "
                  "compile-only; PR-1..6 pass-granularity discipline)",
    }
    if not SKIP_7B:
        rec["vit7b_unlock"] = vit7b_dryrun()

    with open(COST_OUT, "w") as f:
        json.dump(rec, f, indent=1)
    _log(f"wrote {COST_OUT}")

    mem = {
        "arch": "vit_large",
        "dp": DP,
        "per_chip_batch": 12,
        "arms": mem_arms,
        "source": "shardings+memory_analysis",
        "note": (
            "compile-only dryrun on 8 simulated CPU devices "
            "(build_train_setup(init_state=False)), both arms "
            "train.scan_layers=true: bytes-in-use from the "
            "NamedShardings the setup assigned. The replicated arm is "
            "the MEM_r11 before-picture (student+teacher fp32 masters "
            "full-size per device, ZeRO-1 flat moments 1/dp); the "
            "zero3 arm is the after-picture — masters, EMA teacher and "
            "moments all ~1/dp per device, replicated_fraction pinned "
            "near 0 so this artifact cannot silently report the "
            "replicated footprint (telemetry/memory.layout_split). "
            "XLA:CPU temp_size stays an UNSCHEDULED upper bound; "
            "on-chip peaks come from device.memory_stats() via the phW "
            "bench records."
        ),
    }
    if "vit7b_unlock" in rec:
        mem["vit7b"] = rec["vit7b_unlock"]["per_device_state"]
    with open(MEM_OUT, "w") as f:
        json.dump(mem, f, indent=1)
    _log(f"wrote {MEM_OUT}")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
