"""Compile a pretrain configuration's step for a described v5e chip (no
chip time) at several per-chip batches and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmark/tools/size_batch.py <config> 32 64 96 128

Run by hand when a configuration is sized; the number chosen and this
tool's output go into the configuration's file under ``sizing``.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv: list) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.telemetry.ring import make_ring
    from dinov3_tpu.train import build_train_setup

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "benchmark", "configs", argv[0] + ".json")) as f:
        conf = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[:1]
    for b in (int(x) for x in argv[1:]):
        overrides = [o for o in conf["overrides"]
                     if not o.startswith("train.batch_size_per_device=")]
        cfg = load_config(os.path.join(ROOT, conf["recipe"]), overrides=[
            *overrides, f"train.batch_size_per_device={b}"])
        batch = make_synthetic_batch(cfg, b, seed=0)
        setup = build_train_setup(cfg, batch, devices=dev, init_state=False)
        plan = setup.telemetry()
        one = SingleDeviceSharding(dev[0])

        def abstract(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one), tree)

        ring = jax.eval_shape(
            lambda: make_ring(len(plan.metric_names), plan.ring_len))
        args = (abstract(setup.state), abstract(ring),
                abstract({k: jnp.asarray(v) for k, v in batch.items()}),
                abstract(setup.scalars(0)),
                jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one))
        t0 = time.perf_counter()
        m = plan.step_fn.lower(*args).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(json.dumps({
            "config": argv[0], "batch_per_chip": b,
            "argument_gib": round(m.argument_size_in_bytes / 2**30, 3),
            "output_gib": round(m.output_size_in_bytes / 2**30, 3),
            "alias_gib": round(m.alias_size_in_bytes / 2**30, 3),
            "temp_gib": round(m.temp_size_in_bytes / 2**30, 3),
            "total_gib": round(total / 2**30, 3),
            "compile_s": round(time.perf_counter() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
