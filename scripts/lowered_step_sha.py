"""sha256 of the lowered step of benchmark configurations: the yardstick
"the step program did not change" (PERF.md section 6, PRs 29-48).

    JAX_PLATFORMS=cpu python scripts/lowered_step_sha.py [<config> ...]

For each ``benchmark/configs/<config>.json`` (default: every one with a
recipe): its recipe and overrides, ``build_train_setup`` on one CPU device
with an abstract state, the telemetry step ``do_train`` runs, lowered to
StableHLO text (no locations), hashed. Run it in two checkouts and compare;
the numbers depend on the installation and on nothing the chip does.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def lowered_sha(name: str) -> str:
    import jax
    import jax.numpy as jnp

    from dinov3_tpu.configs import load_config
    from dinov3_tpu.data import make_synthetic_batch
    from dinov3_tpu.train import build_train_setup

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        conf = json.load(f)
    cfg = load_config(os.path.join(ROOT, conf["recipe"]), overrides=conf["overrides"])
    batch = {k: jnp.asarray(v) for k, v in make_synthetic_batch(
        cfg, int(cfg.train.batch_size_per_device), seed=0).items()}
    setup = build_train_setup(cfg, batch, devices=jax.devices()[:1],
                              init_state=False)
    plan = setup.telemetry()
    args = (setup.state, jax.eval_shape(plan.init_ring), batch,
            setup.scalars(0), jax.random.key(0))
    with setup.mesh:
        text = plan.step_fn.lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def main(argv: list) -> int:
    names = argv or sorted(
        os.path.basename(p)[:-5] for p in glob.glob(
            os.path.join(ROOT, "benchmark", "configs", "*.json"))
        if "recipe" in json.load(open(p)))
    for name in names:
        print(name, lowered_sha(name), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
