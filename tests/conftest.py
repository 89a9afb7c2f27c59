"""Test harness: 8 virtual CPU devices so every collective / sharding test
runs a real multi-device mesh without hardware (SURVEY.md §4 implication (a))."""

import os

# the test suite always runs on the virtual 8-device CPU mesh, whatever
# the ambient environment selects
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU's concurrency-optimised scheduler lets the 8 virtual devices
# of one process issue independent collectives in different orders; on
# the accumulation step (a scan with collective-permutes next to
# all-gathers on the dp x fsdp mesh) two devices then wait in one
# collective while six wait in another, and the process aborts after the
# 40 s rendezvous timeout ("Expected 8 threads to join the rendezvous,
# but only 6 of them arrived") — measured about every other run of
# tests/test_unified_buckets.py -k accum under jax 0.9.0, 0 of 8 with
# the scheduler off. A test-harness setting: the chip does not run this
# backend.
if "xla_cpu_enable_concurrency_optimized_scheduler" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_enable_concurrency_optimized_scheduler=false").strip()

import jax  # noqa: E402

# jax may already be imported by pytest plugins (jaxtyping/typeguard), in
# which case the env vars above were captured too late — but the backend is
# not initialized until first use, so config updates still take effect.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_default_matmul_precision", "highest")

# No persistent compilation cache in the suite: a fresh checkout always
# starts cold, and on a cold directory the suite pays to serialise
# every entry it writes without ever reading one back. Measured under jax 0.9.0 with 6 workers: the whole
# fast set takes 536 s with the cache off; with a cold cache on it had
# reached 90% after 780 s. Each worker's in-memory jit cache still
# de-duplicates within a file. (Entry points place their own cache by
# utils.configure_compile_cache; that rule is tested in
# tests/test_chip_smoke.py.)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


def ulps_of_scale(got, want) -> float:
    """Largest |got - want| of a float32 leaf, in units of the float32
    spacing at the leaf's own largest magnitude. A float32 reduction's
    error is bounded relative to the magnitude of what it sums, not to
    each (possibly cancelling, near-zero) result element, so this — not
    an element-wise ulp count — is the unit in which two correct
    summation orders differ by "the last digit"."""
    import numpy as np

    a = np.asarray(got, np.float32)
    b = np.asarray(want, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    if not a.size:
        return 0.0
    scale = np.float32(max(np.abs(b).max(), np.finfo(np.float32).tiny))
    return float(np.abs(a - b).max() / np.spacing(scale))


def assert_within_ulps(got, want, max_ulps: float, what: str = "") -> None:
    """``got`` equals ``want`` to within ``max_ulps`` last-digit units of
    the leaf's scale (``ulps_of_scale``); 0 is bitwise equality. For
    pins between two arms that were bitwise under an older compiler and
    now differ in the last digit (ROADMAP D1): the measured difference
    is stated at the call site and pinned, not an open tolerance."""
    worst = ulps_of_scale(got, want)
    assert worst <= max_ulps, (
        f"{what}: {worst:.2f} ulps of scale apart (pin {max_ulps})")


@pytest.fixture(scope="module", autouse=True)
def _no_mesh_from_the_file_before():
    """``build_train_setup`` leaves its mesh as the process's current one
    (parallel/context.py). A worker runs whole files one after another
    (``--dist loadfile``), in an order that shifts with every file added:
    without this, a file that expects no mesh (tests/test_fused_norm.py)
    passes or fails by which file its worker ran before it."""
    from dinov3_tpu.parallel.context import set_current_mesh

    set_current_mesh(None)
    yield


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return jax.random.key(0)
