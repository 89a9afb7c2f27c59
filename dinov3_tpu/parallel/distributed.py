"""Multi-host process bootstrap.

(reference: dinov3_jax/distributed/__init__.py:12-21 hardcoded
``get_rank() == 0`` / single host — the multi-host path never existed.
Here ``jax.distributed.initialize`` is called per host before any device
access; afterwards ``jax.devices()`` is the global device set and the mesh
in parallel/mesh.py spans all hosts, with collectives riding ICI within a
slice and DCN across slices.)
"""

from __future__ import annotations

import logging
import os

import jax

logger = logging.getLogger("dinov3")

_initialized = False


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialize JAX's multi-host runtime when — and only when — the
    job ASKS for it: a coordinator address given as an argument or in
    ``JAX_COORDINATOR_ADDRESS`` (with ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``). Without one this is a single-process run and
    nothing is initialized or looked up: one process drives every chip
    of its host, and a sealed single host (no metadata server, no
    network) must never wait on auto-detection. An initialization that
    was asked for and fails RAISES — a pod job that silently continues
    as N independent single-host runs trains N wrong models.
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if coordinator_address is None:
        logger.info("single-process run; skipping jax.distributed.initialize")
        return
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    env_pid = os.environ.get("JAX_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info(
        "distributed: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_main_process() -> bool:
    return jax.process_index() == 0
