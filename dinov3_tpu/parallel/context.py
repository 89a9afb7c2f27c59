"""Process-wide current-mesh registry.

Modules deep in the network (e.g. ring attention inside ``SelfAttention``)
need the concrete ``Mesh`` to open a ``shard_map`` island, but Flax module
attributes only carry static config. The mesh is process-global state in
practice — one per training job — so the setup layer registers it here
before tracing and call sites read it lazily. The mesh is static w.r.t.
jit tracing, so reading it during trace is sound.
"""

from __future__ import annotations

from jax.sharding import Mesh

_CURRENT_MESH: Mesh | None = None


def set_current_mesh(mesh: Mesh | None) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def get_current_mesh() -> Mesh | None:
    return _CURRENT_MESH


def seq_axis_size() -> int:
    mesh = get_current_mesh()
    if mesh is None or "seq" not in mesh.shape:
        return 1
    return int(mesh.shape["seq"])
