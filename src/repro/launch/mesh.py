"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS for 512 host devices
*before* any jax initialization; tests and benches see the real 1-CPU
topology.

Geometry (per the brief): one v5e pod = 16x16 = 256 chips, axes
("data", "model"); the multi-pod config stacks 2 pods on a leading "pod"
axis (DCN/ICI-superpod) = 512 chips.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def _axis_kw(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"),
        **_axis_kw(2),
    )


def make_stream_mesh(n_shards: int | None = None):
    """1-D data-parallel mesh for the streaming runtime's slot pool.

    The streaming model is tiny and always replicated (one CIM macro's
    weights serve every user), so there is no 'model' axis: the mesh is a
    flat ``("data",)`` axis and the slot pool's batch dimension shards over
    it — one logical pool spanning the whole mesh instead of one pool per
    device.  Defaults to every visible device; force a multi-device host
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = jax.device_count() if n_shards is None else n_shards
    if n > jax.device_count():
        raise ValueError(
            f"{n} shards > {jax.device_count()} devices (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax init)"
        )
    if n == jax.device_count():
        return jax.make_mesh((n,), ("data",), **_axis_kw(1))
    # a strict prefix of the device list (tests sweep 1/2/8-shard meshes)
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
