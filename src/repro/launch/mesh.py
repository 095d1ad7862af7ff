"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init; dryrun.py sets
XLA_FLAGS before importing anything).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types: every step passes explicit
    NamedShardings, and the shard_map bodies name their axes."""
    auto = (jax.sharding.AxisType.Auto,) * len(shape)
    return jax.make_mesh(shape, axes, axis_types=auto)


def devices_error(n: int, context: str = "--layout mesh"):
    """The shared mesh-entry-point guard: the actionable message when
    fewer than `n` devices are addressable, else None. Callers check
    BEFORE any dataset/compile work so a missing XLA_FLAGS fails fast
    with the fix, not deep in jax.make_mesh."""
    have = len(jax.devices())
    if have >= n:
        return None
    return (f"{context} needs >= {n} devices, have {have} (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n})")


def tp_mesh_error(mesh, tp: int):
    """The shared tp-vs-mesh contract: in-slice tensor parallelism of
    width `tp` needs a 'model' axis of exactly that size. Returns the
    actionable message, or None when the mesh satisfies it — the ONE
    definition `core.engine.Trainer` and `launch.steps` both check."""
    if tp <= 1:
        return None
    if "model" not in mesh.axis_names or mesh.shape["model"] != tp:
        return (f"tp={tp} needs a mesh with a 'model' axis of size {tp} "
                f"(got axes {mesh.axis_names} shape {dict(mesh.shape)})")
    return None


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over host devices for CPU tests (requires
    XLA_FLAGS=--xla_force_host_platform_device_count set by the caller)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def device_axes(multi_pod: bool):
    """Mesh axes that play the paper's K devices."""
    return ("pod", "data") if multi_pod else ("data",)
