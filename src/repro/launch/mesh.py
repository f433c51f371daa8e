"""Production mesh: TPU v5e pods, 256 chips each.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import os
import re

import jax

__all__ = ["make_mesh_compat", "make_production_mesh", "parallelism_for",
           "host_device_mesh", "ensure_host_device_count"]

_HOST_FLAG = "--xla_force_host_platform_device_count"


def _jax_backends_initialized() -> bool:
    """True once any jax computation has forced backend init (after which
    XLA_FLAGS changes are silently ignored by XLA)."""
    try:
        from jax._src import xla_bridge
        return bool(getattr(xla_bridge, "_backends", None))
    except Exception:  # pragma: no cover - private API moved
        return jax.local_device_count() > 1  # best effort; can't tell


def ensure_host_device_count(n: int) -> None:
    """Set `--xla_force_host_platform_device_count=n` in XLA_FLAGS (merging
    with any other flags already present).

    Must run before the first jax computation: XLA reads the flag once at
    backend init.  If backends are already initialized with fewer than `n`
    devices this raises a clear RuntimeError instead of letting callers
    proceed against a silently-ignored flag."""
    if _jax_backends_initialized():
        if jax.local_device_count() >= n:
            return  # already running with enough devices — nothing to do
        raise RuntimeError(
            f"jax is already initialized with {jax.local_device_count()} "
            f"device(s); {_HOST_FLAG}={n} cannot take effect now. Set "
            f"XLA_FLAGS='{_HOST_FLAG}={n}' in the environment (or call "
            f"ensure_host_device_count/host_device_mesh) BEFORE the first "
            f"jax computation, e.g. at the top of your script.")
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_HOST_FLAG}=\d+\s*", "", flags).strip()
    os.environ["XLA_FLAGS"] = f"{flags} {_HOST_FLAG}={n}".strip()


def host_device_mesh(n: int, axis: str = "ranks"):
    """A 1-D mesh of `n` host-platform (CPU) devices for the multi-device
    exchange engine (`repro.core.dist`) and its CPU CI.

    Sets/validates `--xla_force_host_platform_device_count=n`, then builds
    the mesh.  Fails with a clear error when called after jax init with too
    few devices (the flag would be ignored)."""
    ensure_host_device_count(n)
    if jax.local_device_count() < n:
        raise RuntimeError(
            f"requested a {n}-device host mesh but jax initialized only "
            f"{jax.local_device_count()} device(s); is another process "
            f"setting XLA_FLAGS after import?")
    return make_mesh_compat((n,), (axis,))


def make_mesh_compat(shape, axes):
    """jax.make_mesh with explicit Auto axis_types — the single home for
    mesh construction; tests and production meshes all route through it."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def parallelism_for(mesh, *, hierarchical: bool = True, q_chunk: int = 256,
                    kv_chunk: int = 1024, use_pallas: bool = False,
                    moe_seq_shard: bool = False):
    from repro.sharding.parallel import Parallelism
    multi = "pod" in mesh.axis_names
    return Parallelism(
        mesh=mesh,
        data_axes=("pod", "data") if multi else ("data",),
        model_axis="model",
        pod_axis="pod" if multi else None,
        hierarchical=hierarchical,
        moe_seq_shard=moe_seq_shard,
        q_chunk=q_chunk, kv_chunk=kv_chunk, use_pallas=use_pallas,
    )
