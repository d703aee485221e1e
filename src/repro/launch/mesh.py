"""Local device meshes and the persistent compile cache.

Importing this module never touches jax device state; meshes are built
inside the functions, over this process's devices (one chip, or the
four chips of one host).

Every mesh uses ``AxisType.Auto`` axes: the sharding rules
(``repro.distributed.sharding``) and the MoE ``shard_map`` path leave
propagation to the compiler, which is what ``Auto`` means.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path, because the path is part of the cache key.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Entry points call this from ``main()``; nothing calls it on import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is set here; otherwise the cache lives at ``CACHE_DIR``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def make_local_mesh(model: int = 1, devices=None) -> jax.sharding.Mesh:
    """(data, model) over ``devices`` (default: all of this process's)."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh((len(devices) // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=devices)
