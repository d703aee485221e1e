"""Production mesh construction, device peaks and the compile cache.

Importing this module never touches jax device state; meshes are built
lazily inside the functions (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to get placeholder devices).

Topology:
* single pod: (16, 16) = 256 chips, axes ("data", "model")
* multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model")

Every mesh uses ``AxisType.Auto`` axes: the sharding rules
(``repro.distributed.sharding``) and the MoE ``shard_map`` path leave
propagation to the compiler, which is what ``Auto`` means.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.sharding import AxisType

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" is TPU v5e; source: Google Cloud documentation, "TPU
#: v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
#: inter-chip interconnect per chip (four links of 50 GB/s).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,          # FLOP/s
        "hbm_bytes": 16e9,             # B
        "hbm_bw": 819e9,               # B/s
        "ici_bw_per_link": 50e9,       # B/s
    },
}

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed path, because the path is part of the cache key.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def peaks(device_kind: str) -> dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to repro.launch.mesh.PEAKS with its source") from None


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Entry points call this from ``main()``; nothing calls it on import.
    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, so
    nothing is set here; otherwise the cache lives at ``CACHE_DIR``.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """A mesh whose axes are all ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1, devices=None) -> jax.sharding.Mesh:
    """(data, model) over ``devices`` (default: all of this process's)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    return auto_mesh((n // model, model), ("data", "model"), devices)


def mesh_chips(mesh: jax.sharding.Mesh) -> int:
    return mesh.devices.size
