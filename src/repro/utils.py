"""Small shared utilities: padding, rounding, dtype helpers, the mesh /
shard_map entry points every module uses (thin calls of the JAX APIs, so
axis types and the replication-check flag are set in one place), and the
persistent compilation-cache placement shared by the serving CLI and
``chip_smoke.py``."""
from __future__ import annotations

import math
import os
import pathlib

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # finite stand-in for -inf inside kernels (avoids NaN in exp/max)


# ------------------------------------------------------------ mesh helpers
def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(tuple(shape)))


def set_mesh(mesh):
    """``jax.set_mesh`` context manager."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ------------------------------------------------------ compilation cache
def compile_cache_dir() -> str:
    """Directory of JAX's persistent compilation cache.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set; otherwise the fixed
    ``.jax_cache`` directory at the root of this checkout (git-ignored).
    The path is part of every cache key, so it never depends on a temp
    dir, pid or time — two runs from the same checkout share entries."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    (and nowhere else), caching every compiled program however fast it
    compiled.  Call before the first compile; returns the directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


def pad_dim(x, dim: int, multiple: int, value=0.0):
    """Pad dimension `dim` of x up to a multiple of `multiple`."""
    size = x.shape[dim]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def unpad_dim(x, dim: int, size: int):
    if x.shape[dim] == size:
        return x
    idx = [slice(None)] * x.ndim
    idx[dim] = slice(0, size)
    return x[tuple(idx)]


def bytes_of(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} PiB"
