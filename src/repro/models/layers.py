"""Shared layer primitives: norms, RoPE, activations, embeddings, init."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def full_precision(fn):
    """``fn`` traced with its matmuls at full precision (the serving steps).

    A float32 matmul at default precision runs one bf16 pass on a TPU, and
    XLA then casts each stacked f32 weight of a layer scan to bf16 in one
    piece, hoisted out of the loop: a full-width granite-3-2b serve step
    held a 5.6 GB bf16 copy of its 10.1 GB f32 weights as temporaries,
    more than a 16 GB v5e chip has left.  At full precision nothing is
    cast (0.74 GB of temporaries), the served math is the weights' own
    float32, and a CPU, which always multiplies at full precision, computes
    exactly what it did."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return fn(*args, **kwargs)
    return traced


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, weight, bias=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def softcap(x, cap: float):
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_gated": jax.nn.gelu}[name]


# ---------------------------------------------------------------- RoPE
def rope_freqs(hsz: int, theta: float):
    """[hsz/2] inverse frequencies."""
    return 1.0 / (theta ** (jnp.arange(0, hsz, 2, dtype=jnp.float32) / hsz))


def apply_rope(x, positions, theta: float = 10_000.0):
    """Rotate head vectors.  x [..., T, n_heads, hsz], positions [..., T]."""
    hsz = x.shape[-1]
    inv = rope_freqs(hsz, theta)                         # [hsz/2]
    ang = positions[..., None].astype(jnp.float32) * inv  # [..., T, hsz/2]
    cos = jnp.cos(ang)[..., None, :]                     # [..., T, 1, hsz/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int):
    """Whisper-style sinusoidal embeddings [length, dim]."""
    log_timescale = jnp.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=-1)


def sinusoidal_at(pos, dim: int):
    """Sinusoidal embedding at dynamic position(s).  pos [...] -> [..., dim]."""
    log_timescale = jnp.log(10_000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    t = pos[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=-1)


# ---------------------------------------------------------------- init
def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
