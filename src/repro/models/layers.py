"""Shared neural building blocks: RMSNorm, RoPE, SwiGLU, initializers, and
``mm``, the projection every layer multiplies its weights with."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .lora import LoraWeight, matmul as lora_matmul


def mm(x: jax.Array, w) -> jax.Array:
    """``x @ w``; a :class:`~repro.models.lora.LoraWeight` adds its
    adapter's low-rank product."""
    if isinstance(w, LoraWeight):
        return lora_matmul(x, w)
    return x @ w


def dense_init(key, n_in, n_out, dtype=jnp.float32):
    scale = jnp.sqrt(1.0 / n_in).astype(jnp.float32)
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * scale).astype(dtype)


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * gamma


def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, hd]; positions: [..., S] (broadcastable)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, hd/2]
    cos = jnp.cos(ang)[..., None, :]                    # [..., S, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    h = jax.nn.silu(mm(x, w1)) * mm(x, w3)
    return mm(h, w2)


def init_swiglu(key, d, ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": dense_init(k1, d, ff, dtype),
            "w3": dense_init(k2, d, ff, dtype),
            "w2": dense_init(k3, ff, d, dtype)}
