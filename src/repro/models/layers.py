"""Shared model building blocks (pure-functional JAX).

Parameters are nested dicts of ``jnp`` arrays.  Everything here is
written to lower cleanly under ``jax.jit`` with GSPMD sharding — no
Python-level data-dependent control flow.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Initializer",
    "rms_norm",
    "swiglu",
    "rope_frequencies",
    "apply_rope",
    "embed",
    "unembed",
]


class Initializer:
    """Deterministic param initializer with a fan-in scaled normal.

    Inside ``with init.stacked(n):`` every array gets a leading ``[n]``
    dimension, so the ``n`` copies of a scanned layer are drawn in one
    call and never built by stacking per-layer arrays (which would hold
    both at once).
    """

    def __init__(self, seed: int, param_dtype=jnp.bfloat16):
        self.key = jax.random.PRNGKey(seed)
        self.param_dtype = param_dtype
        self.lead: tuple[int, ...] = ()

    @contextlib.contextmanager
    def stacked(self, n: int):
        prev = self.lead
        self.lead = prev + (n,)
        try:
            yield self
        finally:
            self.lead = prev

    def next_key(self):
        self.key, sub = jax.random.split(self.key)
        return sub

    def normal(self, shape, fan_in: int | None = None, scale: float = 1.0):
        fan = fan_in if fan_in is not None else shape[0]
        std = scale / np.sqrt(max(fan, 1))
        x = jax.random.normal(self.next_key(), self.lead + tuple(shape),
                              dtype=jnp.float32) * std
        return x.astype(self.param_dtype)

    def zeros(self, shape):
        return jnp.zeros(self.lead + tuple(shape), dtype=self.param_dtype)

    def ones(self, shape):
        return jnp.ones(self.lead + tuple(shape), dtype=self.param_dtype)

    def constant(self, value, shape):
        """``value`` broadcast to ``shape`` (plus the stacked dims)."""
        return jnp.broadcast_to(jnp.asarray(value, self.param_dtype),
                                self.lead + tuple(shape))


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with float32 accumulation."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(dtype) * gamma


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """SwiGLU FFN: (silu(x @ Wg) * (x @ Wu)) @ Wd."""
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def rope_frequencies(head_dim: int, max_pos: int, theta: float) -> jax.Array:
    """[max_pos, head_dim//2] complex-free cos/sin table (f32)."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    pos = np.arange(max_pos)
    ang = np.einsum("p,f->pf", pos, inv)
    return jnp.asarray(np.stack([np.cos(ang), np.sin(ang)]), jnp.float32)


def apply_rope(x: jax.Array, cos_sin: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Rotate ``x [..., S, H, hd]`` by per-position angles.

    ``positions [..., S]`` are absolute token positions (supports
    decode where the single query sits at ``cache_len``).
    """
    cos = cos_sin[0][positions]  # [..., S, hd//2]
    sin = cos_sin[1][positions]
    cos = cos[..., None, :]      # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """Project hidden states to vocabulary logits (f32)."""
    return jnp.einsum("...d,vd->...v", x, table,
                      preferred_element_type=jnp.float32)
