"""What the architectures' references share: dtypes by name, the key
their weights are drawn from, and the float8 rounding of the
lower-precision control."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def weight_seed(seed: int) -> np.uint32:
    """The number the weights' key is made from: the run's seed modulo
    2**32, as an unsigned 32-bit integer, so that one compiled program
    draws the weights of every seed."""
    return np.uint32(seed % 2**32)


def fp8(w, axis: int = -2):
    """float8 e4m3 with one absmax scale per output column (the input
    dimension ``axis`` is reduced)."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.maximum(scale, 1e-12)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
