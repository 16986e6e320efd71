"""Mamba (selective S6) block — chunked selective scan in pure JAX.

The recurrence per channel c and state dim n::

    h_t = exp(A_c,n · dt_t,c) · h_{t-1} + dt_t,c · B_t,n · x_t,c
    y_t,c = Σ_n C_t,n · h_t,c,n + D_c · x_t,c

Sequence processing scans over *chunks* (default 256 steps) with an
inner ``lax.associative_scan``, which is the TPU-friendly formulation
(bounded live state, MXU-aligned inner ops).  Decode keeps ``(conv
state, ssm state)`` and advances one step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["init_mamba", "mamba_seq", "mamba_step", "init_mamba_cache"]


def _dt_rank(d_model: int) -> int:
    return max(1, -(-d_model // 16))


def init_mamba(init, d_model: int, d_state: int, d_conv: int,
               expand: int) -> dict:
    d_in = expand * d_model
    r = _dt_rank(d_model)
    return {
        "in_proj": init.normal((d_model, 2 * d_in), fan_in=d_model),
        "conv_w": init.normal((d_conv, d_in), fan_in=d_conv),
        "conv_b": init.zeros((d_in,)),
        "x_proj": init.normal((d_in, r + 2 * d_state), fan_in=d_in),
        "dt_proj": init.normal((r, d_in), fan_in=r),
        "dt_bias": init.zeros((d_in,)),
        # S4D-real initialization: A = -(1..N), stored as log
        "a_log": init.constant(
            jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32)),
            (d_in, d_state)),
        "d_skip": init.ones((d_in,)),
        "out_proj": init.normal((d_in, d_model), fan_in=d_in),
    }


def _ssm_params(params, xc):
    """Common projections. xc: [..., d_in] (post-conv, silu'd)."""
    r = params["dt_proj"].shape[0]
    n = params["a_log"].shape[1]
    proj = jnp.einsum("...i,ij->...j", xc, params["x_proj"].astype(xc.dtype))
    dt_r, b, c = jnp.split(proj, [r, r + n], axis=-1)
    dt = jnp.einsum("...r,ri->...i", dt_r, params["dt_proj"].astype(xc.dtype))
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))       # [d_in, N]
    return dt, a, b.astype(jnp.float32), c.astype(jnp.float32)


def _selective_scan_chunk(h0, dt, a, b, c, xc):
    """Associative scan within one chunk.

    h0: [B, d_in, N]; dt, xc: [B, L, d_in]; b, c: [B, L, N].
    Returns (y [B, L, d_in], hL).
    """
    # elementwise decay and input terms per step: [B, L, d_in, N]
    decay = jnp.exp(dt[..., None] * a[None, None])
    inp = (dt * xc)[..., None] * b[:, :, None, :]

    def combine(e1, e2):
        d1, i1 = e1
        d2, i2 = e2
        return d1 * d2, i1 * d2 + i2

    dec_c, inp_c = jax.lax.associative_scan(combine, (decay, inp), axis=1)
    h = dec_c * h0[:, None] + inp_c                         # [B, L, d_in, N]
    y = jnp.einsum("blin,bln->bli", h, c)
    return y, h[:, -1]


def mamba_seq(params: dict, x: jax.Array, chunk: int = 256,
              shard=None) -> jax.Array:
    """Full-sequence Mamba block. x: [B, S, d_model] -> same shape.

    ``shard(tensor, kind)`` pins the d_in dimension of the big scan
    intermediates to the "model" axis (d_in = 2·d_model: jamba's
    [B, chunk, d_in, N] selective-scan tensors are ~4 GiB each when
    replicated across the TP group).
    """
    shard = shard or (lambda v, kind: v)
    btype = x.dtype
    bsz, s, _ = x.shape
    d_in = params["dt_bias"].shape[0]
    n = params["a_log"].shape[1]

    xz = jnp.einsum("bsd,di->bsi", x, params["in_proj"].astype(btype))
    xr, z = jnp.split(xz, 2, axis=-1)
    xr = shard(xr, "mamba_din")

    # depthwise causal conv over sequence
    w = params["conv_w"].astype(btype)                       # [K, d_in]
    k = w.shape[0]
    xp = jnp.pad(xr, ((0, 0), (k - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + s] * w[i] for i in range(k))
    xc = jax.nn.silu(xc + params["conv_b"].astype(btype))

    dt, a, b, c = _ssm_params(params, xc)
    dt = shard(dt, "mamba_din")
    xcf = shard(xc.astype(jnp.float32), "mamba_din")

    n_chunks = -(-s // chunk)
    pad = n_chunks * chunk - s
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        xcf = jnp.pad(xcf, ((0, 0), (0, pad), (0, 0)))

    # checkpointed: the scan otherwise saves each chunk's full hidden
    # trajectory [B, L, d_in, N] for backward (~68 GiB/device on jamba
    # train_4k); recomputing the chunk from (h0, inputs) is cheap.
    @jax.checkpoint
    def outer(h, xs):
        dt_k, b_k, c_k, x_k = xs
        y_k, h_new = _selective_scan_chunk(h, dt_k, a, b_k, c_k, x_k)
        return h_new, y_k

    reshape = lambda t: t.reshape(bsz, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    h0 = jnp.zeros((bsz, d_in, n), jnp.float32)
    _, ys = jax.lax.scan(outer, h0,
                         (reshape(dt), reshape(b), reshape(c), reshape(xcf)))
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, n_chunks * chunk, d_in)[:, :s]

    y = y + xcf * params["d_skip"].astype(jnp.float32)
    y = y.astype(btype) * jax.nn.silu(z)
    return jnp.einsum("bsi,id->bsd", y, params["out_proj"].astype(btype))


def init_mamba_cache(bsz: int, d_model: int, d_state: int, d_conv: int,
                     expand: int, dtype=jnp.float32) -> dict:
    d_in = expand * d_model
    return {
        "conv": jnp.zeros((bsz, d_conv - 1, d_in), dtype),
        "ssm": jnp.zeros((bsz, d_in, d_state), jnp.float32),
    }


def mamba_step(params: dict, x: jax.Array, cache: dict
               ) -> tuple[jax.Array, dict]:
    """Single decode step. x: [B, 1, d_model]."""
    btype = x.dtype
    xz = jnp.einsum("bsd,di->bsi", x, params["in_proj"].astype(btype))
    xr, z = jnp.split(xz, 2, axis=-1)                        # [B,1,d_in]

    w = params["conv_w"].astype(btype)
    k = w.shape[0]
    window = jnp.concatenate([cache["conv"].astype(btype), xr], axis=1)
    xc = jnp.einsum("bki,ki->bi", window, w)[:, None]
    xc = jax.nn.silu(xc + params["conv_b"].astype(btype))

    dt, a, b, c = _ssm_params(params, xc)
    decay = jnp.exp(dt[:, 0, :, None] * a[None])             # [B,d_in,N]
    inp = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * b[:, 0, None, :]
    h = cache["ssm"] * decay + inp
    y = jnp.einsum("bin,bn->bi", h, c[:, 0])[:, None]
    y = y + xc.astype(jnp.float32) * params["d_skip"].astype(jnp.float32)
    y = y.astype(btype) * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, params["out_proj"].astype(btype))
    new_cache = {"conv": window[:, 1:].astype(cache["conv"].dtype), "ssm": h}
    return out, new_cache
