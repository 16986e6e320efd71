"""Flash attention — Pallas TPU kernel (TARGET: TPU v5e; validated in
interpret mode on CPU against ``ref.reference_attention``).

Design (TPU-native, not a CUDA port):

* grid = (batch×q_heads, S/block_q, S/block_k); the last axis is
  sequential ("arbitrary") — the online-softmax state for one q block
  lives in VMEM scratch across its k iterations.
* BlockSpec tiling: q/o tiles [block_q, head_dim] and k/v tiles
  [block_k, head_dim] in VMEM; head_dim is MXU-aligned (128 for every
  assigned architecture; rwkv uses its own kernel).
* GQA without materializing repeated KV: the k/v index_map folds the
  query-head → kv-head mapping (zero-copy head grouping).
* f32 accumulation; bf16 in/out friendly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bhsd"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_k: int, seq_len: int, causal: bool,
            scale: float, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)           # [bq, bk]

    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = cols < seq_len                              # tail padding
    if causal:
        mask = mask & (cols <= rows)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1)
    v = v_ref[0].astype(jnp.float32)                  # [bk, hd]
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,      # [BHq, S, hd]
    k: jax.Array,      # [BHkv, S, hd]
    v: jax.Array,      # [BHkv, S, hd]
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over flattened (batch, head) leading dim."""
    bh, s, hd = q.shape
    bh_kv = k.shape[0]
    if bh % bh_kv:
        raise ValueError(f"q heads {bh} not a multiple of kv heads {bh_kv}")
    group = bh // bh_kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    n_q = -(-s // block_q)
    n_k = -(-s // block_k)
    grid = (bh, n_q, n_k)

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, seq_len=s,
        causal=causal, scale=hd ** -0.5, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik: (b // group, ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik: (b // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n_q * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)[:, :s]
