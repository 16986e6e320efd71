"""Grouped-query attention with flash-style chunked softmax.

Three entry points:

* :func:`gqa_attention` — self-attention over a full sequence (train /
  prefill).  Uses an online-softmax scan over KV chunks, so the S×S
  score matrix is never materialized — the pure-jnp analogue of the
  Pallas flash kernel in ``repro.kernels.flash_attention`` (which is
  the TPU-target implementation of the same math).
* :func:`decode_attention` — one new query against a KV cache.
* :func:`cross_attention` — queries attend to a fixed memory (VLM
  frontend tokens / encoder output).

All softmax statistics are f32; inputs/outputs bf16-friendly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "gqa_attention",
    "decode_attention",
    "cross_attention",
    "repeat_kv",
]

_NEG_INF = -1e30


def repeat_kv(kv: jax.Array, groups: int) -> jax.Array:
    """[B, S, Hkv, hd] -> [B, S, Hkv*groups, hd]."""
    if groups == 1:
        return kv
    b, s, h, d = kv.shape
    kv = jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, groups, d))
    return kv.reshape(b, s, h * groups, d)


def _chunked_mha(q, k, v, *, causal: bool, chunk: int,
                 sliding_window: int = 0,
                 q_offset: int = 0):
    """Online-softmax attention, scanning over KV chunks.

    q: [B, Sq, H, hd]; k, v: [B, Sk, H, hd].  Returns [B, Sq, H, hd].
    ``q_offset`` is the absolute position of q[0] (prefill: 0).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = hd ** -0.5
    qs = q * scale  # keep input dtype: MXU takes bf16 in, f32 accum

    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, h, hd)
    vc = v.reshape(b, n_chunks, chunk, h, hd)

    q_pos = q_offset + jnp.arange(sq)

    # The chunk body is checkpointed: the backward pass recomputes the
    # score/softmax tensors per chunk instead of stacking them across
    # the scan — the same recompute strategy as the Pallas flash kernel,
    # and the difference between O(S·chunk) and O(S²) attention
    # residency.
    @jax.checkpoint
    def step(carry, xs):
        m, l, acc = carry
        kb, vb, start = xs
        s = jax.lax.dot_general(
            qs, kb, (((3,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=jnp.float32)      # [B, H, Sq, chunk]
        k_pos = start + jnp.arange(chunk)
        mask = k_pos[None, :] < sk  # padding
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if sliding_window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((3,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)      # [B, H, Sq, hd]
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), starts),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Sq, H, hd]


def gqa_attention(q, k, v, *, causal: bool = True, chunk: int = 512,
                  sliding_window: int = 0) -> jax.Array:
    """Self-attention; q [B,S,Hq,hd], k/v [B,S,Hkv,hd].

    GQA without materializing repeated KV: query heads are folded into
    a [B, S, Hkv, group, hd] view so the online-softmax dots contract
    directly against the Hkv-headed K/V (repeat_kv would multiply KV
    HBM traffic by the group factor — measured 64+ GB/step on the
    llama3 decode cell).
    """
    b, s, hq, hd = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    chunk = min(chunk, s)
    if groups == 1:
        return _chunked_mha(q, k, v, causal=causal, chunk=chunk,
                            sliding_window=sliding_window)
    qg = q.reshape(b, s, hkv, groups, hd)
    og = _chunked_gqa(qg, k, v, causal=causal, chunk=chunk,
                      sliding_window=sliding_window)
    return og.reshape(b, s, hq, hd)


def _chunked_gqa(q, k, v, *, causal: bool, chunk: int,
                 sliding_window: int = 0):
    """Grouped online-softmax attention.

    q: [B, Sq, Hkv, G, hd]; k, v: [B, Sk, Hkv, hd].
    Returns [B, Sq, Hkv, G, hd].
    """
    b, sq, hkv, g, hd = q.shape
    sk = k.shape[1]
    qs = q * (hd ** -0.5)

    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk, hkv, hd)
    q_pos = jnp.arange(sq)

    @jax.checkpoint
    def step(carry, xs):
        m, l, acc = carry
        kb, vb, start = xs
        # batch (B, Hkv), lhs free (Sq, G), rhs free (chunk)
        # -> s: [B, Hkv, Sq, G, chunk]
        s = jax.lax.dot_general(
            qs, kb, (((4,), (3,)), ((0, 2), (0, 2))),
            preferred_element_type=jnp.float32)
        k_pos = start + jnp.arange(chunk)
        mask = k_pos[None, :] < sk
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if sliding_window > 0:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - sliding_window)
        s = jnp.where(mask[None, None, :, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((4,), (1,)), ((0, 1), (0, 2))),
            preferred_element_type=jnp.float32)   # [B, Hkv, Sq, G, hd]
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, sq, g), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, sq, g), jnp.float32)
    acc0 = jnp.zeros((b, hkv, sq, g, hd), jnp.float32)
    starts = jnp.arange(n_chunks) * chunk
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # [B, Hkv, Sq, G, hd] -> [B, Sq, Hkv, G, hd]
    return out.transpose(0, 2, 1, 3, 4).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     sliding_window: int = 0) -> jax.Array:
    """One-step attention: q [B,1,Hq,hd] vs cache [B,Smax,Hkv,hd].

    ``cache_len`` — number of valid cache entries (the new token's KV
    must already be written at ``cache_len - 1``).  The GQA grouping is
    folded into the dots — the cache is never repeated across query
    heads (repeat_kv costs group× the cache's HBM traffic per token).
    """
    b, one, hq, hd = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    qg = (q * (hd ** -0.5)).reshape(b, one, hkv, g, hd)
    # batch (B, Hkv); lhs free (1, G); rhs free (Smax)
    s = jax.lax.dot_general(
        qg, k_cache, (((4,), (3,)), ((0, 2), (0, 2))),
        preferred_element_type=jnp.float32)      # [B, Hkv, 1, G, Smax]
    k_pos = jnp.arange(smax)
    # cache_len: scalar, or [B] per-slot lengths (continuous batching)
    clen = jnp.asarray(cache_len)
    if clen.ndim == 0:
        clen = jnp.full((b,), clen)
    mask = k_pos[None, :] < clen[:, None]                 # [B, Smax]
    if sliding_window > 0:
        mask = mask & (k_pos[None, :] > clen[:, None] - 1 - sliding_window)
    s = jnp.where(mask[:, None, None, None, :], s, _NEG_INF)
    # normalise after the PV product, in f32, as the prefill path does
    # (``_chunked_gqa``), so decode and prefill round alike
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    out = jax.lax.dot_general(
        p.astype(v_cache.dtype), v_cache,
        (((4,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32)      # [B, Hkv, 1, G, hd]
    out = out / p.sum(axis=-1)[..., None]
    return out.transpose(0, 2, 1, 3, 4).reshape(b, one, hq, hd).astype(
        q.dtype)


def cross_attention(q, k, v, chunk: int = 512) -> jax.Array:
    """Non-causal attention of q [B,Sq,Hq,hd] over memory k/v [B,Sm,Hkv,hd]."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    chunk = min(chunk, k.shape[1])
    if groups == 1:
        return _chunked_mha(q, k, v, causal=False, chunk=chunk)
    qg = q.reshape(b, sq, hkv, groups, hd)
    og = _chunked_gqa(qg, k, v, causal=False, chunk=chunk)
    return og.reshape(b, sq, hq, hd)
