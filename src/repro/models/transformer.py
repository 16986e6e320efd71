"""Unified model covering all assigned architectures.

One ``LM`` class dispatches per-layer kinds from ``ModelConfig``:

* dense / MoE decoders (llama3, granite, qwen2.5, minitron, mixtral,
  olmoe),
* attention-free RWKV6,
* hybrid Mamba/attention with MoE (jamba),
* VLM backbone with periodic cross-attention to stub patch embeddings
  (llama-3.2-vision),
* encoder–decoder with cross-attention every decoder layer
  (seamless-m4t; stub frame embeddings feed the encoder).

Layers are *scanned*: the layer pattern has period ``p`` (lcm of the
attention/MoE/cross periods), parameters are stacked ``[L/p, ...]`` per
in-period position, and ``jax.lax.scan`` runs the repeats — keeping the
HLO size O(p) instead of O(L), which is what makes the 100-layer
dry-runs compile quickly.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import attention as attn
from . import moe as moe_mod
from . import rwkv as rwkv_mod
from . import ssm as ssm_mod
from .layers import Initializer, apply_rope, embed, rms_norm, rope_frequencies, swiglu, unembed

__all__ = ["LM", "LayerSpec"]


@dataclass(frozen=True)
class LayerSpec:
    kind: str        # attn | mamba | rwkv
    moe: bool
    cross: bool


def _lcm(*vals: int) -> int:
    out = 1
    for v in vals:
        if v > 1:
            out = out * v // math.gcd(out, v)
    return out


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) absmax int8 quantization. x: [B, 1, H, hd]."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale.astype(
        jnp.bfloat16)


class LM:
    """Functional language model; params are nested dicts."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        param_dtype=jnp.bfloat16,
        attn_chunk: int = 512,
        mamba_chunk: int = 256,
        capacity_factor: float = 1.25,
        max_seq: int = 0,
        remat: str = "none",        # none | full | dots
        shard_act=None,             # fn(x, kind) -> x sharding constraint
        rwkv_chunk: int = 16,
        kv_dtype: str = "bf16",     # bf16 | int8 (quantized KV cache)
    ) -> None:
        self.cfg = cfg
        self.param_dtype = param_dtype
        self.attn_chunk = attn_chunk
        self.mamba_chunk = mamba_chunk
        self.capacity_factor = capacity_factor
        self.max_seq = max_seq or 8192
        self.remat = remat
        self.shard_act = shard_act or (lambda x, kind="act": x)
        self.rwkv_chunk = rwkv_chunk
        self.kv_dtype = kv_dtype

        p = _lcm(
            cfg.attn_layer_period or 1,
            cfg.moe_layer_period if cfg.is_moe else 1,
            cfg.cross_attn_period or 1,
        )
        if cfg.n_layers % p != 0:
            p = cfg.n_layers  # fall back to fully unrolled stack
        self.period = p
        self.n_rep = cfg.n_layers // p
        self.specs = [self._spec(j) for j in range(p)]
        # encoder (enc-dec archs): plain non-causal attention stack
        self.enc_rep = cfg.n_encoder_layers

    def _spec(self, j: int) -> LayerSpec:
        cfg = self.cfg
        cross = cfg.layer_cross_attends(j) or cfg.is_encdec
        return LayerSpec(cfg.layer_kind(j), cfg.layer_is_moe(j), cross)

    # ------------------------------------------------------------------ #
    # init
    # ------------------------------------------------------------------ #
    def _init_mixer(self, init, spec: LayerSpec) -> dict:
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.hd
        if spec.kind == "attn":
            p = {
                "norm": init.ones((d,)),
                "wq": init.normal((d, cfg.n_heads * hd), fan_in=d),
                "wk": init.normal((d, cfg.n_kv_heads * hd), fan_in=d),
                "wv": init.normal((d, cfg.n_kv_heads * hd), fan_in=d),
                "wo": init.normal((cfg.n_heads * hd, d), fan_in=cfg.n_heads * hd),
            }
            if cfg.qkv_bias:
                p["bq"] = init.zeros((cfg.n_heads * hd,))
                p["bk"] = init.zeros((cfg.n_kv_heads * hd,))
                p["bv"] = init.zeros((cfg.n_kv_heads * hd,))
            return p
        if spec.kind == "mamba":
            return {
                "norm": init.ones((d,)),
                **ssm_mod.init_mamba(init, d, cfg.mamba_d_state,
                                     cfg.mamba_d_conv, cfg.mamba_expand),
            }
        return {
            "norm": init.ones((d,)),
            **rwkv_mod.init_rwkv(init, d, cfg.n_heads, hd),
        }

    def _init_layer(self, init, spec: LayerSpec) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        p = {"mixer": self._init_mixer(init, spec)}
        if spec.cross:
            p["cross"] = {
                "norm": init.ones((d,)),
                "wq": init.normal((d, cfg.n_heads * cfg.hd), fan_in=d),
                "wk": init.normal((d, cfg.n_kv_heads * cfg.hd), fan_in=d),
                "wv": init.normal((d, cfg.n_kv_heads * cfg.hd), fan_in=d),
                "wo": init.normal((cfg.n_heads * cfg.hd, d),
                                  fan_in=cfg.n_heads * cfg.hd),
            }
        p["ffn_norm"] = init.ones((d,))
        if spec.moe:
            p["moe"] = moe_mod.init_moe(init, d, cfg.d_ff, cfg.n_experts)
        else:
            p["ffn"] = {
                "w_gate": init.normal((d, cfg.d_ff), fan_in=d),
                "w_up": init.normal((d, cfg.d_ff), fan_in=d),
                "w_down": init.normal((cfg.d_ff, d), fan_in=cfg.d_ff),
            }
        return p

    def init(self, seed: int = 0) -> dict:
        cfg = self.cfg
        init = Initializer(seed, self.param_dtype)
        params: dict = {
            "embed": init.normal((cfg.vocab_size, cfg.d_model),
                                 fan_in=cfg.d_model),
            "final_norm": init.ones((cfg.d_model,)),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init.normal(
                (cfg.vocab_size, cfg.d_model), fan_in=cfg.d_model)
        # decoder stack: n_rep stacked copies per in-period position
        blocks = []
        for spec in self.specs:
            with init.stacked(self.n_rep):
                blocks.append(self._init_layer(init, spec))
        params["blocks"] = blocks
        if cfg.is_encdec:
            with init.stacked(cfg.n_encoder_layers):
                params["encoder"] = self._init_layer(
                    init, LayerSpec("attn", False, False))
            params["enc_norm"] = init.ones((cfg.d_model,))
        if cfg.frontend_tokens and cfg.frontend_dim != cfg.d_model:
            params["frontend_proj"] = init.normal(
                (cfg.frontend_dim, cfg.d_model), fan_in=cfg.frontend_dim)
        return params

    # ------------------------------------------------------------------ #
    # building blocks
    # ------------------------------------------------------------------ #
    def _rope(self, max_pos: int):
        return rope_frequencies(self.cfg.hd, max_pos, self.cfg.rope_theta)

    def _self_attn(self, p, x, cos_sin, positions, causal=True):
        cfg = self.cfg
        b, s, d = x.shape
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        if s > 1:
            h = self.shard_act(h, "attn_in")
        q = jnp.einsum("bsd,de->bse", h, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,de->bse", h, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,de->bse", h, p["wv"].astype(x.dtype))
        if cfg.qkv_bias and "bq" in p:
            q = q + p["bq"].astype(x.dtype)
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        q = q.reshape(b, s, cfg.n_heads, cfg.hd)
        k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
        v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
        q = apply_rope(q, cos_sin, positions)
        k = apply_rope(k, cos_sin, positions)
        o = attn.gqa_attention(q, k, v, causal=causal,
                               chunk=self.attn_chunk,
                               sliding_window=cfg.sliding_window)
        o = o.reshape(b, s, cfg.n_heads * cfg.hd)
        return jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype)), (k, v)

    def _cross_attn(self, p, x, memory):
        """memory: [B, M, d] (frontend embeddings / encoder output)."""
        cfg = self.cfg
        b, s, d = x.shape
        m = memory.shape[1]
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,de->bse", h, p["wq"].astype(x.dtype))
        k = jnp.einsum("bmd,de->bme", memory, p["wk"].astype(x.dtype))
        v = jnp.einsum("bmd,de->bme", memory, p["wv"].astype(x.dtype))
        q = q.reshape(b, s, cfg.n_heads, cfg.hd)
        k = k.reshape(b, m, cfg.n_kv_heads, cfg.hd)
        v = v.reshape(b, m, cfg.n_kv_heads, cfg.hd)
        o = attn.cross_attention(q, k, v, chunk=self.attn_chunk)
        o = o.reshape(b, s, cfg.n_heads * cfg.hd)
        return jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))

    def _ffn(self, p, spec, x):
        cfg = self.cfg
        h = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if spec.moe:
            y, aux = moe_mod.moe_ffn(
                p["moe"], h, top_k=cfg.experts_per_token,
                capacity_factor=self.capacity_factor,
                shard=self.shard_act)
            return y, aux
        f = p["ffn"]
        return swiglu(h, f["w_gate"].astype(x.dtype),
                      f["w_up"].astype(x.dtype),
                      f["w_down"].astype(x.dtype)), 0.0

    def _layer_seq(self, p, spec: LayerSpec, x, memory, cos_sin, positions):
        """Full-sequence layer (train / prefill). Returns (x, aux, kv)."""
        cfg = self.cfg
        kv = None
        if spec.kind == "attn":
            o, kv = self._self_attn(p["mixer"], x, cos_sin, positions)
            # constrain partial sums to the residual sharding *before*
            # the add so GSPMD reduce-scatters instead of all-reducing
            # the full [B,S,d] tensor (Megatron-SP exit)
            x = x + self.shard_act(o, "residual")
        elif spec.kind == "mamba":
            h = rms_norm(x, p["mixer"]["norm"], cfg.norm_eps)
            x = x + ssm_mod.mamba_seq(p["mixer"], h, chunk=self.mamba_chunk,
                                      shard=self.shard_act)
        else:  # rwkv
            h = rms_norm(x, p["mixer"]["norm"], cfg.norm_eps)
            x = x + rwkv_mod.rwkv_seq(p["mixer"], h, cfg.n_heads, cfg.hd,
                                      cfg.norm_eps,
                                      chunk=self.rwkv_chunk)
        if spec.cross and memory is not None:
            x = x + self.shard_act(self._cross_attn(p["cross"], x, memory),
                                   "residual")
        y, aux = self._ffn(p, spec, x)
        return x + self.shard_act(y, "residual"), aux, kv

    def _maybe_remat(self, body):
        """Activation checkpointing policy for the layer-scan body."""
        if self.remat == "full":
            return jax.checkpoint(body)
        if self.remat == "dots":
            return jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            )
        return body

    # ------------------------------------------------------------------ #
    # forward (train / prefill logits)
    # ------------------------------------------------------------------ #
    def _frontend_memory(self, params, frontend, dtype):
        if frontend is None:
            return None
        mem = frontend.astype(dtype)
        if "frontend_proj" in params:
            mem = jnp.einsum("bmf,fd->bmd", mem,
                             params["frontend_proj"].astype(dtype))
        return mem

    def _encode(self, params, memory):
        """Encoder stack over frontend embeddings (enc-dec archs)."""
        cfg = self.cfg
        b, m, d = memory.shape
        cos_sin = self._rope(m)
        positions = jnp.arange(m)[None, :]
        enc_spec = LayerSpec("attn", False, False)

        def body(x, lp):
            o, _ = self._self_attn(lp["mixer"], x, cos_sin, positions,
                                   causal=False)
            x = x + self.shard_act(o, "residual")
            y, _ = self._ffn(lp, enc_spec, x)
            x = self.shard_act(x + y, "residual")
            return x, None

        body = self._maybe_remat(body)
        x, _ = jax.lax.scan(body, memory, params["encoder"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def hidden_states(self, params, tokens, frontend=None):
        """Final-norm hidden states [B, S, d] + MoE aux loss."""
        cfg = self.cfg
        x = embed(params["embed"], tokens).astype(self.param_dtype)
        b, s, _ = x.shape
        memory = self._frontend_memory(params, frontend, x.dtype)
        if cfg.is_encdec and memory is not None:
            memory = self._encode(params, memory)
        cos_sin = self._rope(max(s, 1))
        positions = jnp.arange(s)[None, :]

        aux_total = 0.0
        for j, spec in enumerate(self.specs):
            def body(carry, lp, spec=spec):
                x, aux = carry
                x, a, _ = self._layer_seq(lp, spec, x, memory, cos_sin,
                                          positions)
                x = self.shard_act(x, "residual")
                return (x, aux + a), None
            body = self._maybe_remat(body)
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), params["blocks"][j])

        return rms_norm(x, params["final_norm"], cfg.norm_eps), aux_total

    def forward(self, params, tokens, frontend=None, last_only=False):
        """Causal logits. tokens: [B, S].

        ``last_only`` avoids materializing the [B, S, V] logits tensor —
        serving prefill only needs the final position.
        """
        x, aux_total = self.hidden_states(params, tokens, frontend)
        table = params.get("lm_head", params["embed"])
        if last_only:
            x = x[:, -1:]
        logits = self.shard_act(unembed(x, table), "logits")
        return logits, aux_total

    def loss(self, params, batch, vocab_chunk: int = 512):
        """Next-token cross entropy, chunked over the sequence so the
        [B, S, V] logits tensor is never resident (production LMs with
        128k+ vocabularies cannot afford it).  batch: tokens, labels."""
        cfg = self.cfg
        x, aux = self.hidden_states(params, batch["tokens"],
                                    batch.get("frontend"))
        labels = batch["labels"]
        table = params.get("lm_head", params["embed"])
        b, s, d = x.shape
        chunk = min(vocab_chunk, s)
        n_chunks = s // chunk if s % chunk == 0 else 1
        if s % chunk != 0:
            chunk = s

        xs = x.reshape(b, n_chunks, chunk, d).transpose(1, 0, 2, 3)
        ls = labels.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
        mask = batch.get("mask")
        ms = (mask.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
              if mask is not None else jnp.ones_like(ls, jnp.float32))

        # checkpointed: without it the scan saves every chunk's
        # [B, c, V] logits + one-hot for backward (67 GiB/device on
        # seamless's 256k vocabulary); recomputing them is one extra
        # unembed matmul per chunk.
        @jax.checkpoint
        def body(acc, xs_):
            xc, lc, mc = xs_
            logits = unembed(xc, table)                    # [B, c, V] f32
            logits = self.shard_act(logits, "logits")
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            onehot = jax.nn.one_hot(lc, cfg.vocab_size,
                                    dtype=self.param_dtype)
            picked = jnp.einsum("bsv,bsv->bs", logits, onehot)
            nll = (lse - picked) * mc
            return (acc[0] + nll.sum(), acc[1] + mc.sum()), None

        (total, denom), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xs, ls, ms))
        return total / jnp.maximum(denom, 1.0) + 0.01 * aux

    # ------------------------------------------------------------------ #
    # serving: prefill + decode
    # ------------------------------------------------------------------ #
    def init_cache(self, bsz: int, max_len: int, dtype=None) -> list:
        """Stacked per-position caches mirroring ``params['blocks']``.

        With ``kv_dtype="int8"`` the KV entries are stored quantized
        (per-token-per-head absmax scales) — 1.94× less cache
        residency, the knob that brings 100-layer 32k-context decode
        under a 16 GiB HBM budget (EXPERIMENTS.md §Perf extras).
        """
        cfg = self.cfg
        dtype = dtype or self.param_dtype
        caches = []
        for spec in self.specs:
            if spec.kind == "attn":
                shape = (self.n_rep, bsz, max_len, cfg.n_kv_heads, cfg.hd)
                if self.kv_dtype == "int8":
                    sshape = shape[:-1] + (1,)
                    c = {
                        "k": jnp.zeros(shape, jnp.int8),
                        "v": jnp.zeros(shape, jnp.int8),
                        "k_scale": jnp.zeros(sshape, jnp.bfloat16),
                        "v_scale": jnp.zeros(sshape, jnp.bfloat16),
                    }
                else:
                    c = {
                        "k": jnp.zeros(shape, dtype),
                        "v": jnp.zeros(shape, dtype),
                    }
            elif spec.kind == "mamba":
                c = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (self.n_rep,) + x.shape),
                    ssm_mod.init_mamba_cache(bsz, cfg.d_model,
                                             cfg.mamba_d_state,
                                             cfg.mamba_d_conv,
                                             cfg.mamba_expand, dtype))
            else:
                c = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (self.n_rep,) + x.shape),
                    rwkv_mod.init_rwkv_cache(bsz, cfg.d_model, cfg.n_heads,
                                             cfg.hd, dtype))
            if spec.cross:
                c = dict(c) if isinstance(c, dict) else {"inner": c}
                # cross-attention K/V over the memory are filled by prefill
            caches.append(c)
        return caches

    def _layer_step(self, p, spec: LayerSpec, x, cache, memory, cos_sin,
                    pos):
        """One-token layer step. x: [B,1,d]; cache: this layer's slice."""
        cfg = self.cfg
        new_cache = dict(cache)
        if spec.kind == "attn":
            with jax.named_scope("attn"):
                b = x.shape[0]
                h = rms_norm(x, p["mixer"]["norm"], cfg.norm_eps)
                q = jnp.einsum("bsd,de->bse", h, p["mixer"]["wq"].astype(x.dtype))
                k = jnp.einsum("bsd,de->bse", h, p["mixer"]["wk"].astype(x.dtype))
                v = jnp.einsum("bsd,de->bse", h, p["mixer"]["wv"].astype(x.dtype))
                if cfg.qkv_bias and "bq" in p["mixer"]:
                    q = q + p["mixer"]["bq"].astype(x.dtype)
                    k = k + p["mixer"]["bk"].astype(x.dtype)
                    v = v + p["mixer"]["bv"].astype(x.dtype)
                q = q.reshape(b, 1, cfg.n_heads, cfg.hd)
                k = k.reshape(b, 1, cfg.n_kv_heads, cfg.hd)
                v = v.reshape(b, 1, cfg.n_kv_heads, cfg.hd)
                # pos: scalar (whole batch at one cursor) or [B] vector
                # (continuous batching: per-slot cursors)
                pos_vec = jnp.asarray(pos)
                if pos_vec.ndim == 0:
                    positions = jnp.full((b, 1), pos_vec)
                    upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
                        buf, val.astype(buf.dtype), pos, axis=1)
                else:
                    positions = pos_vec[:, None]
                    upd = lambda buf, val: jax.vmap(
                        lambda bb, vv, pp:
                        jax.lax.dynamic_update_slice_in_dim(
                            bb, vv.astype(bb.dtype), pp, axis=0)
                    )(buf, val, pos_vec)
                q = apply_rope(q, cos_sin, positions)
                k = apply_rope(k, cos_sin, positions)
                if "k_scale" in cache:        # int8-quantized cache
                    kq, ks = _quantize_kv(k)
                    vq, vs = _quantize_kv(v)
                    with jax.named_scope("kv_write"):
                        kc = upd(cache["k"], kq)
                        vc = upd(cache["v"], vq)
                        ksc = upd(cache["k_scale"], ks)
                        vsc = upd(cache["v_scale"], vs)
                    k_deq = kc.astype(x.dtype) * ksc.astype(x.dtype)
                    v_deq = vc.astype(x.dtype) * vsc.astype(x.dtype)
                    o = attn.decode_attention(q, k_deq, v_deq, pos_vec + 1,
                                              sliding_window=cfg.sliding_window)
                    new_cache.update({"k": kc, "v": vc,
                                      "k_scale": ksc, "v_scale": vsc})
                else:
                    with jax.named_scope("kv_write"):
                        kc = upd(cache["k"], k)
                        vc = upd(cache["v"], v)
                    o = attn.decode_attention(q, kc, vc, pos_vec + 1,
                                              sliding_window=cfg.sliding_window)
                    new_cache.update({"k": kc, "v": vc})
                o = o.reshape(b, 1, cfg.n_heads * cfg.hd)
                x = x + jnp.einsum("bse,ed->bsd", o,
                                   p["mixer"]["wo"].astype(x.dtype))
        elif spec.kind == "mamba":
            h = rms_norm(x, p["mixer"]["norm"], cfg.norm_eps)
            inner = {k2: cache[k2] for k2 in ("conv", "ssm")}
            o, inner = ssm_mod.mamba_step(p["mixer"], h, inner)
            x = x + o
            new_cache.update(inner)
        else:  # rwkv
            h = rms_norm(x, p["mixer"]["norm"], cfg.norm_eps)
            inner = {k2: cache[k2] for k2 in ("last_x", "state")}
            o, inner = rwkv_mod.rwkv_step(p["mixer"], h, cache=inner,
                                          n_heads=cfg.n_heads,
                                          head_dim=cfg.hd,
                                          norm_eps=cfg.norm_eps)
            x = x + o
            new_cache.update(inner)
        if spec.cross and memory is not None:
            x = x + self._cross_attn(p["cross"], x, memory)
        with jax.named_scope("ffn"):
            y, _ = self._ffn(p, spec, x)
        return x + y, new_cache

    def decode_step(self, params, cache, tokens, pos, memory=None):
        """Generate logits for one new token.

        tokens: [B, 1] int32; pos: scalar int (current cache length).
        ``memory``: optional [B, M, d] cross-attention memory (VLM
        frontend / encoder output), already projected/encoded.

        Named scopes (op metadata only, read from a device trace):
        ``layers`` (the scan over blocks), ``attn`` (the attention half
        of a block) with ``kv_write`` (the cache updates) inside it,
        ``ffn``, and ``unembed`` (final norm and logits).
        """
        cfg = self.cfg
        x = embed(params["embed"], tokens).astype(self.param_dtype)
        cos_sin = self._rope(self.max_seq)

        new_caches = []
        for j, spec in enumerate(self.specs):
            def body(x, scanned, spec=spec):
                lp, c = scanned
                x, c2 = self._layer_step(lp, spec, x, c, memory, cos_sin,
                                         pos)
                return x, c2
            with jax.named_scope("layers"):
                x, nc = jax.lax.scan(body, x,
                                     (params["blocks"][j], cache[j]))
            new_caches.append(nc)

        with jax.named_scope("unembed"):
            x = rms_norm(x, params["final_norm"], cfg.norm_eps)
            table = params.get("lm_head", params["embed"])
            return unembed(x, table), new_caches

    def encode_memory(self, params, frontend):
        """Prepare cross-attention memory once per request batch."""
        mem = self._frontend_memory(params, frontend, self.param_dtype)
        if mem is not None and self.cfg.is_encdec:
            mem = self._encode(params, mem)
        return mem
