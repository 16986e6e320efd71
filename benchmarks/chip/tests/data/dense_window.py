"""A test architecture, ``dense_window``: ``dense_gqa`` with every layer's
attention held to a sliding window, the last ``sliding_window``
positions, its own included.  It lives only here, beside its
configuration ``tiny_window.json``, to show that an architecture enters
the harness as new files.

Its weights and unembedding are ``dense_gqa``'s.  Its hidden states and
its work counts are its own, and say so on standard error when the
harness calls them.
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.archs import dense_gqa
from chipbench.numerics import F32, fp8

make_weights = dense_gqa.make_weights
logits = dense_gqa.logits


def hidden(m: dict, p: dict, tokens, *, control: bool):
    """Final-norm hidden states [B, S, d] for token ids [B, S]."""
    print("dense_window: hidden", file=sys.stderr)
    act = jnp.bfloat16 if control else F32
    hd, hq, hkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    eps, window = m["norm_eps"], m["sliding_window"]
    b, s = tokens.shape
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, hd, 2) / hd))
    ang = np.arange(s)[:, None] * inv[None, :]
    cos, sin = (jnp.asarray(f(ang), F32)[None, :, None, :]
                for f in (np.cos, np.sin))
    q_pos, k_pos = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = jnp.asarray((k_pos <= q_pos) & (k_pos > q_pos - window))

    def mm(x, wt):
        wt = (fp8(wt) if control else wt.astype(F32)).astype(act)
        return jnp.einsum("bsd,de->bse", x, wt,
                          preferred_element_type=F32).astype(act)

    def norm(x, g):
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * g.astype(F32)).astype(act)

    def rope(x):
        x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(act)

    def layer(x, lp):
        h = norm(x, lp["attn_norm"])
        q = rope(mm(h, lp["wq"]).reshape(b, s, hq, hd))
        k = rope(mm(h, lp["wk"]).reshape(b, s, hkv, hd))
        v = mm(h, lp["wv"]).reshape(b, s, hkv, hd)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(b, s, hkv, -1, hd), k,
                        preferred_element_type=F32) / np.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(act), v,
                       preferred_element_type=F32).astype(act)
        x = x + mm(a.reshape(b, s, hq * hd), lp["wo"])
        h = norm(x, lp["ffn_norm"])
        g = mm(h, lp["w_gate"]).astype(F32)
        u = mm(h, lp["w_up"]).astype(F32)
        return x + mm((jax.nn.silu(g) * u).astype(act), lp["w_down"]), None

    layers = {k: p[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "ffn_norm", "w_gate", "w_up", "w_down")}
    x = p["embed"][tokens]
    x = (fp8(x, -1) if control else x.astype(F32)).astype(act)
    x, _ = jax.lax.scan(layer, x, layers)
    return norm(x, p["final_norm"])


@dataclasses.dataclass(frozen=True)
class Shapes(dense_gqa.Shapes):
    """``dense_gqa``'s counts with each slot's attention reads held to
    the window."""
    window: int = 0

    @classmethod
    def of(cls, model: dict, dtype: str) -> "Shapes":
        print("dense_window: Shapes.of", file=sys.stderr)
        dense = dense_gqa.Shapes.of(model, dtype)
        return cls(**dataclasses.asdict(dense), window=model["sliding_window"])

    def _windowed(self, step):
        return dataclasses.replace(step, ctx_lens=tuple(
            min(c, self.window) for c in step.ctx_lens))

    def step_flops(self, step) -> int:
        return super().step_flops(self._windowed(step))

    def step_bytes(self, step) -> int:
        return super().step_bytes(self._windowed(step))
