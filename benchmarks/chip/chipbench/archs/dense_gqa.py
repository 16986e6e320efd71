"""Architecture ``dense_gqa``: the plain reference of a dense decoder
with grouped-query attention, its lower-precision control, and the work
it needs, counted from its shapes.

The reference is written out in ``jax.numpy`` with no cache, no
batching and no kernels, in float32 with full-precision matmuls.  It
follows the equations the program implements; where those depart from
the published model the configuration file lists the departure
(``departures``), and the reference keeps the program's form so that
the two can be compared:

    x      = E[token]
    per layer:
      h    = rmsnorm(x) * g_attn
      q,k,v = h Wq, h Wk, h Wv            (heads of size hd)
      q,k  = rope(q), rope(k)             (rotate-half, theta, all dims)
      a    = softmax(q k^T / sqrt(hd), causal) v   (kv heads shared by
                                                    n_heads / n_kv_heads)
      x    = x + a Wo
      h    = rmsnorm(x) * g_ffn
      x    = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * g_final) T^T   (T: lm_head, or E if tied)

The weights are drawn from the seed by the recipe the configuration
file names (``weights``), on the device, without the program.

The control is the same model computed one precision lower than the
configuration states: weights rounded to float8 (e4m3, one scale per
output column) and activations and matmul inputs in bfloat16.

The counts (``Shapes``) are the operations and bytes the mathematics
asks for, not what a given program does: a step that reads the whole
KV cache where only the valid entries are needed, or unembeds a
position whose logits nobody uses, does more than is counted here.  So
a program that stops wasting work moves the shares computed from these
counts, and the counts themselves stay put.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..numerics import DTYPES, F32, fp8, weight_seed
from ..work import DTYPE_BYTES


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def weight_recipe(m: dict, dtype, seed: int) -> dict:
    """Weights as the recipe ``fan_in_normal_v1`` draws them: one key
    from the seed, split once per matrix in the order below; each
    matrix ``normal(float32) / sqrt(fan_in)`` cast to ``dtype``; the
    layers' matrices drawn whole, stacked ``[n_layers, ...]``; norm
    gains 1.

    Order: embed [V, d]; lm_head [V, d] (untied only); wq [L, d, H hd];
    wk, wv [L, d, Hkv hd]; wo [L, H hd, d]; w_gate, w_up [L, d, f];
    w_down [L, f, d].
    """
    return _draw(m, dtype, jax.random.PRNGKey(weight_seed(seed)))


def _draw(m: dict, dtype, key) -> dict:
    d, f, v, nl = m["d_model"], m["d_ff"], m["vocab_size"], m["n_layers"]
    hd = _hd(m)

    def normal(shape, fan_in):
        nonlocal key
        key, sub = jax.random.split(key)
        std = 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(sub, shape, dtype=F32) * std).astype(dtype)

    p = {"embed": normal((v, d), d), "final_norm": jnp.ones((d,), dtype)}
    if not m.get("tie_embeddings", False):
        p["lm_head"] = normal((v, d), d)
    p["attn_norm"] = jnp.ones((nl, d), dtype)
    p["wq"] = normal((nl, d, m["n_heads"] * hd), d)
    p["wk"] = normal((nl, d, m["n_kv_heads"] * hd), d)
    p["wv"] = normal((nl, d, m["n_kv_heads"] * hd), d)
    p["wo"] = normal((nl, m["n_heads"] * hd, d), m["n_heads"] * hd)
    p["ffn_norm"] = jnp.ones((nl, d), dtype)
    p["w_gate"] = normal((nl, d, f), d)
    p["w_up"] = normal((nl, d, f), d)
    p["w_down"] = normal((nl, f, d), f)
    return p


def shardings(shapes: dict, mesh) -> dict:
    """Each weight split over every device of ``mesh`` along its
    largest divisible dimension after the layer one, else replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(mesh.axis_names)
    n = mesh.size

    def one(name, leaf):
        spec = [None] * leaf.ndim
        lead = 1 if name not in ("embed", "lm_head", "final_norm") else 0
        dims = sorted(range(lead, leaf.ndim), key=lambda i: -leaf.shape[i])
        for i in dims:
            if leaf.shape[i] % n == 0 and leaf.shape[i] >= n:
                spec[i] = axes
                break
        return NamedSharding(mesh, P(*spec))

    return {k: one(k, v) for k, v in shapes.items()}


def make_weights(m: dict, dtype: str, seed: int, mesh):
    """The recipe's weights placed over ``mesh``, in one jitted call.
    The key is an argument of that call, so one compiled program
    serves every seed."""
    dt = DTYPES[dtype]
    key = jax.random.PRNGKey(weight_seed(seed))
    shapes = jax.eval_shape(lambda k: _draw(m, dt, k), key)
    return jax.jit(lambda k: _draw(m, dt, k),
                   out_shardings=shardings(shapes, mesh))(key)


def _rope_tables(hd: int, n: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = np.einsum("p,f->pf", np.arange(n), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def hidden(m: dict, p: dict, tokens, *, control: bool = False):
    """Final-norm hidden states [B, S, d] for token ids [B, S]."""
    act = jnp.bfloat16 if control else F32
    hd, hq, hkv = _hd(m), m["n_heads"], m["n_kv_heads"]
    eps = m.get("norm_eps", 1e-5)
    b, s = tokens.shape
    cos, sin = _rope_tables(hd, s, m.get("rope_theta", 10000.0))

    def w(x):
        return (fp8(x) if control else x.astype(F32)).astype(act)

    def mm(x, wt):
        return jnp.einsum("bsd,de->bse", x, wt,
                          preferred_element_type=F32).astype(act)

    def norm(x, g):
        xf = x.astype(F32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * g.astype(F32)).astype(act)

    def rope(x):                            # [b, s, heads, hd]
        x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
        c, sn = cos[None, :, None, :], sin[None, :, None, :]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn],
                               -1).astype(act)

    mask = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = norm(x, lp["attn_norm"])
        q = rope(mm(h, w(lp["wq"])).reshape(b, s, hq, hd))
        q = q.reshape(b, s, hkv, hq // hkv, hd)
        k = rope(mm(h, w(lp["wk"])).reshape(b, s, hkv, hd))
        v = mm(h, w(lp["wv"])).reshape(b, s, hkv, hd)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        preferred_element_type=F32) / np.sqrt(hd)
        sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        a = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(act), v,
                       preferred_element_type=F32).astype(act)
        x = x + mm(a.reshape(b, s, hq * hd), w(lp["wo"]))
        h = norm(x, lp["ffn_norm"])
        g = mm(h, w(lp["w_gate"])).astype(F32)
        u = mm(h, w(lp["w_up"])).astype(F32)
        x = x + mm((jax.nn.silu(g) * u).astype(act), w(lp["w_down"]))
        return x, None

    layers = {k: p[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                "ffn_norm", "w_gate", "w_up", "w_down")}
    x = p["embed"][tokens]
    x = (fp8(x, -1) if control else x.astype(F32)).astype(act)
    x, _ = jax.lax.scan(layer, x, layers)
    return norm(x, p["final_norm"])


def logits(p: dict, x, *, control: bool = False, chunks: int = 8):
    """Logits [M, V] of hidden rows ``x`` [M, d], in float32.  The
    table is taken in ``chunks`` slices of the vocabulary, so that no
    float32 copy of the whole table is made."""
    t = p.get("lm_head", p["embed"])
    v, d = t.shape
    if v % chunks:
        chunks = 1
    tc = t.reshape(chunks, v // chunks, d)

    def one(_, tab):
        tab = (fp8(tab, -1).astype(jnp.bfloat16) if control
               else tab.astype(F32))
        return None, jnp.einsum("md,vd->mv", x.astype(tab.dtype), tab,
                                preferred_element_type=F32)

    _, out = jax.lax.scan(one, None, tc)
    return out.transpose(1, 0, 2).reshape(x.shape[0], v)


@dataclass(frozen=True)
class Shapes:
    """The work counts, from the ``"model"`` block of a configuration
    file: the field names of the program's ``ModelConfig``
    (``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads``, ``d_ff``,
    ``vocab_size``, ``head_dim``, ``tie_embeddings``, ``qkv_bias``).
    The FFN is SwiGLU (three matrices)."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int
    tie_embeddings: bool
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, model: dict, dtype: str) -> "Shapes":
        return cls(model["n_layers"], model["d_model"], model["n_heads"],
                   model["n_kv_heads"], model["d_ff"], model["vocab_size"],
                   _hd(model), bool(model.get("tie_embeddings", False)),
                   bool(model.get("qkv_bias", False)), DTYPE_BYTES[dtype])

    # -- parameters --------------------------------------------------- #
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return attn + 3 * d * self.d_ff

    def layer_params(self) -> int:
        bias = ((self.n_heads + 2 * self.n_kv_heads) * self.head_dim
                if self.qkv_bias else 0)
        return self.layer_matmul_params() + bias + 2 * self.d_model

    def table_params(self) -> int:
        return self.vocab_size * self.d_model

    def total_params(self) -> int:
        tables = self.table_params() * (1 if self.tie_embeddings else 2)
        return (tables + self.n_layers * self.layer_params()
                + self.d_model)

    def param_bytes(self) -> int:
        return self.total_params() * self.dtype_bytes

    def kv_bytes_per_token(self) -> int:
        return (self.n_layers * 2 * self.n_kv_heads * self.head_dim
                * self.dtype_bytes)

    # -- one decode step ---------------------------------------------- #
    def step_flops(self, step) -> int:
        """A ``records.StepRecord``'s tokens, one per admitted slot,
        attending over the slots' valid cache entries, of which
        ``n_logits`` positions need logits."""
        matmul = 2 * step.n_tokens * self.n_layers * self.layer_matmul_params()
        attn = 4 * self.n_layers * self.n_heads * self.head_dim * step.sum_ctx
        unembed = 2 * step.n_logits * self.d_model * self.vocab_size
        return matmul + attn + unembed

    def step_bytes(self, step) -> int:
        """Weights read once (of the embedding table only the rows of
        the step's tokens), the valid KV entries read and the new ones
        written.  The unembedding table is read only where some
        position needs logits."""
        if step.n_tokens == 0:
            return 0
        b = self.dtype_bytes
        weights = (self.n_layers * self.layer_params() + self.d_model) * b
        rows = step.n_tokens * self.d_model * b
        table = self.table_params() * b if step.n_logits else 0
        kv = (step.sum_ctx + step.n_tokens) * self.kv_bytes_per_token()
        return weights + rows + table + kv
