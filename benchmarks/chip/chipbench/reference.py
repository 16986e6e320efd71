"""Plain reference of the served model, and its lower-precision control.

A dense decoder with grouped-query attention, written out in
``jax.numpy`` with no cache, no batching and no kernels, in float32
with full-precision matmuls.  It follows the equations the program
implements; where those depart from the published model the
configuration file lists the departure (``departures``), and the
reference keeps the program's form so that the two can be compared:

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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def weight_seed(seed: int) -> np.uint32:
    """The number the weights' key is made from: the run's seed modulo
    2**32, as an unsigned 32-bit integer, so that one compiled program
    draws the weights of every seed."""
    return np.uint32(seed % 2**32)


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


def _fp8(w, axis: int = -2):
    """float8 e4m3 with one absmax scale per output column (the input
    dimension ``axis`` is reduced)."""
    w = w.astype(F32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.maximum(scale, 1e-12)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


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
        return (_fp8(x) if control else x.astype(F32)).astype(act)

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
    x = (_fp8(x, -1) if control else x.astype(F32)).astype(act)
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
        tab = (_fp8(tab, -1).astype(jnp.bfloat16) if control
               else tab.astype(F32))
        return None, jnp.einsum("md,vd->mv", x.astype(tab.dtype), tab,
                                preferred_element_type=F32)

    _, out = jax.lax.scan(one, None, tc)
    return out.transpose(1, 0, 2).reshape(x.shape[0], v)
