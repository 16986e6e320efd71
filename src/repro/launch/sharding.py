"""Parameter / activation / cache sharding rules.

Two policies:

* ``tp`` — tensor parallelism over the "model" axis only; parameters
  replicated across data (small models).
* ``fsdp_tp`` — 2-D sharding: "model" shards the TP dimension and
  ("pod","data") shard a second dimension FSDP-style (big models; XLA
  inserts per-layer all-gathers inside the layer scan).

Rules are name-based over the param tree paths produced by
``repro.models.LM``; any dimension not divisible by the axis size falls
back to replication (``_shard_if_divisible``), which keeps every
(arch × mesh) combination lowerable.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "param_sharding_rules",
    "tree_shardings",
    "batch_sharding",
    "cache_sharding_rules",
    "cache_shardings",
    "make_shard_act",
    "pick_policy",
]


def pick_policy(total_params: int) -> str:
    """fsdp_tp for anything that meaningfully stresses 16 GiB chips:
    f32 optimizer state is 16 B/param, so ≥3 B params ⇒ ≥48 GB of
    optimizer state — must be sharded over data axes too (ZeRO)."""
    return "fsdp_tp" if total_params >= 3e9 else "tp"


def _axsize(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _shard_if_divisible(mesh: Mesh, shape, *axes):
    """PartitionSpec with per-dim fallback to None on non-divisibility."""
    spec = []
    for dim, ax in zip(shape, axes):
        if ax is not None and dim % _axsize(mesh, ax) == 0:
            spec.append(ax)
        else:
            spec.append(None)
    return P(*spec)


def _rule(path: str, shape, mesh: Mesh, policy: str, fsdp):
    """PartitionSpec for one parameter. ``fsdp`` = ('pod','data') axes
    used for the second shard dim under fsdp_tp (or None under tp).

    ``policy == "fsdp"``: pure FSDP — no tensor parallelism at all; the
    "model" axis joins the data axes, every parameter is sharded over
    the combined axes on its largest divisible dim, and the batch is
    sharded over everything.  Zero activation collectives; per-layer
    weight all-gathers only.  Only valid when the global batch divides
    the full mesh (enforced by the caller).
    """
    nd = len(shape)
    if policy == "fsdp":
        allax = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)
        # shard the largest divisible dim over the combined axes
        order = sorted(range(nd), key=lambda i: -shape[i])
        for i in order:
            if shape[i] % _axsize(mesh, allax) == 0 and shape[i] > 1:
                spec = [None] * nd
                spec[i] = allax
                return P(*spec)
        return P(*([None] * nd))
    d2 = fsdp if policy == "fsdp_tp" else None

    def spec(*axes):
        # pad with None for any leading stacked dims not covered
        axes = (None,) * (nd - len(axes)) + tuple(axes)
        return _shard_if_divisible(mesh, shape, *axes)

    leaf = path.split("/")[-1]
    if leaf in ("embed", "lm_head"):                 # [V, d]
        return spec("model", d2)
    if leaf in ("wq", "wk", "wv", "w_r", "w_k", "w_v", "w_g"):
        return spec(d2, "model")                     # [d, H*hd]
    if leaf in ("wo", "w_o"):
        return spec("model", d2)                     # [H*hd, d]
    is_moe = "/moe/" in path
    if leaf in ("w_gate", "w_up"):                   # moe: [(rep,) E, d, f]
        if is_moe and shape[-3] % _axsize(mesh, "model") == 0:
            # expert parallelism: whole experts per model-rank — kills
            # the per-layer all-reduce of [G,E,C,d] partial sums that
            # f-sharding causes (see EXPERIMENTS.md §Perf, olmoe cell)
            return spec("model", d2, None)
        return spec(d2, "model")
    if leaf == "w_down":                             # moe: [(rep,) E, f, d]
        if is_moe and shape[-3] % _axsize(mesh, "model") == 0:
            return spec("model", None, d2)
        return spec("model", d2)
    if leaf == "router":                             # [d, E]
        return spec(d2, None)
    if leaf == "in_proj":                            # [d, 2*d_in]
        return spec(d2, "model")
    if leaf in ("x_proj", "out_proj"):               # [d_in, *]
        return spec("model", d2)
    if leaf == "dt_proj":                            # [r, d_in]
        return spec(d2, "model")
    if leaf in ("conv_w",):                          # [K, d_in]
        return spec(None, "model")
    if leaf in ("a_log",):                           # [d_in, N]
        return spec("model", None)
    if leaf in ("dt_bias", "d_skip", "decay_base", "ln_x"):
        return spec("model")                         # [d_in] / [dh]
    if leaf == "decay_a":                            # [d, LORA]
        return spec(d2, None)
    if leaf == "decay_b":                            # [LORA, dh]
        return spec(None, "model")
    if leaf == "bonus_u":                            # [H, hd]
        return spec(None, None)
    if leaf == "frontend_proj":                      # [F, d]
        return spec(None, "model")
    if leaf in ("bq", "bk", "bv"):
        return spec("model")
    # norms, scalars, mixes
    return P(*([None] * nd))


def _tree_paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _tree_paths(v, f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _tree_paths(v, f"{prefix}/{i}")
    else:
        yield prefix, tree


def param_sharding_rules(shapes_tree, mesh: Mesh, policy: str = "tp"):
    """Pytree of PartitionSpec matching ``shapes_tree`` (of
    ShapeDtypeStruct or arrays)."""
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    fsdp = fsdp if fsdp else None

    def one(path, leaf):
        return _rule(path, leaf.shape, mesh, policy, fsdp)

    flat = list(_tree_paths(shapes_tree))
    specs = {p: one(p, l) for p, l in flat}

    def rebuild(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: rebuild(v, f"{prefix}/{k}") for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            out = [rebuild(v, f"{prefix}/{i}") for i, v in enumerate(tree)]
            return type(tree)(out)
        return specs[prefix]

    return rebuild(shapes_tree)


def tree_shardings(shapes_tree, mesh: Mesh, policy: str = "tp"):
    specs = param_sharding_rules(shapes_tree, mesh, policy)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def batch_sharding(mesh: Mesh, batch: int | None = None,
                   policy: str = "fsdp_tp"):
    """tokens/labels [B, S] sharded over the batch axes (replicated
    when the batch doesn't divide them, e.g. long_500k's batch of 1).
    Pure-FSDP policy shards the batch over every axis."""
    candidates = [tuple(a for a in ("pod", "data")
                        if a in mesh.axis_names)]
    if policy == "fsdp":
        candidates.insert(0, tuple(
            a for a in ("pod", "data", "model") if a in mesh.axis_names))
        candidates.insert(1, tuple(
            a for a in ("data", "model") if a in mesh.axis_names))
    for axes in candidates:
        if axes and (batch is None or batch % _axsize(mesh, axes) == 0):
            return NamedSharding(mesh, P(axes, None))
    return NamedSharding(mesh, P(None, None))


def frontend_sharding(mesh: Mesh, batch: int | None = None):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if batch is not None and (not dp or batch % _axsize(mesh, dp) != 0):
        return NamedSharding(mesh, P(None, None, None))
    return NamedSharding(mesh, P(dp, None, None))


_KV_LEAVES = ("k", "v", "k_scale", "v_scale")


def cache_sharding_rules(cache_tree, mesh: Mesh, batch: int):
    """Pytree of PartitionSpec matching the decode cache ``cache_tree``
    (of ShapeDtypeStruct or arrays), as ``LM.init_cache`` lays it out.

    * axis 1 (slots) over the data axes when the batch divides them;
    * attention ``k``/``v`` [rep, B, S, hkv, hd] and their int8 scales
      ``k_scale``/``v_scale`` [rep, B, S, hkv, 1]: a sequence longer
      than 1024 tokens over "model"; else the KV-head axis 3 over a
      "model" axis longer than one (a one-chip mesh keeps its layout)
      when the heads divide it.  The ``wk``/``wv`` rules split
      the K/V projections by column over "model", so each model rank
      computes a contiguous block of KV heads; keeping the cache on the
      same axis lets each rank write and read only its own heads, where
      a replicated cache is gathered whole every step;
    * Mamba ``ssm`` [rep, B, d_in, N] and ``conv`` [rep, B, K-1, d_in]:
      axis 2 over "model" when it divides; RWKV ``last_x`` [rep, B, d]
      and ``state`` [rep, B, H, hd, hd]: batch only.

    Attention leaves are found by their key, not their rank (RWKV's
    ``state`` is 5-D too); any dimension that does not divide its axis
    stays unsharded.
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    batch_ok = batch % dp_size == 0
    msize = mesh.shape["model"]

    def spec(path, leaf):
        shape = leaf.shape
        nd = len(shape)
        axes = [None] * nd
        if batch_ok and nd >= 2:
            axes[1] = dp
        if nd == 5 and shape[2] > 1024:
            # a long KV cache: the sequence over "model"
            if shape[2] % msize == 0:
                axes[2] = "model"
        elif path[-1].key in _KV_LEAVES and msize > 1:
            axes[3] = "model"          # KV heads, as wk/wv produce them
        elif nd == 4 and shape[2] % msize == 0:
            axes[2] = "model"          # mamba ssm d_in over model
        return _shard_if_divisible(mesh, shape, *axes)

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def cache_shardings(cache_tree, mesh: Mesh, batch: int):
    """Decode caches placed on ``mesh`` by ``cache_sharding_rules``:
    slots over the data axes, attention K/V by KV head over "model"
    (the axis the column-split ``wk``/``wv`` produce them on), a cache
    longer than 1024 tokens by sequence instead."""
    specs = cache_sharding_rules(cache_tree, mesh, batch)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_shard_act(mesh: Mesh, policy: str = "fsdp_tp"):
    """Constraint hook injected into the model.

    * residual activations: batch over data axes, sequence over
      "model" (Megatron SP convention),
    * logits: vocabulary over "model" — the [B, S, V] tensor must never
      be replicated across the TP group,
    * pure-FSDP policy: batch over every axis, nothing else sharded.
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if policy == "fsdp":
        allax = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)

        def shard_act_fsdp(x, kind="residual"):
            if x.ndim < 2:
                return x
            b = allax if x.shape[0] % _axsize(mesh, allax) == 0 else None
            spec = P(b, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))

        return shard_act_fsdp

    def shard_act(x, kind="residual"):
        bshard = dp if (dp and x.shape[0] % _axsize(mesh, dp) == 0) else None
        if kind == "mamba_din" and x.ndim == 3:      # [B, S, d_in]
            dshard = "model" if x.shape[-1] % msize == 0 else None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(bshard, None, dshard)))
        if kind == "moe_tokens" and x.ndim == 4:     # [G, E, C, d]
            eshard = "model" if x.shape[1] % msize == 0 else None
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(bshard, eshard, None, None)))
        if kind == "moe_hidden" and x.ndim == 4:     # [G, E, C, f]
            if x.shape[1] % msize == 0:              # expert parallelism
                spec = P(bshard, "model", None, None)
            else:
                fshard = "model" if x.shape[-1] % msize == 0 else None
                spec = P(bshard, None, None, fshard)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        if x.ndim != 3:
            return x
        if kind == "attn_in":
            # Megatron sequence parallelism: gather the sequence once at
            # attention entry (one [B,S,d] all-gather) so head-sharded
            # attention runs locally — instead of GSPMD gathering K/V
            # chunks per scan iteration (measured 25.8 GB vs 12.9 GB per
            # step on olmoe train)
            spec = P(bshard, None, None)
        elif kind == "logits":
            vshard = "model" if x.shape[-1] % msize == 0 else None
            spec = P(bshard, None, vshard)
        else:
            # Megatron-style sequence parallelism: residuals carried
            # between layers are sharded over "model" along the sequence
            # — without this, the layer-scan's saved carries alone
            # (n_layers × B·S·d) blow the 16 GiB HBM budget at
            # per-device batches ≥ 8·4k tokens.
            sshard = ("model" if x.shape[1] > 1
                      and x.shape[1] % msize == 0 else None)
            spec = P(bshard, sshard, None)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard_act
