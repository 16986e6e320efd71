"""Sharding-rule tests: divisibility fallbacks and policy coverage —
every parameter of every arch gets a legal PartitionSpec on the
production mesh shape (validated against array dims, no devices
needed beyond the local one)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.mesh import make_local_mesh
from repro.launch.sharding import cache_sharding_rules, param_sharding_rules
from repro.models import LM


class FakeMesh:
    """Duck-typed mesh exposing only what the rules consume."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


PROD = FakeMesh({"data": 16, "model": 16})
PROD_MP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _leaves_with_specs(arch, mesh, policy):
    cfg = get_smoke_config(arch)
    model = LM(cfg)
    shapes = jax.eval_shape(lambda: model.init(0))
    specs = param_sharding_rules(shapes, mesh, policy)
    return list(zip(jax.tree.leaves(shapes),
                    jax.tree.leaves(
                        specs, is_leaf=lambda x: isinstance(x, P))))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [PROD, PROD_MP])
@pytest.mark.parametrize("policy", ["tp", "fsdp_tp"])
def test_specs_are_legal(arch, mesh, policy):
    def axsize(ax):
        if ax is None:
            return 1
        if isinstance(ax, tuple):
            return int(np.prod([mesh.shape[a] for a in ax]))
        return mesh.shape[ax]

    for leaf, spec in _leaves_with_specs(arch, mesh, policy):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            assert dim % axsize(ax) == 0, (arch, leaf.shape, spec)


def test_fsdp_tp_shards_more_than_tp():
    """fsdp_tp must strictly increase the number of sharded dims on
    the big matrices (that's the point of the policy)."""
    def sharded_dims(policy):
        total = 0
        for leaf, spec in _leaves_with_specs("llama3_8b", PROD, policy):
            total += sum(1 for ax in tuple(spec) if ax is not None)
        return total

    assert sharded_dims("fsdp_tp") > sharded_dims("tp")


def test_norms_replicated():
    for leaf, spec in _leaves_with_specs("llama3_8b", PROD, "fsdp_tp"):
        if len(leaf.shape) == 1 and leaf.shape[0] <= 64:
            assert all(ax is None for ax in tuple(spec))


def test_fsdp_policy_shards_over_all_axes():
    """Pure FSDP: exactly one dim sharded over the combined axes, no
    tensor parallelism anywhere (EXPERIMENTS.md §Perf iteration 4)."""
    for leaf, spec in _leaves_with_specs("qwen25_32b", PROD, "fsdp"):
        axes = [ax for ax in tuple(spec) if ax is not None]
        assert len(axes) <= 1
        for ax in axes:
            assert isinstance(ax, tuple)  # the combined-axes tuple
            assert set(ax) <= {"pod", "data", "model"}


def test_fsdp_batch_sharding_uses_model_axis():
    from repro.launch.sharding import batch_sharding

    mesh = make_local_mesh(1, 1)  # real mesh with data/model axes
    sh = batch_sharding(mesh, 256, policy="fsdp")
    assert tuple(sh.spec)[0] == ("data", "model")
    sh2 = batch_sharding(mesh, 256, policy="fsdp_tp")
    assert tuple(sh2.spec)[0] in ("data", ("data",))  # P normalizes 1-tuples


KV_LEAVES = ("k", "v", "k_scale", "v_scale")

# (mesh, published or smoke config, cache length)
CACHE_CASES = {
    "heads_over_model": (FakeMesh({"data": 1, "model": 4}), get_config, 768),
    "model_of_one": (FakeMesh({"data": 1, "model": 1}), get_config, 768),
    "smoke_heads": (FakeMesh({"data": 1, "model": 4}), get_smoke_config, 768),
    "long_cache": (FakeMesh({"data": 1, "model": 4}), get_config, 2048),
}


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("case", sorted(CACHE_CASES))
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_sharding_rules(arch, case, kv_dtype):
    """Attention K/V (and their int8 scales) lie by KV head on "model"
    when the heads divide it, by sequence when the cache is longer than
    1024 tokens, unsharded on "model" on a mesh whose model axis is 1;
    Mamba and RWKV leaves keep their rank-based specs."""
    mesh, get, max_len = CACHE_CASES[case]
    slots, msize = 4, mesh.shape["model"]
    model = LM(get(arch), kv_dtype=kv_dtype)
    shapes = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    specs = cache_sharding_rules(shapes, mesh, slots)
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(spec_leaves)
    for (path, leaf), spec in zip(flat, spec_leaves):
        key, shape = path[-1].key, leaf.shape
        want = [None] * len(shape)
        want[1] = "data"                              # slots over data
        if key in KV_LEAVES:
            hkv = shape[3]
            if max_len > 1024:
                want[2] = "model"
            elif msize > 1 and hkv % msize == 0:
                want[3] = "model"
            if case == "heads_over_model":
                assert want[3] == "model", (arch, hkv)
        elif key in ("conv", "ssm") and shape[2] % msize == 0:
            want[2] = "model"
        else:
            assert key in ("conv", "ssm", "last_x", "state"), key
        assert spec == P(*want), (arch, key, shape, spec)
