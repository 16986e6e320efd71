"""Compile-only rehearsals for a described TPU v5e (no chip attached).

Each test compiles a main-path program at real widths with the TPU
compiler for a ``v5e:2x2`` topology that is described, not attached:
what the chip's compiler would refuse (a kernel's tiling, a program
that does not fit HBM, a sharding it cannot partition) fails here.
Nothing runs, so nothing here is a chip measurement.

The topology is described inside a module-scoped fixture, never while
the module is imported: only one process may load the TPU runtime, and
under several pytest workers only the worker given this file does.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import serve_layout
from repro.runtime.serve_loop import decode_program

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _decode_program(cfg, mesh, *, slots: int, max_len: int):
    """The serving decode step, the program ``ServeLoop`` runs, lowered
    for ``mesh`` with params and cache placed as ``build_server`` places
    them.  Returns (compiled, param specs)."""
    model, param_sh, cache_sh = serve_layout(cfg, mesh, slots=slots,
                                             max_len=max_len)
    params = jax.tree.map(lambda l, s: _spec(l.shape, l.dtype, s),
                          jax.eval_shape(lambda: model.init(0)), param_sh)
    cache = jax.tree.map(lambda l, s: _spec(l.shape, l.dtype, s),
                         jax.eval_shape(lambda: model.init_cache(slots,
                                                                 max_len)),
                         cache_sh)
    rep = NamedSharding(mesh, P())
    compiled = decode_program(model, cache_sh).lower(
        params, cache, _spec((slots, 1), jnp.int32, rep),
        _spec((slots,), jnp.int32, rep)).compile()
    return compiled, params


def test_flash_kernel_minitron_widths(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhsd
    cfg = get_config("minitron_4b")
    s, hd = 2048, cfg.hd
    q = _spec((cfg.n_heads, s, hd), jnp.bfloat16, one_chip)
    kv = _spec((cfg.n_kv_heads, s, hd), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention_bhsd).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv_kernel_rwkv6_widths(one_chip):
    from repro.kernels.rwkv_wkv import wkv_bhsd
    cfg = get_config("rwkv6_1b6")
    b, h, s, hd = 1, cfg.n_heads, 512, cfg.hd
    x = _spec((b, h, s, hd), jnp.bfloat16, one_chip)
    u = _spec((h, hd), jnp.bfloat16, one_chip)
    s0 = _spec((b, h, hd, hd), jnp.float32, one_chip)
    compiled = jax.jit(wkv_bhsd).lower(x, x, x, x, u, s0).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_minitron_decode_fits_one_chip(topo):
    cfg = get_config("minitron_4b")
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    compiled, _ = _decode_program(cfg, mesh, slots=8, max_len=2048)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 10e9      # published widths
    assert _device_bytes(mem) <= HBM_BYTES


def test_llama3_decode_sharded_four_chips(topo):
    cfg = get_config("llama3_8b")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    compiled, params = _decode_program(cfg, mesh, slots=8, max_len=1024)
    leaves = jax.tree.leaves(params)
    total = sum(l.size * l.dtype.itemsize for l in leaves)
    per_device = sum(math.prod(l.sharding.shard_shape(l.shape))
                     * l.dtype.itemsize for l in leaves)
    assert total > 15e9                           # published widths
    assert 0.24 * total <= per_device <= 0.26 * total
    assert _device_bytes(compiled.memory_analysis()) <= HBM_BYTES


def test_granite_decode_keeps_cache_sharded_by_head(topo):
    """The four-chip serving step keeps each chip's KV heads where the
    column-split K/V projections produce them: no all-gather of the
    whole stacked cache, 2 of 8 KV heads a chip."""
    cfg = get_config("granite_8b")
    slots, max_len = 32, 768
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    model, _, cache_sh = serve_layout(cfg, mesh, slots=slots,
                                      max_len=max_len)
    compiled, _ = _decode_program(cfg, mesh, slots=slots, max_len=max_len)
    whole = (f"bf16[{cfg.n_layers},{slots},{max_len},{cfg.n_kv_heads},"
             f"{cfg.hd}]")
    gathers = [line for line in compiled.as_text().splitlines()
               if "all-gather" in line and whole in line.split("=")[1]]
    assert not gathers, gathers[:2]
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    for leaf, sh in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_sh)):
        assert sh.shard_shape(leaf.shape)[3] == cfg.n_kv_heads // 4
    assert _device_bytes(compiled.memory_analysis()) < 6e9
