"""Launch-layer tests on the CPU: the full-width serving path at a
reduced size, sharded serving on four virtual devices, the compile
cache location, and that the chip smoke refuses to run off the chip."""
import importlib.util
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")


def _small(arch: str):
    """``arch``'s layer pattern at widths a CPU test can afford."""
    return replace(get_config(arch), n_layers=2, d_model=128, n_heads=4,
                   n_kv_heads=2, d_ff=256, vocab_size=1024)


def test_start_server_serves_and_decode_matches_forward():
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import start_server

    cfg = _small("minitron_4b")
    loop, requests = start_server(cfg, make_local_mesh(1, 1), n_requests=4,
                                  max_prompt=40, new_tokens=5, seed=3)
    assert sorted({len(r.prompt) for r in requests}) == [16, 24, 32, 40]
    assert loop.slots == 4 and loop.max_len == 128
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(loop.cache))
    done = loop.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3]
    assert all(len(r.out) == 5 for r in done)
    assert loop._step._cache_size() == 1       # traced positions
    fwd = jax.jit(lambda p, t: loop.model.forward(p, t, last_only=True)[0])
    for r in done:
        ref = np.asarray(fwd(loop.params, jnp.asarray(r.prompt[None])))[0, -1]
        assert np.argmax(r.prompt_logits) == np.argmax(ref)
        assert r.out[0] == np.argmax(r.prompt_logits)
        np.testing.assert_allclose(r.prompt_logits, ref, atol=0.05)


_SHARDED = r"""
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import start_server

cfg = replace(get_config("llama3_8b"), n_layers=2, d_model=128, n_heads=8,
              n_kv_heads=4, d_ff=256, vocab_size=1024)
outs, logits = {}, {}
for n in (1, 4):
    loop, _ = start_server(cfg, make_local_mesh(1, n), n_requests=4,
                           max_prompt=20, new_tokens=6,
                           param_dtype=jnp.float32)
    if n == 4:
        total = sum(x.nbytes for x in jax.tree.leaves(loop.params))
        per_dev = {d: 0 for d in jax.devices()}
        for x in jax.tree.leaves(loop.params):
            for s in x.addressable_shards:
                per_dev[s.device] += s.data.nbytes
        assert max(per_dev.values()) < 0.3 * total, per_dev
    done = sorted(loop.run(), key=lambda r: r.rid)
    outs[n] = [r.out for r in done]
    logits[n] = np.stack([r.prompt_logits for r in done])
assert outs[1] == outs[4], outs
np.testing.assert_allclose(logits[1], logits[4], atol=1e-4)
print("SHARDED_OK")
"""


def test_sharded_serving_matches_one_device():
    """(data=1, model=4) tensor parallelism serves what one device
    serves: the same greedy tokens, about a quarter of the params on
    each device.  Four virtual CPU devices, in a child process."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", _SHARDED],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_OK" in proc.stdout


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir is None   # set nothing


def test_compile_cache_defaults_inside_checkout(monkeypatch,
                                                restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path       # fixed, not per run


def test_meshes_use_auto_axes():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(1, 1)
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_dryrun_import_sets_no_flags():
    code = ("import os; before = os.environ.get('XLA_FLAGS'); "
            "import repro.launch.dryrun; "
            "assert os.environ.get('XLA_FLAGS') == before")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC, env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_chip_smoke_refuses_cpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    assert jax.devices()[0].platform == "cpu"
    rc = chip_smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
