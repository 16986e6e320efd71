"""Pipeline-parallel runner tests.

The GPipe schedule needs multiple devices, so the numerical checks run
in a subprocess with 4 host-platform CPU devices (the main test process
keeps its single real device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

_PROGRAM = r"""
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.runtime.pipeline import pipeline_apply, stack_stage_params

assert len(jax.devices("cpu")) == 4, jax.devices()
mesh = make_mesh((4,), ("stage",))
rng = np.random.default_rng(0)
D, B, S_STAGES = 16, 8, 4

stages = [
    {"w": jnp.asarray(rng.normal(size=(D, D)) / np.sqrt(D), jnp.float32),
     "b": jnp.asarray(rng.normal(size=(D,)) * 0.1, jnp.float32)}
    for _ in range(S_STAGES)
]
params = stack_stage_params(stages)
x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

def stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])

# sequential reference
ref = x
for st in stages:
    ref = stage_fn(st, ref)

with mesh:
    out = jax.jit(
        lambda p, x: pipeline_apply(stage_fn, p, x, mesh=mesh,
                                    microbatches=4))(params, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)
print("FWD_OK")

# gradients through the pipeline == gradients through the sequential net
def loss_pipe(p, x):
    return (pipeline_apply(stage_fn, p, x, mesh=mesh,
                           microbatches=4) ** 2).mean()

def loss_seq(stages, x):
    y = x
    for st in stages:
        y = stage_fn(st, y)
    return (y ** 2).mean()

with mesh:
    g_pipe = jax.jit(jax.grad(loss_pipe))(params, x)
g_seq = jax.grad(loss_seq)(stages, x)
g_seq = stack_stage_params(g_seq)
for a, b in zip(jax.tree.leaves(g_pipe), jax.tree.leaves(g_seq)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-4)
print("GRAD_OK")

# uneven microbatches (fill/drain correctness): mu != n_stages
with mesh:
    out2 = jax.jit(
        lambda p, x: pipeline_apply(stage_fn, p, x, mesh=mesh,
                                    microbatches=8))(params, x)
np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                           atol=1e-5, rtol=1e-5)
print("MB_OK")
"""


def test_pipeline_forward_backward_multi_device():
    # the child never needs an accelerator: pin it to virtual CPU
    # devices so it does not try to load the TPU runtime
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (SRC, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _PROGRAM],
        capture_output=True, text=True, timeout=480, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FWD_OK" in proc.stdout
    assert "GRAD_OK" in proc.stdout
    assert "MB_OK" in proc.stdout
