"""Pipeline parallelism end-to-end: the paper's scheduler decides the
stage split; the GPipe runner executes it.

Runs on 4 host-platform devices (set before jax import), builds a
4-stage MLP "model", trains it a few steps with gradients flowing
through the pipeline (collective_permute transposes give the backward
schedule for free).

Run:  PYTHONPATH=src python examples/pipeline_training.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Platform, Processor, Workflow, schedule
from repro.launch.mesh import make_mesh
from repro.runtime.pipeline import pipeline_apply, stack_stage_params


def plan_stages(n_layers: int, n_stages: int) -> list[list[int]]:
    """Let the scheduler split a layer chain into pipeline stages."""
    wf = Workflow(name="mlp-chain")
    prev = None
    for i in range(n_layers):
        t = wf.add_task(work=1.0, mem=0.1, persistent=1.0,
                        label=f"layer{i}")
        if prev is not None:
            wf.add_edge(prev, t, 0.5)
        prev = t
    # memory: 2 layers of weights (1.0 each) + transient activations
    plat = Platform([Processor(f"d{i}", 1.0, n_layers / n_stages + 1.5)
                     for i in range(n_stages)], bandwidth=10.0)
    report = schedule(wf, plat, kprime=[n_stages])
    assert report.feasible, report.infeasibility
    res = report.best
    stages = [sorted(m) for m in res.quotient.members.values()]
    stages.sort(key=min)
    print(f"scheduler split {n_layers} layers into "
          f"{[len(s) for s in stages]} per stage "
          f"(makespan {res.makespan:.2f})")
    return stages


def main():
    n_layers, n_stages, d, batch = 8, 4, 32, 16
    stages = plan_stages(n_layers, n_stages)
    assert len(stages) == n_stages

    rng = np.random.default_rng(0)
    layers_per_stage = len(stages[0])
    params = stack_stage_params([
        {"w": jnp.asarray(
            rng.normal(size=(layers_per_stage, d, d)) / np.sqrt(d),
            jnp.float32)}
        for _ in range(n_stages)
    ])

    def stage_fn(p, x):
        def layer(x, w):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(layer, x, p["w"])
        return y

    mesh = make_mesh((n_stages,), ("stage",))
    x = jnp.asarray(rng.normal(size=(batch, d)), jnp.float32)
    y_target = jnp.asarray(rng.normal(size=(batch, d)), jnp.float32)

    @jax.jit
    def train_step(params, x, y):
        def loss(p):
            out = pipeline_apply(stage_fn, p, x, mesh=mesh,
                                 microbatches=4)
            return ((out - y) ** 2).mean()
        l, g = jax.value_and_grad(loss)(params)
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, g)
        return params, l

    with mesh:
        losses = []
        for _ in range(20):
            params, l = train_step(params, x, y_target)
            losses.append(float(l))
    print(f"pipeline training: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0]


if __name__ == "__main__":
    main()
