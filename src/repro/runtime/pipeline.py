"""Pipeline-parallel execution: GPipe microbatch schedule over a
"stage" mesh axis via shard_map + collective_permute.

The scheduler (autoshard) decides *which* blocks form stages; this
module is the runtime that executes a stage-partitioned model:

* stage parameters are stacked ``[n_stages, ...]`` and sharded over the
  "stage" axis (one stage's weights per device group),
* microbatches flow through a rotating buffer: at step t, stage s
  processes microbatch ``t − s`` (when valid) and the buffer is
  ``collective_permute``d one stage forward,
* total steps = µ + S − 1 (fill + drain); outputs accumulate on the
  last stage,
* ``jax.grad`` through the runner yields the reverse (backward)
  pipeline automatically — the transpose of collective_permute is the
  reverse permute, so the GPipe backward schedule falls out of
  autodiff.

This is the PP building block the dry-run meshes don't exercise (they
use DP/FSDP/TP axes); tests run it on 4 host devices in a subprocess.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage: list) -> dict:
    """Stack a list of per-stage param pytrees along a leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)


def pipeline_apply(stage_fn, stage_params, x, *, mesh: Mesh,
                   axis: str = "stage", microbatches: int | None = None):
    """Run ``x`` through a pipeline of stages.

    Args:
      stage_fn: ``(params_slice, x_mb) -> x_mb`` — one stage's compute.
      stage_params: pytree stacked ``[S, ...]``, sharded over ``axis``.
      x: ``[B, ...]`` global input batch (replicated).
      mesh: mesh containing the ``axis`` of size S.
      microbatches: µ (defaults to S — the minimum for full utilization).

    Returns ``[B, ...]`` outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    mu = microbatches or n_stages
    b = x.shape[0]
    if b % mu:
        raise ValueError(f"batch {b} not divisible by {mu} microbatches")
    mb = b // mu
    xs = x.reshape((mu, mb) + x.shape[1:])

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params_local, xs_local):
        # params_local: [1, ...] (this stage's slice); xs_local: [µ, mb, ...]
        params_local = jax.tree.map(lambda t: t[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        steps = mu + n_stages - 1
        # the carry becomes device-varying after the first ppermute, so
        # its initial value must be typed as varying too
        buf = jax.lax.pcast(jnp.zeros_like(xs_local[0]), (axis,),
                            to="varying")
        out = jax.lax.pcast(jnp.zeros_like(xs_local), (axis,),
                            to="varying")

        def step(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (while t < µ)
            inject = jnp.where(t < mu, t, 0)
            buf = jnp.where(stage_id == 0,
                            xs_local[inject], buf)
            y = stage_fn(params_local, buf)
            # microbatch index this stage just produced
            m = t - stage_id
            valid = (m >= 0) & (m < mu)
            out = jnp.where(
                (stage_id == n_stages - 1) & valid,
                jax.lax.dynamic_update_slice_in_dim(
                    out, y[None], jnp.clip(m, 0, mu - 1), axis=0),
                out)
            # rotate stage s -> s+1
            buf = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out),
                                     jnp.arange(steps))
        # out is only populated on the last stage; emit per-stage and
        # let the caller slice (the vma type system can't see that a
        # broadcast ppermute would make it replicated)
        return out[None]

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    result = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(axis),
    )(stage_params, xs)
    # [S, µ, mb, ...] — the last stage's buffer holds the outputs
    return result[-1].reshape((b,) + x.shape[1:])
