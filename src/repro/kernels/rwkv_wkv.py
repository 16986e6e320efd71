"""RWKV6 WKV recurrence — Pallas TPU kernel (TARGET: TPU v5e; validated
in interpret mode against ``ref.reference_wkv``).

The recurrence (per batch b, head h; state S ∈ R^{hd×hd})::

    out_t = r_t · (S + (u ⊙ k_t) v_tᵀ)
    S     = diag(w_t) · S + k_t v_tᵀ

TPU adaptation: the sequence is processed in chunks; grid =
(B, H, S/chunk) with the chunk axis sequential, the f32 state carried
in VMEM scratch between chunk iterations.  Within a chunk the time loop
is a ``fori_loop`` over 8-step tiles of rank-1 updates on the
VMEM-resident state — the memory-hierarchy-aware reformulation of the
CUDA kernel (which keeps S in registers/shared memory per thread
block).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_bhsd"]


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            state_scr, *, chunk: int, n_chunks: int, rows: int):
    # the state is kept transposed, Sᵀ[v, k], so the per-step decay
    # w[k] and the bonus scale broadcast along rows; time steps are
    # taken ``rows`` at a time so every load and store of a sequence
    # tile starts at a multiple of 8 rows (Mosaic's tiling rule)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                   # [1, hd]

    def block(i, st):
        base = pl.multiple_of(i * rows, rows)
        tile = lambda ref: ref[0, 0, pl.ds(base, rows), :].astype(
            jnp.float32)                               # [rows, hd]
        rb, kb, vb, wb = tile(r_ref), tile(k_ref), tile(v_ref), tile(w_ref)
        v_cols = vb.T                                  # [hd, rows]
        outs = []
        for j in range(rows):
            rt, kt, wt = rb[j:j + 1], kb[j:j + 1], wb[j:j + 1]  # [1, hd]
            vkt = v_cols[:, j:j + 1] * kt              # (k vᵀ)ᵀ: [hd, hd]
            outs.append(jax.lax.dot_general(           # r·(S + u⊙k vᵀ)
                rt, st + vkt * u, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32))   # [1, hd]
            st = st * wt + vkt
        o_ref[0, 0, pl.ds(base, rows), :] = jnp.concatenate(
            outs, axis=0).astype(o_ref.dtype)
        return st

    state = jax.lax.fori_loop(0, chunk // rows, block, state_scr[...])
    state_scr[...] = state

    @pl.when(ic == n_chunks - 1)
    def _finalize():
        sT_ref[0, 0] = state


def wkv_bhsd(
    r: jax.Array,      # [B, H, S, hd]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,      # decay in (0, 1)
    u: jax.Array,      # [H, hd] bonus
    s0: jax.Array,     # [B, H, hd, hd] initial state (f32)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out [B, H, S, hd], final state [B, H, hd, hd])."""
    b, h, s, hd = r.shape
    if s % chunk:
        raise ValueError(f"seq len {s} must be a multiple of chunk {chunk}")
    n_chunks = s // chunk
    grid = (b, h, n_chunks)

    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks,
                               rows=math.gcd(chunk, 8))
    seq_spec = pl.BlockSpec((1, 1, chunk, hd),
                            lambda ib, ih, ic: (ib, ih, ic, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd),
                              lambda ib, ih, ic: (ib, ih, 0, 0))

    out, sT = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            # u is passed as [H, 1, hd] so the block's last two dims
            # equal the array's (Mosaic's (8, 128) tiling rule)
            pl.BlockSpec((1, 1, hd), lambda ib, ih, ic: (ih, 0, 0)),
            state_spec,
        ],
        out_specs=[seq_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, hd), s0.swapaxes(-1, -2))
    return out, sT.swapaxes(-1, -2)
