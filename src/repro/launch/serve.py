"""Serving launcher.

Three modes:

* default: a reduced ("smoke") config of ``--arch`` in float32, batched
  prefill + greedy decode; runs on any backend, the CPU included.
* ``--full``: the published config in bf16, params and KV cache placed
  on a ``(data=1, model=<all local devices>)`` mesh, serving seeded
  requests of mixed prompt lengths through the continuous-batching
  :class:`~repro.runtime.serve_loop.ServeLoop`.  This is the path that
  runs on a TPU (``chip_smoke.py`` drives it there).
* ``--production``: a compile-only rehearsal of the full-size serve
  step on 512 placeholder CPU host devices (the dry run); nothing
  executes on a chip.

Examples::

    python -m repro.launch.serve --arch llama3_8b --tokens 16
    python -m repro.launch.serve --arch minitron_4b --full
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig, get_config, get_smoke_config
from repro.models import LM
from repro.runtime.serve_loop import Request, ServeLoop

from .compile_cache import enable_compile_cache
from .mesh import make_local_mesh
from .sharding import (cache_shardings, make_shard_act, pick_policy,
                       tree_shardings)


def greedy_decode(model: LM, params, prompt, new_tokens: int,
                  frontend=None):
    """Prefill via teacher-forced decode steps, then greedy generation."""
    bsz, plen = prompt.shape
    max_len = plen + new_tokens + 1
    cache = model.init_cache(bsz, max_len)
    memory = model.encode_memory(params, frontend)

    # the position is traced: one program for every step
    step = jax.jit(
        lambda p, c, t, pos: model.decode_step(p, c, t, pos, memory=memory),
        donate_argnums=(1,))
    logits = None
    for t in range(plen):
        logits, cache = step(params, cache, prompt[:, t:t + 1], np.int32(t))
    out = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    for t in range(plen, plen + new_tokens):
        out.append(tok)
        logits, cache = step(params, cache, tok, np.int32(t))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def serve_layout(cfg: ModelConfig, mesh, *, slots: int, max_len: int,
                 param_dtype=jnp.bfloat16):
    """The model for ``cfg`` on ``mesh`` and where its params and KV
    cache go: (model, param shardings, cache shardings)."""
    policy = pick_policy(cfg.total_params())
    model = LM(cfg, param_dtype=param_dtype, max_seq=max_len,
               shard_act=make_shard_act(mesh, policy))
    shapes = jax.eval_shape(lambda: model.init(0))
    cache_shapes = jax.eval_shape(lambda: model.init_cache(slots, max_len))
    return (model, tree_shardings(shapes, mesh, policy),
            cache_shardings(cache_shapes, mesh, slots))


def build_server(cfg: ModelConfig, mesh, *, slots: int, max_len: int,
                 seed: int = 0, param_dtype=jnp.bfloat16) -> ServeLoop:
    """``cfg`` with params and KV cache placed on ``mesh``."""
    model, param_sh, cache_sh = serve_layout(
        cfg, mesh, slots=slots, max_len=max_len, param_dtype=param_dtype)
    # init compiled with the sharding rules as output shardings: each
    # device draws only its own shards
    params = jax.jit(lambda: model.init(seed), out_shardings=param_sh)()
    return ServeLoop(model, params, slots=slots, max_len=max_len,
                     cache_sharding=cache_sh)


MIN_PROMPT = 16


def seeded_requests(vocab: int, n: int, *, max_prompt: int,
                    new_tokens: int, seed: int = 0) -> list[Request]:
    """``n`` requests with prompt lengths spread evenly over
    ``[MIN_PROMPT, max_prompt]`` and random token ids."""
    rng = np.random.default_rng(seed)
    lens = np.linspace(MIN_PROMPT, max_prompt, n).astype(int)
    return [Request(i, rng.integers(0, vocab, int(n_tok)).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i, n_tok in enumerate(lens)]


def serve_len(requests: list[Request]) -> int:
    """Cache length that holds every request's prompt and output,
    rounded up to a multiple of 128."""
    need = max(len(r.prompt) + r.max_new_tokens for r in requests)
    return -(-need // 128) * 128


def start_server(cfg: ModelConfig, mesh, *, n_requests: int = 8,
                 max_prompt: int = 256, new_tokens: int = 32, seed: int = 0,
                 param_dtype=jnp.bfloat16):
    """A server for ``cfg`` on ``mesh`` with one slot per request and
    ``n_requests`` seeded requests queued; ``loop.run()`` serves them.
    Returns (loop, requests)."""
    requests = seeded_requests(cfg.vocab_size, n_requests,
                               max_prompt=max_prompt, new_tokens=new_tokens,
                               seed=seed)
    loop = build_server(cfg, mesh, slots=n_requests,
                        max_len=serve_len(requests), seed=seed,
                        param_dtype=param_dtype)
    for r in requests:
        loop.submit(r)
    return loop, requests


def main(argv=None) -> int:
    from repro.obs import setup_logging
    _log = setup_logging()  # CLI entry point: bare messages on stdout
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="published config in bf16 on all local devices: "
                         "8 seeded requests, prompts of 16-256 tokens, "
                         "through the continuous-batching loop")
    ap.add_argument("--production", action="store_true",
                    help="compile-only rehearsal of the full-size serve "
                         "step on CPU host devices (the dry run)")
    args = ap.parse_args(argv)

    if args.production:
        from repro.launch.dryrun import run_cell, use_host_devices
        use_host_devices()
        result = run_cell(args.arch, args.shape, multi_pod=False)
        return 0 if result["status"] == "ok" else 1

    enable_compile_cache()
    if args.full:
        cfg = get_config(args.arch)
        loop, requests = start_server(
            cfg, make_local_mesh(1, len(jax.devices())),
            new_tokens=args.tokens)
        t0 = time.perf_counter()
        done = loop.run()
        dt = time.perf_counter() - t0
        n_tok = sum(len(r.out) for r in done)
        _log.info("%s: served %d/%d requests, %d tokens in %.2fs "
                  "(smoke reading, not a metric: %.1f tok/s)",
                  cfg.name, len(done), len(requests), n_tok, dt, n_tok / dt)
        return 0 if len(done) == len(requests) else 1

    cfg = get_smoke_config(args.arch)
    model = LM(cfg, param_dtype=jnp.float32, attn_chunk=16,
               max_seq=args.prompt_len + args.tokens + 8)
    params = model.init(0)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    frontend = None
    if cfg.frontend_tokens:
        frontend = jnp.asarray(
            rng.normal(size=(args.batch, cfg.frontend_tokens,
                             cfg.frontend_dim)), jnp.float32)
    t0 = time.perf_counter()
    out = greedy_decode(model, params, prompt, args.tokens, frontend)
    dt = time.perf_counter() - t0
    _log.info("generated %s tokens in %.2fs (%.1f tok/s)",
              out.shape, dt, args.batch * args.tokens / dt)
    _log.info("sample: %s", np.asarray(out[0])[:16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
