#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU, in one process.

Default (one chip): ``minitron_4b`` at its published widths (32 layers,
d_model 3072, vocab 256000) in bf16, built by
``repro.launch.serve.build_server`` on a mesh over the chip, serves 8
seeded requests (prompts of 16 to 256 tokens, 32 new tokens each)
through the continuous-batching ``ServeLoop``.  It checks that every
request got its tokens, that the decode step compiled once, and that
for two requests the decode path's logits at the last prompt position
agree with ``model.forward`` (the full-sequence path) on the same
params: max-abs difference within ``LOGIT_ATOL``, and the same top-1
token unless forward's top two are a near-tie.

``--chips 4`` runs only the sharded path: ``llama3_8b`` at full depth
on a ``(data=1, model=4)`` mesh answering a few requests, with each
device holding about a quarter of the params; then a 4-layer
``llama3_8b`` at published widths in float32 (full-precision matmuls)
on one chip and on the 4-chip mesh, whose greedy tokens must be equal
and logits within ``SHARDED_ATOL``.

Weights and prompts are generated from ``--seed``.  Without a TPU it
prints no result and exits non-zero.  The last line of a passing run
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Wall
clock rates it prints are smoke readings, not benchmark metrics.

Run:  python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# decode-path vs forward-path logits at one position, bf16 params:
# twice the largest max-abs difference read over all 8 seeded requests
# on a TPU v5e (0.0889; the 8 readings ran from 0.0545 to 0.0889)
LOGIT_ATOL = 0.18
# 1-chip vs 4-chip logits of the same model and requests, f32 params,
# full-precision matmuls
SHARDED_ATOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def report_compiles() -> None:
    """The programs JAX compiled or loaded from the persistent cache, as
    ``repro.obs``'s compile counter has counted them."""
    from repro.obs import METRICS, cache_loads, compiles
    secs = METRICS.histograms.get("jax.compile_s")
    log(f"compiles: {compiles()} programs compiled, {cache_loads()} "
        f"loaded from the persistent cache, "
        f"{secs.sum if secs else 0.0:.2f} s in all")
    for key in sorted(METRICS.counters):
        if key.startswith(("jax.compiles.", "jax.cache_loads.")):
            log(f"  {key}: {METRICS.counters[key]}")


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def bytes_per_device(tree, devices) -> list[int]:
    import jax
    out = {d: 0 for d in devices}
    for x in jax.tree.leaves(tree):
        for shard in x.addressable_shards:
            out[shard.device] += shard.data.nbytes
    return [out[d] for d in devices]


def describe(cfg) -> str:
    return (f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab_size}")


def serve(cfg, mesh, *, label: str, **kw):
    """Start the server for ``cfg`` on ``mesh`` (``kw`` goes to
    ``repro.launch.serve.start_server``) and serve its seeded requests;
    returns the loop and the finished requests by id."""
    import jax
    from repro.launch.serve import start_server

    t0 = time.perf_counter()
    loop, requests = start_server(cfg, mesh, **kw)
    jax.block_until_ready(loop.params)
    log(f"[{label}] init {time.perf_counter() - t0:.2f} s; params "
        f"{tree_bytes(loop.params)} B, cache {tree_bytes(loop.cache)} B "
        f"({loop.slots} slots x {loop.max_len} positions, "
        f"{jax.tree.leaves(loop.cache)[0].dtype}); prompts of "
        f"{sorted(len(r.prompt) for r in requests)} tokens")
    t0 = time.perf_counter()
    done = loop.run()
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in done)
    log(f"[{label}] served {len(done)}/{len(requests)} requests, "
        f"{n_tok} tokens generated in {dt:.2f} s "
        f"(smoke reading, not a metric: {n_tok / dt:.1f} tok/s)")
    check(len(done) == len(requests), f"{label}: not every request served")
    for r in done:
        check(len(r.out) == r.max_new_tokens,
              f"{label}: request {r.rid} got {len(r.out)} of "
              f"{r.max_new_tokens} tokens")
    return loop, {r.rid: r for r in done}


def check_decode_vs_forward(loop, reqs, label: str) -> None:
    """Decode-path logits at the last prompt position against the
    full-sequence forward pass on the same params."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fwd = jax.jit(lambda p, t: loop.model.forward(p, t, last_only=True)[0])
    for r in reqs:
        ref = np.asarray(fwd(loop.params, jnp.asarray(r.prompt[None])))[0, -1]
        got = np.asarray(r.prompt_logits, np.float32)
        err = float(np.max(np.abs(got - ref)))
        top2 = np.sort(ref)[-2:]
        log(f"[{label}] request {r.rid} (prompt {len(r.prompt)}): decode "
            f"top-1 {int(np.argmax(got))}, forward top-1 "
            f"{int(np.argmax(ref))} (margin {top2[1] - top2[0]:.4f}), "
            f"max|logit| {float(np.max(np.abs(ref))):.3f}, "
            f"max-abs diff {err:.4f} (bound {LOGIT_ATOL})")
        check(bool(np.all(np.isfinite(got))), "non-finite decode logits")
        check(err <= LOGIT_ATOL, f"request {r.rid}: max-abs diff {err}")
        if top2[1] - top2[0] > 2 * err:
            check(int(np.argmax(got)) == int(np.argmax(ref)),
                  f"request {r.rid}: decode and forward top-1 differ")
        else:   # a near-tie: decode may pick either of the tied tokens
            check(ref[np.argmax(got)] >= ref.max() - LOGIT_ATOL,
                  f"request {r.rid}: decode's top-1 is not near forward's")


def one_chip(seed: int) -> None:
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.obs import cache_loads, compiles

    cfg = get_config("minitron_4b")
    log(describe(cfg))
    loop, done = serve(cfg, make_local_mesh(1, 1), seed=seed,
                       label="minitron_4b")
    decode_compiles = (compiles("jit(decode_step)")
                       + cache_loads("jit(decode_step)"))
    log(f"[minitron_4b] decode program compiled or loaded "
        f"{decode_compiles} x")
    check(decode_compiles == 1, "decode step compiled more than once")
    check_decode_vs_forward(loop, [done[0], done[len(done) - 1]],
                            "minitron_4b")


def four_chips(devices, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.obs import cache_loads, compiles

    few = dict(n_requests=4, max_prompt=128, new_tokens=16, seed=seed)
    mesh4 = make_local_mesh(1, 4)
    cfg = get_config("llama3_8b")
    log(describe(cfg) + " on a (data=1, model=4) mesh")
    loop, _ = serve(cfg, mesh4, label="llama3_8b x4", **few)
    per_dev = bytes_per_device(loop.params, devices[:4])
    total = tree_bytes(loop.params)
    log(f"[llama3_8b x4] param bytes per device: {per_dev} "
        f"(total {total}, quarter {total // 4})")
    check(max(per_dev) <= 0.3 * total,
          "params are not sharded over the four devices")
    check(compiles("jit(decode_step)") + cache_loads("jit(decode_step)")
          == 1, "decode compiled more than once")
    del loop

    # float32 params and full-precision float32 matmuls: the comparison
    # tests the sharding, and bf16 rounding (of params, or of matmul
    # inputs at the TPU's default precision) of differently ordered
    # partial sums would let greedy tokens part on near-ties
    short = replace(cfg, n_layers=4)
    log(f"comparison: {describe(short)}, float32, matmul precision "
        f"highest, 1 chip vs 4 chips")
    outs = {}
    with jax.default_matmul_precision("highest"):
        for label, mesh in (("1 chip", make_local_mesh(1, 1)),
                            ("4 chips", mesh4)):
            loop, outs[label] = serve(short, mesh,
                                      label=f"llama3_8b/4L {label}",
                                      param_dtype=jnp.float32, **few)
            del loop
    one, four = outs["1 chip"], outs["4 chips"]
    for rid in one:
        err = float(np.max(np.abs(one[rid].prompt_logits
                                  - four[rid].prompt_logits)))
        same = one[rid].out == four[rid].out
        log(f"[compare] request {rid}: greedy tokens equal: {same}; "
            f"max-abs logit diff {err:.5f} (bound {SHARDED_ATOL})")
        check(same, f"request {rid}: 1-chip and 4-chip tokens differ")
        check(err <= SHARDED_ATOL, f"request {rid}: logits differ by {err}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}; "
              "this script runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind}, {len(devices)} visible; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    from repro.obs import count_compiles
    count_compiles()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devices, args.seed)
    else:
        one_chip(args.seed)
    report_compiles()
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
            f"bytes_limit {stats.get('bytes_limit')}")
    log(f"total {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
