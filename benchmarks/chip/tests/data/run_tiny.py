"""Drive the whole harness, less its look for a chip, on the CPU at a
tiny size, with the timed path sound or broken underneath; print the
check's readings (``{"readings": ...}``), then the result line.

    python run_tiny.py <cache dir> <fault> [<chips> [<configuration>]]

``fault``: ``none``; ``control`` (the float8 control judged in the
program's place); ``token`` (every generated token altered where
the step produces it); ``state`` (the step returns the KV cache it was
given, its new entries dropped); ``batch`` (half of the slots left
out: the first half is given the second half's logits); ``exchange``
(the attention output's sum over the devices of the model axis left
out: each device keeps its own partial product).  ``chips`` > 1 needs
that many JAX devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
``configuration``: a file in this directory, ``tiny.json`` by default;
its architecture is looked for in ``chipbench/archs/`` and here.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

DATA = Path(__file__).resolve().parent
BENCH = DATA.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
from chipbench import archs, traffic  # noqa: E402

# the tiny cell's limit on ``gap``: sound runs on the CPU read about
# 0.01-0.03, the float8 control about 0.2-0.3
TINY_LIMITS = {"gap": 0.1}
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(chips: int = 1, config: str = "tiny.json") -> SimpleNamespace:
    conf = json.loads((DATA / config).read_text())
    conf["mesh"] = {"data": 1, "model": chips}
    e2e = [{"name": n, "unit": u} for n, u in
           (("ttft_p90_s", "s"), ("itl_p99_ms", "ms"), ("setup_s", "s"))]
    return SimpleNamespace(name="tiny", chips=chips, config=conf,
                           arch=archs.load(conf["architecture"], DATA),
                           mix=traffic.load(DATA / "tiny_traffic.json"),
                           limits=dict(TINY_LIMITS), end_to_end=e2e,
                           per_layer=[])


def plant(fault: str, devices) -> None:
    if fault in ("token", "state", "batch"):
        from repro.runtime import serve_loop
        honest = serve_loop.decode_program

        def broken(model, cache_sharding=None):
            step = honest(model, cache_sharding)

            def run_step(params, cache, tokens, pos):
                kept = jax.tree.map(jnp.copy, cache)
                logits, new = step(params, cache, tokens, pos)
                if fault == "token":
                    return logits.at[..., 5].add(1e4), new
                if fault == "state":
                    return logits, kept
                half = logits.shape[0] // 2
                return logits.at[:half].set(logits[half:2 * half]), new
            return run_step
        serve_loop.decode_program = broken
    elif fault == "exchange":
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np
        from repro.models import transformer
        mesh = Mesh(np.array(devices).reshape(1, -1), ("data", "model"))
        real = transformer.jnp

        def local_product(o, w):
            return jnp.einsum("bse,ed->bsd", o, w)

        kept = jax.shard_map(local_product, mesh=mesh,
                             in_specs=(P(None, None, "model"), P("model", None)),
                             out_specs=P(), check_vma=False)

        class NoExchange:
            def __getattr__(self, name):
                return getattr(real, name)

            @staticmethod
            def einsum(spec, *ops, **kw):
                if spec == "bse,ed->bsd":
                    return kept(*ops)
                return real.einsum(spec, *ops, **kw)
        transformer.jnp = NoExchange()
    elif fault not in ("none", "control"):
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    cache, fault = sys.argv[1], sys.argv[2]
    chips = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    config = sys.argv[4] if len(sys.argv) > 4 else "tiny.json"
    run.CACHE_DIR = Path(cache)
    devices = jax.devices()[:chips]
    plant(fault, devices)
    result, numbers = run.run_cell(tiny_cell(chips, config), devices,
                                   seed=2**31 + 11, seconds=2.0, trace=False,
                                   control=fault == "control", peak=PEAK)
    print(json.dumps({"readings": numbers}), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
