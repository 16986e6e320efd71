"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

A compile-only rehearsal on 512 placeholder CPU host devices: nothing
runs, and no number it records was measured on a chip.  For each cell
this proves the distribution config is coherent:

* ``jax.jit(step).lower(**input_specs).compile()`` succeeds on the
  single-pod (16, 16) mesh and the 2-pod (2, 16, 16) mesh,
* ``compiled.memory_analysis()`` fits the per-chip HBM budget,
* ``compiled.cost_analysis()`` + post-SPMD collective parsing produce
  the roofline terms (compute / memory / collective).

Results are cached as JSON under ``experiments/dryrun/``; the
benchmarks' roofline tables read from there.

Usage::

    python -m repro.launch.dryrun --arch llama3_8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import logging
import os
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    build_prefill_step,
    build_serve_step,
    build_train_step,
)

# explicit name: under ``python -m`` this module runs as __main__, and
# a __main__ logger would sit outside the "repro" handler subtree
_log = logging.getLogger("repro.launch.dryrun")

N_HOST_DEVICES = 512   # 2 pods x 16 x 16


def use_host_devices(n: int = N_HOST_DEVICES) -> None:
    """Run JAX on ``n`` placeholder CPU host devices.

    Must be called before JAX initialises a backend (the device count
    is fixed then); importing this module sets nothing.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()
    jax.config.update("jax_platforms", "cpu")


# --- hardware model of the dry run (TPU v5e target) ------------------- #
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link
HBM_BYTES = 16 * 2**30       # per chip
OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# long_500k needs sub-quadratic attention: run only for SSM/hybrid.
LONG_OK_FAMILIES = ("ssm", "hybrid")


def cells(include_long: bool = True):
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape.name == "long_500k":
                if cfg.family not in LONG_OK_FAMILIES:
                    continue  # skip recorded in EXPERIMENTS.md
            yield arch, shape.name


def build(arch: str, shape_name: str, mesh, **kw):
    kind = SHAPES[shape_name].kind
    if kind == "train":
        kw.pop("kv_dtype", None)   # decode-only knob
        return build_train_step(arch, shape_name, mesh, **kw)
    kw.pop("moment_dtype", None)   # train-only knobs
    kw.pop("rwkv_chunk", None)
    kw.pop("grad_accum", None)
    kw.pop("remat", None)
    if kind == "prefill":
        kw.pop("kv_dtype", None)   # decode-only knob
        return build_prefill_step(arch, shape_name, mesh, **kw)
    return build_serve_step(arch, shape_name, mesh, **kw)


def _write_hlo(save_hlo: Path, hlo_text: str) -> Path:
    """Write compressed HLO next to the cell JSON.

    ``save_hlo`` is the codec-less base path (``<cell>.hlo``); the
    codec suffix is appended here.  zstandard is optional (not part of
    the baked toolchain) — fall back to stdlib gzip so a missing
    compressor never fails the cell.  Returns the path written.
    """
    try:
        import zstandard
    except ImportError:
        import gzip
        out = save_hlo.with_name(save_hlo.name + ".gz")
        out.write_bytes(gzip.compress(hlo_text.encode(), compresslevel=6))
    else:
        out = save_hlo.with_name(save_hlo.name + ".zst")
        out.write_bytes(
            zstandard.ZstdCompressor(level=6).compress(hlo_text.encode()))
    return out


_REPO_ROOT = str(Path(__file__).resolve().parents[3])


def _sanitize_traceback(tb: str) -> str:
    """Relativize repo paths so committed artifacts stay machine-neutral."""
    return tb.replace(_REPO_ROOT + os.sep, "")


def _spec_args(bundle):
    s = bundle.input_specs
    if "batch" in s:                       # train
        return (s["params"], s["opt_state"], s["batch"])
    if "cache" in s:                       # decode
        args = [s["params"], s["cache"], s["tokens"]]
        if "memory" in s:
            args.append(s["memory"])
        return tuple(args)
    args = [s["params"], s["tokens"]]      # prefill
    if "frontend" in s:
        args.append(s["frontend"])
    return tuple(args)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides: dict | None = None, verbose: bool = True,
             save_hlo: Path | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.perf_counter()
    bundle = build(arch, shape_name, mesh, **(overrides or {}))
    with mesh:
        lowered = bundle.step_fn.lower(*_spec_args(bundle))
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    if save_hlo is not None:
        _write_hlo(save_hlo, hlo_text)
    hlo = analyze_hlo(hlo_text)
    coll = hlo.collectives

    # trip-count-aware per-device terms (see hlo_analysis docstring;
    # XLA's own cost_analysis undercounts while bodies)
    flops = float(hlo.flops)
    bytes_accessed = float(hlo.bytes_accessed)
    compute_s = flops / PEAK_FLOPS
    # XLA:CPU float-normalization promotes bf16 compute to f32 (verified
    # on a trivial bf16 matmul) — TPU keeps bf16.  Activation-class
    # traffic is therefore inflated ~2x on this host backend; we report
    # the raw term and a bf16-corrected term and use the corrected one
    # for the roofline (documented in EXPERIMENTS.md §Dry-run).
    memory_s_raw = bytes_accessed / HBM_BW
    memory_s = 0.5 * memory_s_raw
    collective_s = coll.wire_bytes / LINK_BW

    per_dev_bytes = (
        mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    # TPU estimate: arguments (params/opt/caches) carry their declared
    # dtypes and are exact; temps are bf16-activations promoted to f32
    # by the CPU backend -> halve them for the TPU number.
    per_dev_bytes_tpu = (
        0.5 * mem.temp_size_in_bytes + mem.argument_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * n_active * tokens
    else:
        tokens = shape.global_batch
        model_flops = 2 * n_active * tokens

    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1])[0]
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "policy": bundle.policy,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "per_device_bytes": int(per_dev_bytes),
        "per_device_gib": round(per_dev_bytes / 2**30, 3),
        "per_device_gib_tpu_est": round(per_dev_bytes_tpu / 2**30, 3),
        "argument_gib": round(mem.argument_size_in_bytes / 2**30, 3),
        "temp_gib": round(mem.temp_size_in_bytes / 2**30, 3),
        "fits_hbm": bool(per_dev_bytes_tpu <= HBM_BYTES),
        "fits_hbm_raw": bool(per_dev_bytes <= HBM_BYTES),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_accessed,
        "xla_cost_flops_raw": float(cost.get("flops", 0.0)),
        "n_while_loops": hlo.n_while,
        "max_trip_count": hlo.max_trip,
        "collective_bytes_per_device": coll.total_bytes,
        "collective_wire_bytes": coll.wire_bytes,
        "collectives": {k: [coll.count_by_type[k], v]
                        for k, v in coll.bytes_by_type.items()},
        "compute_s": compute_s,
        "memory_s": memory_s,
        "memory_s_raw": memory_s_raw,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops_total": model_flops,
        "model_flops_per_device": model_flops / n_chips,
        "useful_flop_frac": (model_flops / n_chips) / flops if flops else 0.0,
        "roofline_frac": (
            (model_flops / n_chips / PEAK_FLOPS)
            / max(compute_s, memory_s, collective_s)
            if max(compute_s, memory_s, collective_s) > 0 else 0.0),
    }
    if verbose:
        _log.info("%s", json.dumps(
            {k: result[k] for k in (
                "arch", "shape", "mesh", "policy", "compile_s",
                "per_device_gib_tpu_est", "fits_hbm", "compute_s",
                "memory_s", "collective_s", "dominant",
                "useful_flop_frac", "roofline_frac")},
            indent=None))
    return result


def _cached_ok(path: Path) -> bool:
    """True iff the cached cell JSON records a successful run.

    Error cells (and unreadable files) are treated as stale so a fixed
    environment regenerates them without needing ``--force``.
    """
    try:
        return json.loads(path.read_text()).get("status") == "ok"
    except (OSError, ValueError):
        return False


def cell_path(arch: str, shape: str, multi_pod: bool, tag: str = "") -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"_{tag}" if tag else ""
    return OUT_DIR / f"{arch}__{shape}__{mesh}{suffix}.json"


def main(argv=None) -> int:
    from repro.obs import setup_logging
    setup_logging()  # CLI entry point: bare messages on stdout
    use_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for experiment JSONs")
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--rwkv-chunk", type=int, default=None)
    ap.add_argument("--moment-dtype", default=None)
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--kv-dtype", default=None)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--remat", default=None)
    args = ap.parse_args(argv)

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    todo = []
    if args.all:
        todo = list(cells())
    else:
        if not args.arch:
            ap.error("--arch required unless --all")
        shapes = [args.shape] if args.shape else [
            s for a, s in cells() if a == args.arch]
        todo = [(args.arch, s) for s in shapes]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    overrides = {}
    if args.attn_chunk:
        overrides["attn_chunk"] = args.attn_chunk
    if args.rwkv_chunk:
        overrides["rwkv_chunk"] = args.rwkv_chunk
    if args.moment_dtype:
        overrides["moment_dtype"] = args.moment_dtype
    if args.grad_accum:
        overrides["grad_accum"] = args.grad_accum
    if args.kv_dtype:
        overrides["kv_dtype"] = args.kv_dtype
    if args.policy:
        overrides["policy"] = args.policy
    if args.remat:
        overrides["remat"] = args.remat

    failures = 0
    for arch, shape in todo:
        for mp in meshes:
            path = cell_path(arch, shape, mp, args.tag)
            if path.exists() and not args.force:
                if _cached_ok(path):
                    _log.info("cached: %s", path.name)
                    continue
                _log.info("stale error cell, re-running: %s", path.name)
            try:
                result = run_cell(arch, shape, multi_pod=mp,
                                  overrides=overrides or None,
                                  save_hlo=path.with_suffix(".hlo"))
            except Exception as e:  # noqa: BLE001 - record and continue
                failures += 1
                result = {
                    "arch": arch, "shape": shape,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error", "error": repr(e),
                    "traceback": _sanitize_traceback(
                        traceback.format_exc())[-2000:],
                }
                _log.error("FAIL %s %s mp=%s: %r", arch, shape, mp, e)
            path.write_text(json.dumps(result, indent=2))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
