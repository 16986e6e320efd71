#!/usr/bin/env python3
"""Chip benchmark of the serving path, one cell per run.

    python3 benchmarks/chip/run.py --workload minitron_4b.chat \\
        --seed 1234 --seconds 45 --trace 0

Run from the root of a checkout on a machine with the chips the cell
asks for.  Everything about a cell is found by name from
``BENCHMARK.json``: its configuration (``configs/<config>.json``), the
architecture that names (``chipbench/archs/<architecture>.py``: the
reference and the work counts), its traffic mix
(``traffic/<traffic>.json``), its per-layer metrics
(``metrics/<name>.py``) and the limits of its correctness check
(``limits/<workload>.json``).

One run, in one process:

1. builds the program's server (``ServeLoop``) as
   ``repro.launch.serve.build_server`` does, its weights drawn on the
   device by the program's init from ``--seed`` in one jitted call
   that takes the seed as an argument, and warms up its decode program;
2. offers the mix's requests open-loop at their due times for
   ``--seconds``, stepping the server with ``ServeLoop.run(max_steps=1)``,
   then drains every request sent;
3. reads the peak device memory, frees the server, and compares a
   sample of what it served with the float32 reference;
4. prints the result as the last line of standard output: with
   ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
   per-layer metrics read from a profiler trace of part of the window,
   in which the program's own tracer puts its spans.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2; with an unknown architecture, or a reader that does
not load, it exits before it looks for one.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

from chipbench import archs, check, records, spans, traffic, xplane  # noqa: E402
from chipbench.drive import drive  # noqa: E402
from chipbench.numerics import weight_seed  # noqa: E402
from chipbench.peaks import peak_for  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with everything its
    files say."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    per_layer = [m for m in bench["per_layer"] if applies(m)]
    for m in per_layer:     # a reader that does not load stops the run here
        reader(m["name"])
    return SimpleNamespace(
        name=name, chips=cell["chips"], config=config,
        arch=archs.load(config["architecture"]),
        mix=traffic.load(HERE / "traffic" / f"{cell['traffic']}.json"),
        limits=check.load_limits(HERE / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=per_layer)


def chips_or_exit(n: int):
    """The first ``n`` TPU devices, or exit 2 with no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform!r}")
        raise SystemExit(2)
    if len(devices) < n:
        log(f"the cell needs {n} chips; JAX sees {len(devices)}")
        raise SystemExit(2)
    return devices[:n]


class Compiles:
    """Counts programs compiled and programs loaded from the persistent
    cache, from JAX's monitoring events: the backend-compile event
    times every program that was compiled or loaded, the cache-hit
    event marks those loaded."""

    def __init__(self) -> None:
        import jax
        self.requested = self.loaded = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requested += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    @property
    def compiled(self) -> int:
        return self.requested - self.loaded

    def total(self) -> int:
        return self.requested


class GcPauses:
    """The interpreter's garbage collections while it is open: how many,
    and the longest, by generation."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], self._t,
                                time.perf_counter() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def __str__(self) -> str:
        oldest = [t for g, _, t in self.pauses if g == 2]
        at = max(self.pauses, key=lambda p: p[2], default=(0, T_START, 0))
        return (f"garbage collections {len(self.pauses)}, longest "
                f"{at[2] * 1e3:.1f} ms (generation {at[0]}, "
                f"{at[1] - T_START:.3f} s after process start); of the "
                f"oldest generation {len(oldest)}, longest "
                f"{max(oldest, default=0) * 1e3:.1f} ms")


def reader(name: str):
    """The ``read(run)`` function of the per-layer metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Profiler:
    """``jax.profiler`` into a temporary directory, host tracing of
    annotations only (no Python function tracing).  ``op_names`` and
    ``scopes`` as ``spans.extract`` and ``spans.reduce`` take them."""

    def __init__(self, op_names: dict[str, str], scopes) -> None:
        import jax
        self.op_names, self.scopes = op_names, scopes
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.opts = jax.profiler.ProfileOptions()
        self.opts.python_tracer_level = 0
        self.opts.host_tracer_level = 2

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir, profiler_options=self.opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def reduce(self) -> tuple[dict, dict]:
        """(``xplane.reduce`` of the trace, ``spans.reduce`` of the
        program's spans in it), each ``{}`` where there is no trace;
        removes the trace."""
        paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
        try:
            if not paths:
                return {}, {}
            data = xplane.load(paths[-1])
            tr = xplane.extract(data)
            log(f"trace planes: {tr.planes}")
            return xplane.reduce(tr), spans.reduce(
                spans.extract(data, self.op_names), scopes=self.scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache``, every program in it however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build_server(cfg, mesh, *, slots: int, max_len: int, seed: int,
                 param_dtype):
    """``repro.launch.serve.build_server`` with the seed an argument of
    the jitted init, not a constant of it: one program, found in the
    compilation cache, draws the weights of every seed."""
    import jax
    from repro.launch.serve import serve_layout
    from repro.runtime.serve_loop import ServeLoop

    model, param_sh, cache_sh = serve_layout(
        cfg, mesh, slots=slots, max_len=max_len, param_dtype=param_dtype)
    params = jax.jit(model.init, out_shardings=param_sh)(weight_seed(seed))
    return ServeLoop(model, params, slots=slots, max_len=max_len,
                     cache_sharding=cache_sh)


def start_server(cell, devices, seed: int, compiles: Compiles):
    """The program's server for ``cell`` on ``devices``, weights drawn
    from ``seed``, its decode program warmed up.  Returns (loop, the
    program's request type, mesh shape)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import ModelConfig
    from repro.launch.mesh import make_local_mesh
    from repro.runtime.serve_loop import Request

    conf, server = cell.config, cell.mix["server"]
    mesh_shape = (conf["mesh"]["data"], conf["mesh"]["model"])
    if mesh_shape[0] * mesh_shape[1] != len(devices):
        raise SystemExit(f"mesh {mesh_shape} does not fit {len(devices)} chips")
    t0 = time.perf_counter()
    loop = build_server(ModelConfig(**conf["model"]),
                        make_local_mesh(*mesh_shape),
                        slots=server["slots"], max_len=server["max_len"],
                        seed=seed, param_dtype=getattr(jnp, conf["dtype"]))
    jax.block_until_ready((loop.params, loop.cache))
    t1 = time.perf_counter()
    loop.submit(Request(-1, np.arange(1, 3, dtype=np.int32), max_new_tokens=2))
    loop.run()
    log(f"set-up phases: process start to devices {t0 - T_START:.3f} s, "
        f"server built (weights drawn, cache placed) {t1 - t0:.3f} s, "
        f"decode warm-up {time.perf_counter() - t1:.3f} s; programs "
        f"compiled {compiles.compiled}, loaded from the cache "
        f"{compiles.loaded}")
    return loop, Request, mesh_shape


def run_cell(cell, devices, *, seed: int, seconds: float, trace: bool,
             control: bool = False, peak: dict | None = None
             ) -> tuple[dict, dict]:
    """One run of ``cell`` on ``devices``; returns (result line,
    readings of the check).  ``peak`` defaults to the peaks table's row
    for the devices' kind."""
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.obs import Tracer, activate

    use_compile_cache()
    compiles = Compiles()
    conf, mix = cell.config, cell.mix
    server = mix["server"]
    kind = devices[0].device_kind
    peak = peak or peak_for(kind)
    shapes = cell.arch.Shapes.of(conf["model"], conf["dtype"])

    # -- set-up: server, warm-up, the schedule -------------------------- #
    loop, Request, mesh_shape = start_server(cell, devices, seed, compiles)
    plan = traffic.schedule(mix, seconds, seed, conf["model"]["vocab_size"])
    reqs = [Request(i, p.prompt, max_new_tokens=p.max_new_tokens)
            for i, p in enumerate(plan)]
    profiler = (Profiler(spans.hlo_op_names(loop.decode_hlo()),
                         conf.get("scopes", spans.SCOPES)) if trace else None)
    loaded_before = compiles.total()
    # the profiler covers the window's last seconds: stopping it stalls
    # the host while it collects the device's events, and the stall then
    # falls in the drain, after the last arrival
    span = (max(0.0, seconds - TRACE_SECONDS), seconds) if trace else None
    setup_s = time.perf_counter() - T_START
    log(f"the window opens {setup_s:.3f} s after process start")

    # -- the window and the drain -------------------------------------- #
    pauses = GcPauses()
    use0 = resource.getrusage(resource.RUSAGE_SELF)
    with activate(Tracer(profiler=True)) if trace else nullcontext():
        run = drive(loop, plan, reqs, seconds, trace=span, profiler=profiler,
                    annotate=jax.profiler.TraceAnnotation if trace else None)
    use1 = resource.getrusage(resource.RUSAGE_SELF)
    pauses.close()
    in_window = compiles.total() - loaded_before
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    recs = run.records
    failed = sum(not r.complete for r in recs)
    late_mean, late_max = records.lateness(recs)
    log(f"cell {cell.name}: {kind} x{len(devices)}, seed {seed}, "
        f"{len(recs)} requests over {seconds} s, {len(run.steps)} steps, "
        f"drain ended at {run.end:.3f} s; the clock stood still "
        f"{run.paused_s:.3f} s while the profiler collected its trace")
    log(f"set-up {setup_s:.3f} s; programs compiled or loaded in the "
        f"window and drain: {in_window}; generator late by "
        f"{late_mean * 1e3:.3f} ms mean, {late_max * 1e3:.3f} ms max; "
        f"{failed} of {len(recs)} requests unanswered")
    slow = sorted(run.steps, key=lambda s: s.start - s.end)[:3]
    between = sorted(zip(run.steps, run.steps[1:]),
                     key=lambda ab: ab[0].end - ab[1].start)[:3]
    log("longest steps: " + ", ".join(
        f"{(s.end - s.start) * 1e3:.1f} ms at {s.start:.3f} s" for s in slow)
        + "; longest host work between steps: " + ", ".join(
        f"{(b.start - a.end) * 1e3:.1f} ms at {a.end:.3f} s"
        for a, b in between)
        + f"; the process over window and drain: user "
        f"{use1.ru_utime - use0.ru_utime:.3f} s, system "
        f"{use1.ru_stime - use0.ru_stime:.3f} s, involuntary context "
        f"switches {use1.ru_nivcsw - use0.ru_nivcsw}, major page faults "
        f"{use1.ru_majflt - use0.ru_majflt}; {pauses}")

    # -- the check, after the program's state is freed ------------------ #
    served = [(plan[i].prompt, list(reqs[i].out), reqs[i].prompt_logits)
              for i in check.sample(recs, mix["check"]["requests"], seed)]
    del loop, reqs
    gc.collect()
    t0 = time.perf_counter()
    numbers = check.readings(
        cell.arch, conf["model"], conf["dtype"], seed,
        make_local_mesh(*mesh_shape),
        served, max_len=server["max_len"], max_out=mix["output"]["max"],
        control=control) if served else {}
    phases = numbers.pop("phases_s", {})
    log(f"reference over {len(served)} requests "
        f"({sum(len(s[1]) for s in served)} generated tokens) took "
        f"{time.perf_counter() - t0:.3f} s: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in phases.items()))
    if control:
        own, _ = check.verdict(numbers, cell.limits, failed, len(recs))
        log(f"the program's own numbers are {'' if own else 'not '}correct; "
            "the control is judged in its place")
    correct, shown = check.verdict(
        check.as_control(numbers) if control else numbers, cell.limits,
        failed, len(recs))

    # -- metrics ------------------------------------------------------- #
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    metrics, out = {}, {}
    ctx = SimpleNamespace(records=recs, steps=run.steps, end=run.end,
                          config=conf, shapes=shapes, peak=peak,
                          chips=len(devices), seconds=seconds,
                          traced_steps=run.traced_steps, trace={}, spans={})
    if trace:
        ctx.trace, ctx.spans = profiler.reduce()
        log(f"program spans: {json.dumps(ctx.spans)}")
        tr = ctx.trace
        if tr:
            device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in tr["device_ops"]],
                "idle_gaps": [[n, s] for n, s in tr["idle_gaps"]]}
            log(f"trace: window {tr['window_s']:.6f} s, busy by device "
                f"{tr['busy_by_device']}, decode program runs "
                f"{tr['program_runs']} for {tr['steps']} steps; device time "
                f"by program {tr['programs']}")
        for m in cell.per_layer:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {
            "ttft_p90_s": lambda: records.percentile(
                records.ttft_s(recs, run.end), 90),
            "itl_p99_ms": lambda: records.percentile(
                records.itl_ms(recs, run.end), 99),
            "setup_s": lambda: setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]](), "unit": m["unit"]}
    for name, value in numbers.items():
        log(f"reading {name}: {value!r}")
    for name, shown_one in shown.items():
        log(f"check {name}: {shown_one['value']!r} (limit {shown_one['limit']!r})")
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": metrics, "device": device, **out}
    if control:
        result["readings"] = numbers
    result["checks"] = shown
    return result, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the lower-precision control in the "
                         "program's place, and print the program's readings "
                         "beside it (for setting the check's limits; not "
                         "part of a benchmark run)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    devices = chips_or_exit(cell.chips)
    result, _ = run_cell(cell, devices, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
