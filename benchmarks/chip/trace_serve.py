#!/usr/bin/env python3
"""A traced run of a cell with the program's own spans: where the time
of a serving step goes, by span of ``ServeLoop.run`` and by named scope
of the decode program.

    python3 benchmarks/chip/trace_serve.py --workload minitron_4b.chat \\
        --seed 1234 --seconds 51 --out trace_out

It makes ``run.py``'s traced run (``--trace 1``) with two additions:
the program's tracer, ``repro.obs.Tracer(profiler=True)``, is active
around the open loop, so the ``serve.*`` spans land in the profiler's
trace; and before the trace directory is removed, ``chipbench.spans``
reads the spans and the decode program's ops by scope from it.  It
prints ``run.py``'s result line, then a line with the span readings
(``chipbench.spans.reduce``) and, beside them, ``host_ms`` as the
harness computes it.  Under ``--out`` it leaves the readings, the
decode program's compiled HLO text and the gzipped ``.xplane.pb``.
Not a benchmark run: the benchmark's ``run.py`` does not activate the
program's tracer.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

import run
from chipbench import spans


def traced_run(cell, devices, *, seed: int, seconds: float, out: Path,
               peak: dict | None = None) -> tuple[dict, dict]:
    """``run.run_cell(..., trace=True)`` with the program's tracer on;
    returns (the result line, the span readings).  ``peak`` as
    ``run.run_cell`` takes it."""
    from repro.obs import Tracer, activate

    out.mkdir(parents=True, exist_ok=True)
    found: dict = {}
    drive, profiler = run.drive, run.Profiler

    def traced_drive(loop, *args, **kw):
        hlo = loop.decode_hlo()
        (out / "decode.hlo.txt").write_text(hlo)
        found["op_names"] = spans.hlo_op_names(hlo)
        with activate(Tracer(profiler=True)):
            return drive(loop, *args, **kw)

    class Keeping(profiler):
        def reduce(self) -> dict:
            paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
            if paths:
                with open(paths[-1], "rb") as f, \
                        gzip.open(out / "trace.xplane.pb.gz", "wb") as g:
                    shutil.copyfileobj(f, g)
                found["spans"] = spans.reduce(
                    spans.extract(paths[-1], found.get("op_names")))
            return super().reduce()

    run.drive, run.Profiler = traced_drive, Keeping
    try:
        result, _ = run.run_cell(cell, devices, seed=seed, seconds=seconds,
                                 trace=True, peak=peak)
    finally:
        run.drive, run.Profiler = drive, profiler
    readings = found.get("spans", {})
    host = result["metrics"].get("host_ms.chat")
    readings["host_ms"] = host["value"] if host else None
    (out / "readings.json").write_text(json.dumps(readings, indent=1))
    return result, readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.chips_or_exit(cell.chips)
    result, readings = traced_run(cell, devices, seed=args.seed,
                                  seconds=args.seconds, out=args.out)
    print(json.dumps(result), flush=True)
    print(json.dumps(readings), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
