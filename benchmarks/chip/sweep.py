#!/usr/bin/env python3
"""Find the knee of a cell's traffic mix: the highest arrival rate the
server sustains without a growing backlog.  Used once, to fix the rate
written in a mix file; the benchmark's runs do not call it.

    python3 benchmarks/chip/sweep.py --workload minitron_4b.chat \\
        --seed 7 --seconds 30 --factors 0.7,0.85,1,1.15

One process builds the server once.  A short probe at the mix's own
rate measures the wall time of a step; from it and the mean number of
steps a request of the mix holds a slot (prompt plus output, one token
a step), the capacity estimate is ``slots / (steps * step time)``.
Each factor times that estimate is then offered open-loop for
``--seconds`` and drained.  For each rate it prints the queue wait in
the first and last third of the window and the requests still queued
when the window closed: a backlog that grows shows as a last third
that waits much longer than the first.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run
from chipbench import records, traffic
from chipbench.drive import drive


def offer(loop, Request, mix, vocab, rate, seconds, seed):
    plan = traffic.schedule(dict(mix, rate_per_s=rate), seconds, seed, vocab)
    reqs = [Request(i, p.prompt, max_new_tokens=p.max_new_tokens)
            for i, p in enumerate(plan)]
    return drive(loop, plan, reqs, seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--factors", default="0.7,0.85,1,1.15")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    devices = run.chips_or_exit(cell.chips)
    run.use_compile_cache()
    loop, Request, _ = run.start_server(cell, devices, args.seed,
                                        run.Compiles())
    mix, vocab = cell.mix, cell.config["model"]["vocab_size"]

    probe = offer(loop, Request, mix, vocab, mix["rate_per_s"], 10.0, args.seed + 1)
    busy = [s for s in probe.steps if s.n_tokens]
    step_s = float(np.median([s.end - s.start for s in busy]))
    n = 1000
    steps = np.mean(traffic.quantile_lengths(mix["prompt"], n)
                    + traffic.quantile_lengths(mix["output"], n) - 1)
    est = mix["server"]["slots"] / (steps * step_s)
    print(json.dumps({"probe_step_ms": 1e3 * step_s,
                      "mean_steps_per_request": float(steps),
                      "capacity_estimate_per_s": est}), flush=True)
    for f in (float(x) for x in args.factors.split(",")):
        rate = f * est
        d = offer(loop, Request, mix, vocab, rate, args.seconds, args.seed + 2)
        recs = d.records
        third = args.seconds / 3
        wait = [((r.admit if r.admit is not None else d.end) - r.due, r.due)
                for r in recs]
        first = [w for w, due in wait if due < third]
        last = [w for w, due in wait if due >= 2 * third]
        queued = sum(1 for r in recs
                     if r.admit is None or r.admit > args.seconds)
        print(json.dumps({
            "factor": f, "rate_per_s": rate, "requests": len(recs),
            "queue_wait_first_third_s": float(np.mean(first)),
            "queue_wait_last_third_s": float(np.mean(last)),
            "queued_at_close": queued,
            "ttft_p50_s": records.percentile(records.ttft_s(recs, d.end), 50),
            "ttft_p90_s": records.percentile(records.ttft_s(recs, d.end), 90),
            "itl_p50_ms": records.percentile(records.itl_ms(recs, d.end), 50),
            "itl_p99_ms": records.percentile(records.itl_ms(recs, d.end), 99),
            "unanswered": sum(not r.complete for r in recs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
