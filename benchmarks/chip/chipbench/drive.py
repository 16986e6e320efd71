"""The open loop: send requests when they are due, step the server.

The server is driven from outside through three things only: its
``submit(request)``, its ``run(max_steps=1)``, which admits queued
requests to free slots, runs one decode step and returns the requests
it finished, and the length of its queue of requests not yet admitted.
Of a request the harness reads only ``out``, the tokens generated.

Every step is timed on the host clock.  Each token a request gets is
stamped with the end of the step that produced it, which is after the
host has the step's logits, so the device has finished.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass

from .records import RequestRecord, StepRecord
from .traffic import Planned


@dataclass
class Drive:
    records: list[RequestRecord]
    steps: list[StepRecord]
    end: float                          # drain finished (or gave up)
    traced_steps: tuple[int, int] = (0, 0)      # [first, last) step index
    paused_s: float = 0.0               # the clock stood still this long


def drive(loop, plan: list[Planned], requests: list, seconds: float, *,
          drain_limit_s: float = 120.0, trace: tuple[float, float] | None = None,
          profiler=None, annotate=None) -> Drive:
    """Offer ``requests`` (the program's request objects for ``plan``,
    in the same order) to ``loop`` at their due times over a window of
    ``seconds``, then drain.

    With ``trace = (a, b)``, ``profiler.start()`` is called before the
    first step that starts at ``a`` s or later and ``profiler.stop()``
    after the first step that ends at ``b`` s or later.  Stopping the
    profiler holds the host for as long as it takes to collect the
    trace; the harness's clock stands still meanwhile, so that no
    request's time counts that pause.  ``annotate`` gives a context
    manager per host span name (``None``: no spans).
    """
    span = annotate or (lambda name: nullcontext())
    recs = [RequestRecord(p.due_s, len(p.prompt), p.max_new_tokens)
            for p in plan]
    index = {id(r): i for i, r in enumerate(requests)}
    steps: list[StepRecord] = []
    queued: deque[int] = deque()
    inflight: list[int] = []
    traced_steps = [0, 0]
    tracing = started = False
    paused = 0.0
    n, nxt, k = len(plan), 0, 0
    clock = time.perf_counter
    t0 = clock()
    while True:
        now = clock() - t0
        if nxt < n and plan[nxt].due_s <= now:
            with span("bench.submit"):
                while nxt < n and plan[nxt].due_s <= now:
                    loop.submit(requests[nxt])
                    recs[nxt].sent = now
                    queued.append(nxt)
                    nxt += 1
        if not queued and not inflight:
            if nxt >= n:
                break
            with span("bench.wait_arrival"):
                wait = plan[nxt].due_s - (clock() - t0)
                if wait > 0:
                    time.sleep(wait)
            continue
        if now > seconds + drain_limit_s:
            break
        if trace and not started and now >= trace[0]:
            profiler.start()
            tracing = started = True
            traced_steps[0] = k
        ts = clock() - t0
        with span("bench.step"):
            finished = loop.run(max_steps=1)
        te = clock() - t0
        with span("bench.track"):
            for _ in range(len(queued) - len(loop.queue)):
                j = queued.popleft()
                recs[j].admit, recs[j].admit_step = ts, k
                inflight.append(j)
            ctx_lens = tuple(k - recs[j].admit_step + 1 for j in inflight)
            n_logits = 0
            for j in inflight:
                new = len(requests[j].out) - recs[j].n_out
                if new > 0:
                    n_logits += new
                    recs[j].stamps.extend([te] * new)
            steps.append(StepRecord(ts, te, ctx_lens, n_logits))
            done = {index[id(r)] for r in finished if id(r) in index}
            if done:
                inflight = [j for j in inflight if j not in done]
        k += 1
        if tracing and te >= trace[1]:
            t_stop = clock()
            profiler.stop()
            paused = clock() - t_stop
            t0 += paused
            tracing, traced_steps[1] = False, k
    end = clock() - t0
    if tracing:
        profiler.stop()
        traced_steps[1] = k
    return Drive(recs, steps, end, tuple(traced_steps), paused)
