"""From a profiler trace to device busy time, idle gaps and program time.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
(``load``) into a small ``Trace``: per device, the intervals of its
operations and of its program executions; on the host, the spans of
the thread that drives the loop (the harness's ``bench.*`` spans and
what runs inside them).  ``reduce`` works on a ``Trace`` alone, so it is tested on a
synthetic one with known intervals.

The window is the trace's own: from the start of the first
``bench.step`` span to the end of the last.  All times in the trace
are nanoseconds on one clock.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

STEP_SPAN = "bench.step"


@dataclass
class Trace:
    # device name -> [(op name, start_ns, dur_ns)]
    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    # device name -> [(program name, start_ns, dur_ns)]
    modules: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    # spans of the host thread that holds the harness's step spans
    host: list[tuple[str, float, float]] = field(default_factory=list)
    # every plane with the names of its lines and their event counts
    planes: dict[str, dict[str, int]] = field(default_factory=dict)


def load(path: Path):
    """The profile in an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def extract(data) -> Trace:
    """``data``: a ``jax.profiler.ProfileData`` (``load``)."""
    tr = Trace()
    host_lines = []
    for plane in data.planes:
        tr.planes[plane.name] = {line.name: len(list(line.events))
                                 for line in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if line.name == "XLA Ops":
                    ops = evs
                elif line.name == "XLA Modules":
                    mods = evs
            if ops or mods:
                tr.ops[plane.name] = ops or mods
                tr.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                if any(name == STEP_SPAN for name, _, _ in evs):
                    host_lines.append(evs)
    for evs in host_lines:
        tr.host.extend(evs)
    return tr


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def short(name: str) -> str:
    """An HLO op's name and output shape, without its operands:
    ``%fusion.1 = bf16[28,9216]{...} fusion(...)`` -> ``%fusion.1 =
    bf16[28,9216]``."""
    return name.split("{")[0].split("(")[0].strip()


def self_times(events: list[tuple[str, float, float]]):
    """Each event's duration less that of the events nested in it
    (a loop's body ops run inside the loop's own event)."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []          # [name, end, self time]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            n, _, t = stack.pop()
            out[n] += t
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    for n, _, t in stack:
        out[n] += t
    return out


def _labels(host: list[tuple[str, float, float]], times: list[float]):
    """The innermost host span that covers each of ``times``.  Spans of
    one thread nest, so a stack swept along time finds it."""
    spans = sorted(host, key=lambda e: (e[1], -e[2]))
    out, stack, i = {}, [], 0
    for t in sorted(times):
        while i < len(spans) and spans[i][1] <= t:
            name, s, d = spans[i]
            while stack and stack[-1][1] < s:
                stack.pop()
            stack.append((name, s + d))
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out[t] = stack[-1][0] if stack else "host idle (no span)"
    return out


def reduce(tr: Trace, program: str = "decode_step", top: int = 10) -> dict:
    """Busy and idle time per device, the device time of the programs
    whose name contains ``program``, host time per step, and the
    breakdown: the device ops that took most time, and the idle time
    by the host span it fell in (summed over the devices, divided by
    their number)."""
    steps = [(s, s + d) for name, s, d in tr.host if name == STEP_SPAN]
    if not steps or not any(tr.ops.values()):
        return {}
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    window = (hi - lo) * 1e-9
    busy, prog, n_prog = {}, {}, {}
    op_time: dict[str, float] = defaultdict(float)
    mod_time: dict[str, float] = defaultdict(float)
    gaps: dict[str, float] = defaultdict(float)
    devices = sorted(d for d, evs in tr.ops.items() if evs)
    for dev in devices:
        spans = _clip([(s, s + d) for _, s, d in tr.ops[dev]], lo, hi)
        merged = _union(spans)
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        inside = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
                  for n, s, d in tr.ops[dev] if s + d > lo and s < hi]
        for name, t in self_times(inside).items():
            op_time[short(name)] += t * 1e-9 / len(devices)
        for name, s, d in tr.modules.get(dev, []):
            if lo <= s + d / 2 <= hi:
                mod_time[name.split("(")[0]] += d * 1e-9 / len(devices)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        label = _labels(tr.host, [(a + b) / 2 for a, b in idle])
        for a, b in idle:
            gaps[label[(a + b) / 2]] += (b - a) * 1e-9 / len(devices)
        mods = [(s, d) for name, s, d in tr.modules.get(dev, [])
                if program in name and lo <= s + d / 2 <= hi]
        prog[dev] = sum(d for _, d in mods) * 1e-9
        n_prog[dev] = len(mods)
    n = len(devices)
    step_wall = sum(b - a for a, b in steps) * 1e-9
    prog_mean = sum(prog.values()) / n
    return {
        "window_s": window,
        "busy_s": sum(busy.values()) / n,
        "busy_by_device": busy,
        "idle_share": 1.0 - sum(busy.values()) / n / window,
        "program_s": prog_mean,
        "program_runs": n_prog,
        "steps": len(steps),
        "host_per_step_s": (step_wall - prog_mean) / len(steps),
        "programs": sorted(mod_time.items(), key=lambda kv: -kv[1])[:top],
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
    }
