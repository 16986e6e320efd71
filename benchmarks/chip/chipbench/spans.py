"""From a profiler trace to the serving loop's own spans and the decode
program's time by named scope.

``extract`` reads from the same ``.xplane.pb`` as ``xplane.extract``:

* on the host thread that drives the loop, the program's ``serve.*``
  spans (``repro.runtime.serve_loop``, mirrored into the profiler's
  trace by ``repro.obs.Tracer(profiler=True)``) with their stats, and
  the harness's ``bench.*`` spans;
* on each device, every op with the path of named scopes it was traced
  under (``layers``, ``attn``, ``kv_write``, ``ffn``, ``unembed``, from
  ``models/transformer.LM.decode_step``), and the executions of the
  programs.

A TPU trace's op events carry no ``op_name`` (their stats are the
device offset and duration), so an op's scope path comes from the
decode program's compiled HLO text (``op_names``: instruction name ->
``op_name``, ``hlo_op_names``).  ``reduce`` works on a ``SpanTrace``
alone, so it is tested on a synthetic one with known intervals.  All
times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

from .xplane import _union, self_times

SCOPES = ("layers", "attn", "kv_write", "ffn", "unembed")
STEP = "serve.step"
CHILDREN = ("serve.admit", "serve.launch", "serve.pull", "serve.pick")


@dataclass
class SpanTrace:
    # spans of the thread that drives the loop: (name, start, dur, stats)
    host: list[tuple[str, float, float, dict]] = field(default_factory=list)
    # device -> [(op name, start, dur, scope path)]; "" where none
    ops: dict[str, list[tuple[str, float, float, str]]] = field(
        default_factory=dict)
    # device -> [(program name, start, dur)]
    modules: dict[str, list[tuple[str, float, float]]] = field(
        default_factory=dict)


_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` metadata, from a compiled
    program's HLO text (``compiled.as_text()``)."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


def instruction(op: str) -> str:
    """The HLO instruction an op event is named for: ``%fusion.1 =
    bf16[...] fusion(...)`` or ``fusion.1`` -> ``fusion.1``."""
    return op.split(" = ")[0].strip().lstrip("%")


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or ``""``."""
    inner = ""
    for part in path.split("/"):
        if part in SCOPES:
            inner = part
    return inner


def extract(path: Path, op_names: dict[str, str] | None = None) -> SpanTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    st = SpanTrace()
    op_names = op_names or {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns, e.duration_ns,
                            op_names.get(instruction(e.name), ""))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            if ops or mods:
                st.ops[plane.name] = ops
                st.modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                       for e in line.events
                       if e.name.startswith(("serve.", "bench."))]
                if any(name == STEP for name, *_ in evs):
                    st.host.extend(evs)
    return st


def _steps(st: SpanTrace) -> list[dict]:
    """Each ``serve.step`` with the interval of each of its children."""
    spans = sorted(st.host, key=lambda e: e[1])
    steps = [{"start": s, "end": s + d, "stats": stats}
             for name, s, d, stats in spans if name == STEP]
    i = 0
    for name, s, d, _ in spans:
        if name not in CHILDREN:
            continue
        while i < len(steps) and steps[i]["end"] < s:
            i += 1
        if i < len(steps) and steps[i]["start"] <= s:
            steps[i][name] = (s, s + d)
    return [x for x in steps if all(c in x for c in CHILDREN)]


def _program_end(st: SpanTrace, lo: float, hi: float,
                 program: str) -> float | None:
    """The latest end, over the devices, of an execution of ``program``
    that starts in ``[lo, hi]``."""
    ends = [s + d for mods in st.modules.values()
            for name, s, d in mods if program in name and lo <= s <= hi]
    return max(ends, default=None)


def reduce(st: SpanTrace, program: str = "decode_step") -> dict:
    """Per step of the serving loop, in ms and averaged over the traced
    steps: ``launch_ms`` (start of the step to the end of its launch),
    ``pull_ms`` (end of the step's decode program on the latest chip to
    the end of the pull; the pull's earlier part waits on the device),
    ``pick_ms``; the decode program's device self time by innermost
    scope, averaged over the chips too (``attn_ms`` is ``attn`` less
    ``kv_write``, ``layer_cache_ms`` is ``kv_write`` and ``layers``
    outside ``attn``/``ffn``, ``unscoped_ms`` is the ops in none; an op
    with no scope of its own takes that of the op it runs inside);
    ``program_ms``, the decode program's device time; ``prefill_share``
    (%) from the steps' stats; and the device's idle time, its share
    inside the steps, and how it divides among the innermost host spans
    it falls in.  Empty where the trace has no step with its four
    children."""
    steps = _steps(st)
    if not steps:
        return {}
    n = len(steps)
    lo, hi = steps[0]["start"], steps[-1]["end"]
    pulls = []
    for x in steps:
        p0, p1 = x["serve.pull"]
        end = _program_end(st, x["serve.launch"][0], p1, program)
        if end is not None:
            pulls.append(p1 - min(max(end, p0), p1))
    out = {
        "steps": n,
        "launch_ms": sum(x["serve.launch"][1] - x["start"]
                         for x in steps) / n * 1e-6,
        "pull_ms": sum(pulls) / len(pulls) * 1e-6 if pulls else None,
        "pick_ms": sum(x["serve.pick"][1] - x["serve.pick"][0]
                       for x in steps) / n * 1e-6,
    }
    pre = sum(x["stats"].get("prefill", 0) for x in steps)
    dec = sum(x["stats"].get("decode", 0) for x in steps)
    out["prefill_share"] = 100.0 * pre / (pre + dec) if pre + dec else None

    devices = sorted(d for d, ops in st.ops.items() if ops)
    by_scope: dict[str, float] = defaultdict(float)
    prog = 0.0
    idle_by_span: dict[str, float] = defaultdict(float)
    idle = idle_in_steps = 0.0
    step_iv = [(x["start"], x["end"]) for x in steps]
    for dev in devices:
        runs = [(s, s + d) for name, s, d in st.modules.get(dev, [])
                if program in name and lo <= s <= hi]
        prog += sum(b - a for a, b in runs)
        inside = [(f"{i}|{scope}", max(s, a), min(s + d, b) - max(s, a))
                  for i, (name, s, d, scope) in enumerate(_inherit(st.ops[dev]))
                  for a, b in runs if s < b and s + d > a]
        for key, t in self_times(inside).items():
            by_scope[key.split("|", 1)[1]] += t
        merged = _union([(max(s, lo), min(s + d, hi))
                         for _, s, d, _ in st.ops[dev] if s + d > lo and s < hi])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for a, b in gaps:
            idle += b - a
            idle_in_steps += sum(max(0.0, min(b, e) - max(a, s))
                                 for s, e in step_iv)
            cuts = sorted({a, b} | {t for _, s, d, _ in st.host
                                    for t in (s, s + d) if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                idle_by_span[_innermost(st.host, (x + y) / 2)] += y - x
    k = len(devices) or 1
    per = 1e-6 / k / n
    out["program_ms"] = prog * per
    out["attn_ms"] = by_scope["attn"] * per
    out["ffn_ms"] = by_scope["ffn"] * per
    out["unembed_ms"] = by_scope["unembed"] * per
    out["layer_cache_ms"] = (by_scope["kv_write"] + by_scope["layers"]) * per
    out["unscoped_ms"] = by_scope[""] * per
    out["idle_ms"] = idle * per
    out["idle_in_serve_share"] = 100.0 * idle_in_steps / idle if idle else None
    out["idle_by_span_ms"] = {name: t * per for name, t in sorted(
        idle_by_span.items(), key=lambda kv: -kv[1])}
    return out


def _inherit(ops):
    """Each op with its innermost scope; an op that has none of its own
    takes that of the innermost op whose interval holds it: XLA's loops
    run their bodies inside the loop's own event, and fusions it clones
    into a body carry no ``op_name``."""
    out, stack = [], []
    for name, s, d, path in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        scope = scope_of(path) or (stack[-1][1] if stack else "")
        stack.append((s + d, scope))
        out.append((name, s, d, scope))
    return out


def _innermost(host, t: float) -> str:
    """The innermost host span that covers time ``t``."""
    best, best_d = "host idle (no span)", float("inf")
    for name, s, d, _ in host:
        if s <= t <= s + d and d < best_d:
            best, best_d = name, d
    return best
