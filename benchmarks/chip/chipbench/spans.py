"""From a profiler trace to the serving loop's own spans and the decode
program's time by named scope.

``extract`` reads from the same profile as ``xplane.extract``:

* on the host thread that drives the loop, the program's ``serve.*``
  spans (``repro.runtime.serve_loop``, mirrored into the profiler's
  trace by ``repro.obs.Tracer(profiler=True)``) with their stats, and
  the harness's ``bench.*`` spans;
* on each device, every op with the path of named scopes it was traced
  under, and the executions of the programs.

Which named scopes ``reduce`` gives time to is data: a configuration's
``"scopes"`` list, by default ``SCOPES``, those of
``models/transformer.LM.decode_step``.

A TPU trace's op events carry no ``op_name`` (their stats are the
device offset and duration), so an op's scope path comes from the
decode program's compiled HLO text (``op_names``: instruction name ->
``op_name``, ``hlo_op_names``).  ``reduce`` works on a ``SpanTrace``
alone, so it is tested on a synthetic one with known intervals.  All
times are nanoseconds on the trace's one clock.

A per-layer reader gets ``reduce``'s dict as ``ctx.spans``.  Besides
the step's own timings, it holds every count the program puts on its
``serve.step`` spans (``step_stats``, a mean per step) and the time of
every other ``serve.*`` span of the program (``span_ms``), so that a
count or a host span that a new model adds to the serving loop is read
by a new reader file alone.
"""
from __future__ import annotations

import re
from bisect import bisect_left, bisect_right
from collections import defaultdict
from dataclasses import dataclass, field

from .xplane import _labels, _union, self_times

SCOPES = ("layers", "attn", "kv_write", "ffn", "unembed")
STEP = "serve.step"
CHILDREN = ("serve.admit", "serve.launch", "serve.pull", "serve.pick")
# ``serve.step`` stats that are no counts: the step's number, the
# profiler's own (``_r``), and the ids of the requests a step admitted
# or finished, a list that reaches the trace as a string of ids or, one
# id alone, as a number
NOT_COUNTS = ("step_num", "_", "rids_")


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


def scope_of(path: str, scopes=SCOPES) -> str:
    """The innermost of ``scopes`` in an ``op_name`` path, or ``""``."""
    inner = ""
    for part in path.split("/"):
        if part in scopes:
            inner = part
    return inner


def extract(data, op_names: dict[str, str] | None = None) -> SpanTrace:
    """``data``: a ``jax.profiler.ProfileData``."""
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


def _overlapping(iv: list[tuple[float, float]], starts: list[float],
                 a: float, b: float):
    """The intervals of ``iv`` (sorted, not overlapping; ``starts`` their
    starts) that overlap ``(a, b)``."""
    i = max(bisect_right(starts, a) - 1, 0)
    while i < len(iv) and iv[i][0] < b:
        if iv[i][1] > a:
            yield iv[i]
        i += 1


def reduce(st: SpanTrace, program: str = "decode_step",
           scopes=SCOPES) -> dict:
    """Per step of the serving loop, in ms and averaged over the traced
    steps: ``launch_ms`` (start of the step to the end of its launch),
    ``pull_ms`` (end of the step's decode program on the latest chip to
    the end of the pull; the pull's earlier part waits on the device),
    ``pick_ms``; ``prefill_share`` (%) from the steps' stats;
    ``step_stats``, each count on the steps' stats (every number but
    ``NOT_COUNTS``; a stat that is no number on some step is no count),
    its mean per step, a step without it counting 0; ``span_ms``, each
    ``serve.*`` span other than the step and its four children by name,
    its time inside the steps, ms a step; and, where
    the trace holds device ops, the decode program's device self time by
    innermost scope of ``scopes``, averaged over the chips too
    (``scope_ms``, ``""`` for the ops in none; an op with no scope of
    its own takes that of the op it runs inside), and of the default
    scopes ``attn_ms`` (``attn`` less ``kv_write``), ``ffn_ms``,
    ``unembed_ms``, ``layer_cache_ms`` (``kv_write`` and ``layers``
    outside ``attn``/``ffn``) and ``unscoped_ms``; ``program_ms``, the
    decode program's device time; and the device's idle time, its share
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
    out["step_stats"] = _counts([x["stats"] for x in steps])
    step_iv = [(x["start"], x["end"]) for x in steps]
    step_starts = [a for a, _ in step_iv]
    inside: dict[str, float] = defaultdict(float)
    for name, s, d, _ in st.host:
        if name.startswith("serve.") and name not in (STEP, *CHILDREN):
            for a, b in _overlapping(step_iv, step_starts, s, s + d):
                inside[name] += min(b, s + d) - max(a, s)
    out["span_ms"] = {name: t / n * 1e-6 for name, t in sorted(inside.items())}

    devices = sorted(d for d, ops in st.ops.items() if ops)
    if not devices:
        return out
    by_scope: dict[str, float] = defaultdict(float)
    prog = 0.0
    idle = idle_in_steps = 0.0
    pieces: list[tuple[float, float]] = []
    edges_host = sorted({t for _, s, d, _ in st.host for t in (s, s + d)})
    for dev in devices:
        runs = sorted((s, s + d) for name, s, d in st.modules.get(dev, [])
                      if program in name and lo <= s <= hi)
        run_starts = [a for a, _ in runs]
        prog += sum(b - a for a, b in runs)
        inside = [(f"{i}|{scope}", max(s, a), min(s + d, b) - max(s, a))
                  for i, (name, s, d, scope)
                  in enumerate(_inherit(st.ops[dev], scopes))
                  for a, b in _overlapping(runs, run_starts, s, s + d)]
        for key, t in self_times(inside).items():
            by_scope[key.split("|", 1)[1]] += t
        merged = _union([(max(s, lo), min(s + d, hi))
                         for _, s, d, _ in st.ops[dev] if s + d > lo and s < hi])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            idle += b - a
            idle_in_steps += sum(min(b, e) - max(a, s) for s, e in
                                 _overlapping(step_iv, step_starts, a, b))
            cuts = [a] + edges_host[bisect_right(edges_host, a):
                                    bisect_left(edges_host, b)] + [b]
            pieces.extend(zip(cuts, cuts[1:]))
    label = _labels([(name, s, d) for name, s, d, _ in st.host],
                    [(x + y) / 2 for x, y in pieces])
    idle_by_span: dict[str, float] = defaultdict(float)
    for x, y in pieces:
        idle_by_span[label[(x + y) / 2]] += y - x
    per = 1e-6 / len(devices) / n
    out["program_ms"] = prog * per
    out["scope_ms"] = {k: by_scope[k] * per for k in ("", *scopes)}
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


def _counts(stats: list[dict]) -> dict[str, float]:
    """Each count of the steps' ``stats``, its mean over the steps."""
    sums: dict[str, float] = defaultdict(float)
    others = set()
    for one in stats:
        for key, value in one.items():
            if isinstance(value, (int, float)):
                sums[key] += value
            else:
                others.add(key)
    return {key: t / len(stats) for key, t in sorted(sums.items())
            if key not in others and not key.startswith(NOT_COUNTS)}


def _inherit(ops, scopes):
    """Each op with its innermost scope; an op that has none of its own
    takes that of the innermost op whose interval holds it: XLA's loops
    run their bodies inside the loop's own event, and fusions it clones
    into a body carry no ``op_name``."""
    out, stack = [], []
    for name, s, d, path in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        scope = scope_of(path, scopes) or (stack[-1][1] if stack else "")
        stack.append((s + d, scope))
        out.append((name, s, d, scope))
    return out
