"""The serving loop's spans and the decode program's scopes, read from a
trace: ``chipbench.spans`` and the span readers on a synthetic trace
with known intervals, and ``run.py``'s traced run end to end on the CPU
at a tiny size."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(HERE / "data"))

from chipbench import spans, xplane  # noqa: E402
from chipbench.records import RequestRecord, StepRecord  # noqa: E402

PROG = "jit(decode_step)/jit(main)"


def step_ops(t0: float) -> list[tuple[str, float, float, str]]:
    """One decode program's ops from ``t0``: a layer loop holding an
    attention op, a cache write and an FFN op (which runs an op of no
    scope of its own inside it), then the unembedding and an op under no
    scope.  Self times: attn 40, kv_write 20, ffn 60, the loop 25,
    unembed 15, none 5; 165 in all."""
    body = f"{PROG}/layers/while/body"
    return [("%while.6 = (s32[]) while(...)", t0, 145, f"{PROG}/layers/while"),
            ("%fusion.1 = bf16[2,8]", t0 + 5, 40, f"{body}/attn/dot_general"),
            ("%dus.2 = bf16[2,4,8]", t0 + 45, 20,
             f"{body}/attn/kv_write/dynamic_update_slice"),
            ("%fusion.3 = bf16[2,16]", t0 + 65, 60, f"{body}/ffn/dot_general"),
            ("%fusion.9 = bf16[2,16]", t0 + 70, 10, ""),
            ("%fusion.4 = f32[2,512]", t0 + 145, 15, f"{PROG}/unembed/dot"),
            ("%gather.5 = bf16[2,8]", t0 + 160, 5, f"{PROG}/gather")]


def synthetic(devices: int = 1) -> spans.SpanTrace:
    """Two steps: the first [100, 400], the second [500, 800]."""
    host = [("bench.step", 95, 315, {}), ("bench.track", 410, 80, {}),
            ("bench.step", 495, 315, {})]
    for (s, admit, launch, pull, pick, stats) in (
            (100, (100, 10), (110, 20), (130, 200), (330, 60),
             {"prefill": 2, "decode": 0}),
            (500, (500, 5), (505, 10), (515, 185), (700, 80),
             {"prefill": 1, "decode": 1})):
        host += [("serve.step", s, 300, dict(stats, step_num=s // 400)),
                 ("serve.admit", *admit, {}), ("serve.launch", *launch, {}),
                 ("serve.pull", *pull, {}), ("serve.pick", *pick, {})]
    ops, mods = {}, {}
    for i in range(devices):
        dev = f"/device:TPU:{i}"
        ops[dev] = (step_ops(135) + [("%slice.1", 305, 5, "jit(slice)")]
                    + step_ops(520) + [("%slice.1", 690, 5, "jit(slice)")])
        mods[dev] = [("jit_decode_step(7)", 135, 165), ("jit_slice(8)", 305, 5),
                     ("jit_decode_step(7)", 520, 165), ("jit_slice(8)", 690, 5)]
    return spans.SpanTrace(host=host, ops=ops, modules=mods)


@pytest.mark.parametrize("devices", [1, 4])
def test_span_readings_of_known_intervals(devices):
    r = spans.reduce(synthetic(devices))
    ms = 1e-6
    assert r["steps"] == 2
    assert r["launch_ms"] == pytest.approx((30 + 15) / 2 * ms)
    # the decode program ends at 300 and 685; the pulls at 330 and 700
    assert r["pull_ms"] == pytest.approx((30 + 15) / 2 * ms)
    assert r["pick_ms"] == pytest.approx((60 + 80) / 2 * ms)
    assert r["program_ms"] == pytest.approx(165 * ms)
    assert r["attn_ms"] == pytest.approx(40 * ms)
    assert r["ffn_ms"] == pytest.approx(60 * ms)
    assert r["unembed_ms"] == pytest.approx(15 * ms)
    assert r["layer_cache_ms"] == pytest.approx((20 + 25) * ms)
    assert r["unscoped_ms"] == pytest.approx(5 * ms)
    assert sum(r[k] for k in ("attn_ms", "ffn_ms", "unembed_ms",
                              "layer_cache_ms", "unscoped_ms")) \
        == pytest.approx(r["program_ms"])
    assert r["prefill_share"] == pytest.approx(75.0)
    # idle [100,135] [300,305] [310,520] [685,690] [695,800]: 360, of
    # which [400,500] lies between the steps
    assert r["idle_ms"] == pytest.approx(360 / 2 * ms)
    assert r["idle_in_serve_share"] == pytest.approx(100 * 260 / 360)
    # each gap divided among the innermost spans it crosses
    assert r["idle_by_span_ms"] == pytest.approx({
        "serve.admit": 15 / 2 * ms, "serve.launch": 30 / 2 * ms,
        "serve.pull": 45 / 2 * ms, "serve.pick": 140 / 2 * ms,
        "serve.step": 30 / 2 * ms, "bench.step": 15 / 2 * ms,
        "bench.track": 80 / 2 * ms, "host idle (no span)": 5 / 2 * ms})


def test_step_counts_and_the_programs_other_spans():
    """``step_stats``: each count on the steps' stats, a mean per step;
    ``span_ms``: each other ``serve.*`` span of the program, its time
    inside the steps, a step's.  Neither moves another reading."""
    plain = spans.reduce(synthetic())
    assert plain["step_stats"] == {"decode": 0.5, "prefill": 1.5}
    assert plain["span_ms"] == {}
    st = synthetic()
    more = ({"queued": 3, "load": 0.25, "compiled": 2, "experts": [1, 2],
             "rids_admitted": "7 8", "_r": 1},
            {"queued": 0, "load": 0.75, "experts": [3], "rids_admitted": 9,
             "_r": 1})
    for (_, _, _, stats), extra in zip(
            [e for e in st.host if e[0] == "serve.step"], more):
        stats.update(extra)
    st.host += [("serve.reset", 90, 50, {}),    # 40 inside the first step
                ("serve.reset", 600, 10, {}),   # inside the second
                ("serve.reset", 820, 30, {}),   # after the steps
                ("serve.route", 530, 4, {"tokens": 12})]
    r = spans.reduce(st)
    assert r["step_stats"] == {"compiled": 1.0, "decode": 0.5, "load": 0.5,
                               "prefill": 1.5, "queued": 1.5}
    assert r["span_ms"] == {"serve.reset": (40 + 10) / 2 * 1e-6,
                            "serve.route": 4 / 2 * 1e-6}
    new = ("step_stats", "span_ms")
    assert {k: v for k, v in r.items() if k not in new} == \
        {k: v for k, v in plain.items() if k not in new}


def test_a_pull_after_the_device_is_done_counts_whole():
    st = synthetic()
    # the first step's program ends before its pull starts
    st.modules["/device:TPU:0"][0] = ("jit_decode_step(7)", 112, 10)
    r = spans.reduce(st)
    assert r["pull_ms"] == pytest.approx((200 + 15) / 2 * 1e-6)


def test_no_serve_steps_reads_nothing():
    st = synthetic()
    st.host = [e for e in st.host if not e[0].startswith("serve.")]
    assert spans.reduce(st) == {}


def test_scope_paths_from_the_decode_programs_hlo_text():
    hlo = (
        "ENTRY %main {\n"
        "  %fusion.150 = bf16[28,9216]{1,0} fusion(%a, %b), kind=kOutput, "
        'calls=%fc.150, metadata={op_name="jit(decode_step)/layers/while/'
        'body/ffn/dot_general" source_file="t.py" source_line=3}\n'
        "  ROOT %copy.89 = bf16[2,4]{1,0} copy(%c)\n"
        "  %dus.4 = bf16[2]{0} dynamic-update-slice(%d), metadata={"
        'op_type="dus" op_name="jit(decode_step)/layers/while/body/attn/'
        'kv_write/dynamic_update_slice"}\n}\n')
    names = spans.hlo_op_names(hlo)
    assert set(names) == {"fusion.150", "dus.4"}
    assert spans.scope_of(names["fusion.150"]) == "ffn"
    assert spans.scope_of(names["dus.4"]) == "kv_write"
    assert spans.scope_of("jit(decode_step)/layers/while") == "layers"
    assert spans.scope_of("jit(decode_step)/gather") == ""
    assert spans.instruction("%fusion.150 = bf16[28,9216]{1,0} fusion(x)") \
        == "fusion.150"
    assert spans.instruction("copy.89") == "copy.89"


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", ["host_ms.chat", "idle_share.chat",
                                  "queue_wait_share.chat", "step_mfu.chat",
                                  "step_roofline.chat"])
def test_existing_readers_ignore_the_span_readings(name):
    from chipbench import peaks
    from chipbench.archs import dense_gqa
    recs = [RequestRecord(0.0, 4, 3, sent=0.0, admit=0.01, admit_step=0,
                          stamps=[0.1, 0.2, 0.3])]
    steps = [StepRecord(0.01 * k, 0.01 * k + 0.008, (k + 1,), 1)
             for k in range(6)]
    conf = json.loads((BENCH / "configs" / "minitron_4b.json").read_text())
    ctx = SimpleNamespace(
        records=recs, steps=steps, end=0.4, chips=1, seconds=0.4,
        config=conf, shapes=dense_gqa.Shapes.of(conf["model"], conf["dtype"]),
        peak=peaks.peak_for("TPU v5 lite"), traced_steps=(0, 6),
        trace=xplane.reduce(_xplane_trace()), spans={})
    plain = reader(name)(ctx)
    ctx.spans = spans.reduce(synthetic())
    assert plain is not None and reader(name)(ctx) == plain


SPAN_METRICS = {"launch_ms.chat": "launch_ms", "pull_ms.chat": "pull_ms",
                "pick_ms.chat": "pick_ms", "attn_ms.chat": "attn_ms",
                "ffn_ms.chat": "ffn_ms", "unembed_ms.chat": "unembed_ms",
                "layer_cache_ms.chat": "layer_cache_ms",
                "unscoped_ms.chat": "unscoped_ms"}


@pytest.mark.parametrize("name", list(SPAN_METRICS))
def test_span_readers_read_the_span_readings(name):
    r = spans.reduce(synthetic(4))
    assert reader(name)(SimpleNamespace(spans=r)) == r[SPAN_METRICS[name]]
    assert r[SPAN_METRICS[name]] > 0
    assert reader(name)(SimpleNamespace(spans={})) is None


def test_scopes_are_data():
    """A scope that the configuration names gets its ops' time; ops under
    a scope it does not name go to the scope that holds them."""
    st = synthetic()
    for dev, ops in st.ops.items():
        st.ops[dev] = [(n, s, d, p.replace("/ffn/", "/moe/"))
                       for n, s, d, p in ops]
    default = spans.reduce(st)
    moe = spans.reduce(st, scopes=spans.SCOPES + ("moe",))
    ms = 1e-6
    assert default["ffn_ms"] == 0 and moe["ffn_ms"] == 0
    assert moe["scope_ms"]["moe"] == pytest.approx(60 * ms)
    assert "moe" not in default["scope_ms"]
    # without the scope, its ops fall to the layer loop that holds them
    assert default["layer_cache_ms"] == pytest.approx((20 + 25 + 60) * ms)
    assert moe["layer_cache_ms"] == pytest.approx((20 + 25) * ms)
    assert sum(moe["scope_ms"].values()) == pytest.approx(moe["program_ms"])


def test_a_trace_without_device_ops_reads_no_scopes():
    st = synthetic()
    st.ops, st.modules = {}, {}
    r = spans.reduce(st)
    assert r["launch_ms"] > 0 and r["pull_ms"] is None
    assert "attn_ms" not in r and "scope_ms" not in r


def _xplane_trace() -> xplane.Trace:
    st = synthetic()
    return xplane.Trace(
        ops={d: [(n, s, t) for n, s, t, _ in evs] for d, evs in st.ops.items()},
        modules=st.modules,
        host=[(n, s, d) for n, s, d, _ in st.host])


def test_traced_run_with_the_programs_spans_on_the_cpu(tmp_path, capsys):
    """``run.run_cell(..., trace=True)`` on the tiny cell: the program's
    spans reach the profiler's trace on the driving thread, with the
    step stats, and the span readers read them."""
    import jax
    import run
    import run_tiny
    run.CACHE_DIR = tmp_path / "cache"
    cell = run_tiny.tiny_cell()
    cell.per_layer = [{"name": n, "unit": "ms"} for n in SPAN_METRICS] + [
        {"name": "queue_wait_share.chat", "unit": "%"}]
    result, _ = run.run_cell(cell, jax.devices()[:1], seed=2**31 + 17,
                             seconds=3.0, trace=True, peak=run_tiny.PEAK)
    assert result["correct"]
    assert "queue_wait_share.chat" in result["metrics"]
    line = [x for x in capsys.readouterr().err.splitlines()
            if x.startswith("program spans: ")]
    r = json.loads(line[-1].removeprefix("program spans: "))
    assert r["steps"] > 0 and r["launch_ms"] > 0 and r["pick_ms"] > 0
    assert 0 < r["prefill_share"] < 100
    # the step's counts, whatever form the profiler gives them; the
    # request ids, a number where a step admitted one, are no count
    counts = r["step_stats"]
    assert {"admitted", "prefill", "decode", "busy", "queued"} <= set(counts)
    assert not any(k.startswith(spans.NOT_COUNTS) for k in counts)
    assert counts["busy"] == pytest.approx(counts["prefill"] + counts["decode"])
    assert r["prefill_share"] == pytest.approx(
        100 * counts["prefill"] / counts["busy"])
    assert r["span_ms"] == {}
    # the CPU's trace holds no device plane, so only the host spans read
    got = result["metrics"]
    assert got["launch_ms.chat"]["value"] == r["launch_ms"]
    assert got["pick_ms.chat"]["value"] == r["pick_ms"]
