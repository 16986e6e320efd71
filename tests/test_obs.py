"""repro.obs: spans, typed metrics, exporters — and the inertness
contract.

The load-bearing guarantees:

* **bit-identity** — makespans (all seven n=1000 families) and
  ``ServiceTrace``s are bit-identical with tracing on or off;
* **picklability** — histogram deltas ship through ``SweepPoint``
  across the ``workers=2`` process pool and merge in the parent;
* **Chrome-trace schema** — valid JSON, globally monotone ``ts``,
  matched B/E pairs per track (Perfetto's stack discipline).
"""
import json
import pickle

import pytest

from repro.core import (
    FAMILIES,
    ScheduleReport,
    default_cluster,
    generate_workflow,
    schedule,
)
from repro.obs import (
    METRICS,
    Histogram,
    JsonlSink,
    MetricsRegistry,
    ObsConfig,
    RATIO_BOUNDARIES,
    Span,
    Tracer,
    activate,
    percentile,
    percentiles,
    span_events,
    trace_span,
    tracing_active,
    write_chrome_trace,
)
from repro.service import ServiceConfig, Submission, run_service
from repro.service.report import ServiceReport


# ---------------------------------------------------------------------- #
# metrics registry
# ---------------------------------------------------------------------- #
class TestMetrics:
    def test_histogram_buckets_and_stats(self):
        h = Histogram(boundaries=(1.0, 10.0, 100.0))
        for v in (0.5, 1.0, 5.0, 50.0, 500.0):
            h.observe(v)
        # upper-edge inclusive: 1.0 lands in the first bucket
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        assert h.sum == pytest.approx(556.5)
        assert h.min == 0.5 and h.max == 500.0

    def test_histogram_dict_round_trip_and_merge(self):
        h = Histogram(boundaries=(1.0, 10.0))
        h.observe(0.3)
        h.observe(30.0)
        d = h.to_dict()
        assert Histogram.from_dict(d).to_dict() == d
        h2 = Histogram(boundaries=(1.0, 10.0))
        h2.observe(5.0)
        h2.merge_dict(d)
        assert h2.count == 3
        assert h2.min == 0.3 and h2.max == 30.0

    def test_percentiles_clamped_to_observed_range(self):
        h = Histogram(boundaries=(1.0, 10.0, 100.0))
        for v in (2.0, 3.0, 4.0, 5.0):
            h.observe(v)
        p = percentiles(h.to_dict())
        assert set(p) == {"p50", "p95", "p99"}
        for v in p.values():
            assert 2.0 <= v <= 5.0  # clamped to [min, max]
        assert p["p50"] <= p["p95"] <= p["p99"]
        assert percentile(h.to_dict(), 0) == pytest.approx(2.0)
        assert percentiles({}) is None

    def test_registry_snapshot_delta_merge(self):
        reg = MetricsRegistry()
        reg.counter("c", 2)
        reg.gauge("g", 1.5)
        reg.observe("h", 0.25)
        snap = reg.snapshot()
        reg.counter("c", 3)
        reg.gauge("g", 2.5)
        reg.observe("h", 0.75)
        d = reg.delta(snap)
        assert d["counters"] == {"c": 3}
        assert d["gauges"] == {"g": 2.5}
        assert d["histograms"]["h"]["count"] == 1
        # merging the delta into a snapshot-restored registry lands on
        # the current state (count/sum; min/max keep current values)
        reg2 = MetricsRegistry()
        reg2.restore(snap)
        reg2.merge(d)
        assert reg2.counters["c"] == 5
        assert reg2.histograms["h"].count == 2

    def test_delta_is_sparse_and_picklable(self):
        reg = MetricsRegistry()
        reg.observe("ratio", 1.02, boundaries=RATIO_BOUNDARIES)
        snap = reg.snapshot()
        reg.observe("ratio", 1.05, boundaries=RATIO_BOUNDARIES)
        d = reg.delta(snap)
        assert list(d) == ["histograms"]  # nothing else moved
        rt = pickle.loads(pickle.dumps(d))
        assert rt == d
        json.loads(json.dumps(d))  # JSON-clean too

    def test_counters_alias_feeds_registry(self):
        from repro.core import counters

        assert counters.COUNTERS is METRICS.counters
        snap = METRICS.snapshot()
        counters.bump("obs_test_counter", 7)
        assert METRICS.delta(snap)["counters"]["obs_test_counter"] == 7


# ---------------------------------------------------------------------- #
# tracer
# ---------------------------------------------------------------------- #
class TestTracer:
    def test_nesting_depth_and_attrs(self):
        tr = Tracer()
        with activate(tr):
            assert tracing_active()
            with trace_span("outer", a=1):
                with trace_span("inner") as sp:
                    sp.attrs["b"] = 2
        assert not tracing_active()
        by_name = {s.name: s for s in tr.spans}
        assert by_name["outer"].depth == 0
        assert by_name["inner"].depth == 1
        assert by_name["outer"].attrs == {"a": 1}
        assert by_name["inner"].attrs == {"b": 2}
        # inner closed first
        assert tr.spans[0].name == "inner"

    def test_disabled_fast_path_discards_attrs(self):
        with trace_span("nope", x=1) as sp:
            sp.attrs["y"] = 2
            sp.attrs.update(z=3)
        assert dict(sp.attrs) == {}  # shared null span never grows

    def test_activate_none_is_passthrough(self):
        tr = Tracer()
        with activate(tr):
            with activate(None):
                with trace_span("still-traced"):
                    pass
        assert [s.name for s in tr.spans] == ["still-traced"]

    def test_by_duration(self):
        tr = Tracer()
        tr.extend([Span("a", 0.0, 0.1, "t"), Span("b", 0.0, 0.5, "t"),
                   Span("c", 0.0, 0.3, "t")])
        assert [s.name for s in tr.by_duration(2)] == ["b", "c"]


# ---------------------------------------------------------------------- #
# exporters
# ---------------------------------------------------------------------- #
def _check_chrome_schema(path):
    """Valid JSON, globally monotone ts, matched B/E pairs per tid."""
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events, "empty trace"
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts), "ts not monotone"
    stacks: dict = {}
    for e in events:
        key = (e["pid"], e["tid"])
        if e["ph"] == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif e["ph"] == "E":
            assert stacks.get(key), f"E without B on {key}"
            assert stacks[key].pop() == e["name"]
    leftovers = {k: v for k, v in stacks.items() if v}
    assert not leftovers, f"unclosed B events: {leftovers}"
    return events


class TestExport:
    def test_span_events_and_chrome_trace(self, tmp_path):
        spans = [
            Span("run", ts=0.0, dur=1.0, tid="main", depth=0),
            Span("stage", ts=0.2, dur=0.3, tid="main", depth=1,
                 attrs={"k": 4}),
            Span("stage", ts=0.6, dur=0.0, tid="main", depth=1),
        ]
        path = tmp_path / "trace.json"
        write_chrome_trace(path, span_events(spans))
        events = _check_chrome_schema(path)
        assert len(events) == 6  # one B + one E per span
        args = [e.get("args") for e in events if e["ph"] == "B"]
        assert {"k": 4} in args

    def test_jsonl_sink(self, tmp_path):
        path = tmp_path / "events.jsonl"
        with JsonlSink(path) as sink:
            assert sink.enabled
            sink.emit({"a": 1})
            sink.emit({"b": [1, 2]})
        lines = path.read_text().splitlines()
        assert [json.loads(ln) for ln in lines] == [{"a": 1},
                                                    {"b": [1, 2]}]
        disabled = JsonlSink(None)
        disabled.emit({"x": 1})  # no-op, no error
        assert not disabled.enabled


# ---------------------------------------------------------------------- #
# inertness: bit-identical results with tracing on/off
# ---------------------------------------------------------------------- #
def _plan_fingerprint(rep: ScheduleReport):
    s = rep.summary
    return (s.makespan, s.k_used, s.k_prime, tuple(s.block_of_task),
            tuple(sorted(s.proc_of_block.items())))


class TestInertness:
    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_scheduler_bit_identical_all_families(self, family,
                                                  tmp_path):
        plat = default_cluster()
        wf = generate_workflow(family, 1000, seed=11, platform=plat)
        off = schedule(wf, plat, kprime=[4, 9])
        on = schedule(wf, plat, kprime=[4, 9],
                      obs=ObsConfig(enabled=True,
                                    trace_path=tmp_path / "t.json"))
        assert off.feasible and on.feasible
        assert _plan_fingerprint(off) == _plan_fingerprint(on)
        assert on.spans and not off.spans
        _check_chrome_schema(tmp_path / "t.json")

    def test_probe_spans_inert_too(self):
        plat = default_cluster()
        wf = generate_workflow("montage", 300, seed=3, platform=plat)
        off = schedule(wf, plat, kprime=[6])
        on = schedule(wf, plat, kprime=[6],
                      obs=ObsConfig(enabled=True, probe_spans=True))
        assert _plan_fingerprint(off) == _plan_fingerprint(on)
        assert any(s.name.startswith("probe.") for s in on.spans)

    def test_service_trace_bit_identical(self, tmp_path):
        plat = default_cluster()
        subs = [
            Submission(generate_workflow("blast", 120, seed=5,
                                         platform=plat),
                       tenant="a", arrival_t=0.0, name="j0"),
            Submission(generate_workflow("blast", 120, seed=5,
                                         platform=plat),
                       tenant="b", arrival_t=1.0, name="j1"),
            Submission(generate_workflow("genome", 150, seed=6,
                                         platform=plat),
                       tenant="a", arrival_t=2.0, name="j2"),
        ]
        off = run_service(subs, plat)
        trace_path = tmp_path / "svc.json"
        sink_path = tmp_path / "svc.jsonl"
        on = run_service(subs, plat,
                         obs=ObsConfig(enabled=True,
                                       trace_path=trace_path,
                                       sink=sink_path))
        # the virtual-time trace is the determinism contract
        assert on.trace.to_dict() == off.trace.to_dict()
        assert on.spans and not off.spans
        names = {s.name for s in on.spans}
        assert {"service.admit", "service.dispatch", "service.plan",
                "service.complete"} <= names
        events = _check_chrome_schema(trace_path)
        # both clock domains present in one file
        assert {"wall", "virtual"} <= {e["pid"] for e in events}
        # the sink streamed the service log and the spans
        records = [json.loads(ln)
                   for ln in sink_path.read_text().splitlines()]
        kinds = {r["event"] for r in records}
        assert kinds == {"service", "span"}
        assert sum(r["event"] == "service" for r in records) == len(
            on.trace.log)

    def test_service_percentiles_from_histograms(self):
        plat = default_cluster()
        subs = [Submission(generate_workflow("blast", 120, seed=5,
                                             platform=plat),
                           arrival_t=float(i), name=f"j{i}")
                for i in range(3)]
        rep = run_service(subs, plat)
        p = rep.plan_latency_percentiles
        assert p is not None and p["p50"] <= p["p95"] <= p["p99"]
        assert rep.queue_wait_percentiles is not None
        # identical DAGs: second+ submissions hit the plan cache, so
        # the premium histogram has samples near 1.0
        prem = rep.makespan_premium_percentiles
        assert prem is not None and prem["p50"] >= 0.5


# ---------------------------------------------------------------------- #
# worker shipping: pickled histogram deltas under the process pool
# ---------------------------------------------------------------------- #
class TestWorkerShipping:
    def test_histogram_deltas_cross_the_pool(self):
        plat = default_cluster()
        wf = generate_workflow("blast", 300, seed=7, platform=plat)
        snap = METRICS.snapshot()
        rep = schedule(wf, plat, kprime=[1, 4, 9], workers=2)
        # every sweep point shipped its non-counter metrics delta back
        for p in rep.sweep:
            hist = p.metrics["histograms"]["sched_sweep_point_s"]
            assert hist["count"] == 1
        # and the parent registry merged them (plus any pre-sweep
        # parent-side observations)
        d = METRICS.delta(snap)
        assert (d["histograms"]["sched_sweep_point_s"]["count"]
                >= len(rep.sweep))
        # aggregated run metrics on the report
        agg = rep.metrics["histograms"]["sched_sweep_point_s"]
        assert agg["count"] == len(rep.sweep)

    def test_parallel_spans_carry_worker_tracks(self):
        plat = default_cluster()
        wf = generate_workflow("blast", 300, seed=7, platform=plat)
        rep = schedule(wf, plat, kprime=[1, 4, 9], workers=2,
                       obs=ObsConfig(enabled=True))
        tids = {s.tid for s in rep.spans}
        assert len(tids) >= 2  # parent + at least one worker pid


# ---------------------------------------------------------------------- #
# serialization compatibility
# ---------------------------------------------------------------------- #
class TestSerialization:
    def test_schedule_report_metrics_round_trip(self):
        plat = default_cluster()
        wf = generate_workflow("blast", 120, seed=4, platform=plat)
        rep = schedule(wf, plat, kprime=[1, 4])
        rt = ScheduleReport.from_json(rep.to_json())
        assert rt.metrics == rep.metrics
        assert rt.metrics["histograms"]["sched_sweep_point_s"][
            "count"] == 2

    def test_pre_pr8_payloads_still_load(self):
        plat = default_cluster()
        wf = generate_workflow("blast", 120, seed=4, platform=plat)
        rep = schedule(wf, plat, kprime=[1])
        d = rep.to_dict()
        del d["metrics"]                       # pre-PR-8 shape
        for p in d["sweep"]:
            del p["metrics"]
        old = ScheduleReport.from_dict(d)
        assert old.metrics == {} and old.sweep[0].metrics == {}

        svc = run_service(
            [Submission(wf, name="j0")], plat)
        sd = svc.to_dict()
        del sd["metrics"]                      # pre-PR-8 shape
        assert ServiceReport.from_dict(sd).metrics == {}
        assert ServiceReport.from_dict(sd).plan_latency_percentiles \
            is None


# ---------------------------------------------------------------------- #
# spans mirrored into the JAX profiler's trace; the compile counter
# ---------------------------------------------------------------------- #
def _profiled(body, tmp_path):
    """Run ``body`` under ``jax.profiler.trace``; the host events of the
    thread that ran it, as {name: [(start_ns, end_ns, stats)]}."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        body()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs: dict = {}
            for e in line.events:
                evs.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
            if "outer" in evs:
                return evs
    raise AssertionError("no host line holds the spans")


class TestProfilerMirror:
    def test_spans_nest_on_the_host_plane_with_their_stats(self, tmp_path):
        tr = Tracer(profiler=True)

        def body():
            with activate(tr):
                for step in range(2):
                    with trace_span("outer", step_num=step) as sp:
                        with trace_span("inner", k=3):
                            pass
                        sp.attrs.update(n=5, rids=[4, 7])

        evs = _profiled(body, tmp_path)
        assert len(evs["outer"]) == len(evs["inner"]) == 2
        for (o0, o1, ostats), (i0, i1, istats) in zip(evs["outer"],
                                                      evs["inner"]):
            assert o0 <= i0 <= i1 <= o1
            assert istats["k"] == 3
            assert ostats["n"] == 5 and ostats["rids"] == "4 7"
        assert [s[2]["step_num"] for s in evs["outer"]] == [0, 1]
        # the in-memory spans are kept as well
        assert [s.name for s in tr.spans] == ["inner", "outer"] * 2
        assert tr.spans[1].attrs["rids"] == [4, 7]

    def test_obs_config_makes_a_mirroring_tracer(self):
        tr = ObsConfig(enabled=True, profiler=True).make_tracer()
        assert tr._annotations is not None
        assert ObsConfig(enabled=True).make_tracer()._annotations is None

    def test_without_a_tracer_the_null_span_records_nothing(self):
        from repro.obs.tracer import _NULL_SPAN
        idle = Tracer(profiler=True)        # made, never activated
        assert not tracing_active()
        assert trace_span("x", a=1) is _NULL_SPAN
        with trace_span("x") as sp:
            sp.attrs["b"] = 2
        assert idle.spans == []


class TestCompileCounter:
    def test_one_shape_compiles_once_a_second_shape_again(self):
        import jax
        import numpy as np
        from repro.obs import cache_loads, compiles, count_compiles

        count_compiles()
        count_compiles()                      # idempotent: one listener

        def counted_twice(x):
            return x * 2 + 1

        f = jax.jit(counted_twice)
        name = "jit(counted_twice)"
        before = compiles() + cache_loads()
        f(np.ones(3, np.float32))
        f(np.zeros(3, np.float32))
        assert compiles(name) + cache_loads(name) == 1
        f(np.ones(4, np.float32))
        assert compiles(name) + cache_loads(name) == 2
        assert compiles() + cache_loads() - before == 2
        assert METRICS.histograms["jax.compile_s"].count >= 2

    def test_programs_counts_compiles_and_cache_loads_together(self):
        import jax
        import numpy as np
        from repro.obs import cache_loads, compiles, count_compiles, programs

        count_compiles()
        before = programs()
        assert before == compiles() + cache_loads()

        def counted_in_all(x):
            return x - 7

        jax.jit(counted_in_all)(np.ones(6, np.float32))
        assert programs() - before == 1 == compiles("jit(counted_in_all)")
        assert programs() == compiles() + cache_loads()

    def test_a_cache_load_is_not_a_compile(self, tmp_path):
        import jax
        import numpy as np
        from jax.experimental.compilation_cache import compilation_cache
        from repro.obs import cache_loads, compiles, count_compiles

        count_compiles()
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
        prev = {k: getattr(jax.config, k) for k in keys}
        try:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
            compilation_cache.reset_cache()

            def loaded_once(x):
                return x * 3 - 1

            name = "jit(loaded_once)"
            x = np.ones(5, np.float32)
            jax.jit(loaded_once)(x)
            assert (compiles(name), cache_loads(name)) == (1, 0)
            jax.clear_caches()
            jax.jit(loaded_once)(x)
            assert (compiles(name), cache_loads(name)) == (1, 1)
        finally:
            for k, v in prev.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()
