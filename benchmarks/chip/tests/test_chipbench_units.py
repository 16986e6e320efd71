"""The chip benchmark's arithmetic, on the CPU: work counts and peaks,
the traffic generator, the end-to-end metrics from request records,
the trace reduction, and the command's refusal without a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench import records, traffic, work, xplane  # noqa: E402
from chipbench.archs import dense_gqa  # noqa: E402
from chipbench.drive import drive  # noqa: E402
from chipbench.peaks import peak_for  # noqa: E402
from chipbench.records import RequestRecord, StepRecord  # noqa: E402


BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def architecture(config: dict) -> str:
    """The architecture that a configuration entry of ``BENCHMARK.json``
    names in its file."""
    return json.loads((ROOT / config["file"]).read_text())["architecture"]


def shapes(name: str) -> dense_gqa.Shapes:
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return dense_gqa.Shapes.of(conf["model"], conf["dtype"])


def step(ctx_lens, n_logits):
    return StepRecord(0.0, 0.0, tuple(ctx_lens), n_logits)


# -- work counts and peaks ------------------------------------------------ #
@pytest.mark.parametrize("name,layer,total_bytes,kv", [
    ("minitron_4b", 110_106_624, 10_192_558_080, 131_072),
    ("granite_8b", 218_112_000, 16_106_725_376, 147_456),
])
def test_counts_match_hand_counts(name, layer, total_bytes, kv):
    s = shapes(name)
    assert s.layer_params() == layer
    assert s.param_bytes() == total_bytes
    assert s.kv_bytes_per_token() == kv
    conf = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert conf["param_bytes"] == total_bytes
    assert conf["kv_bytes_per_token"] == kv


def test_step_work_by_hand():
    s = shapes("minitron_4b")
    d, v, nl = 3072, 256_000, 32
    # one token at position 0, no logits: matmuls and one KV entry
    assert s.step_flops(step([1], 0)) == 2 * nl * (110_106_624 - 2 * d) \
        + 4 * nl * 24 * 128
    assert s.step_flops(step([], 1)) == 2 * d * v
    weights = (nl * 110_106_624 + d) * 2
    assert s.step_bytes(step([1], 0)) == weights + d * 2 + 2 * 131_072
    assert s.step_bytes(step([1], 1)) == s.step_bytes(step([1], 0)) + v * d * 2
    assert s.step_bytes(step([], 0)) == 0


def dense_counts_of_totals(s, n_tokens: int, sum_ctx: int, n_logits: int):
    """The dense counts written out from a step's three totals."""
    hd, d, nl = s.head_dim, s.d_model, s.n_layers
    matmul = d * (s.n_heads + s.n_kv_heads) * hd * 2 + 3 * d * s.d_ff
    flops = (2 * n_tokens * nl * matmul + 4 * nl * s.n_heads * hd * sum_ctx
             + 2 * n_logits * d * s.vocab_size)
    if n_tokens == 0:
        return flops, 0
    b = s.dtype_bytes
    kv = nl * 2 * s.n_kv_heads * hd * b
    nbytes = ((nl * (matmul + 2 * d) + d) * b + n_tokens * d * b
              + (s.vocab_size * d * b if n_logits else 0)
              + (sum_ctx + n_tokens) * kv)
    return flops, nbytes


@pytest.mark.parametrize("name", [c["name"] for c in BENCHMARK["configs"]
                                  if architecture(c) == "dense_gqa"])
def test_dense_counts_of_a_step_record_are_those_of_its_totals(name):
    s = shapes(name)
    rng = np.random.default_rng(2**31 + 3)
    for _ in range(300):
        lens = rng.integers(1, 769, size=rng.integers(0, 33))
        n_logits = int(rng.integers(0, len(lens) + 1))
        rec = step(lens.tolist(), n_logits)
        assert rec.n_tokens == len(lens) and rec.sum_ctx == lens.sum()
        assert (s.step_flops(rec), s.step_bytes(rec)) == \
            dense_counts_of_totals(s, len(lens), int(lens.sum()), n_logits)


def test_a_windowed_architecture_counts_each_slots_context():
    from chipbench import archs
    conf = json.loads((HERE / "data" / "tiny_window.json").read_text())
    win = archs.load(conf["architecture"], HERE / "data").Shapes.of(
        conf["model"], conf["dtype"])
    dense = dense_gqa.Shapes.of(conf["model"], conf["dtype"])
    assert win.window == 6
    # a slot reads at most the window, whatever the others read
    assert win.step_flops(step([3, 10, 40], 2)) == \
        dense.step_flops(step([3, 6, 6], 2)) < dense.step_flops(
            step([3, 10, 40], 2))
    assert win.step_bytes(step([3, 10, 40], 2)) == \
        dense.step_bytes(step([3, 6, 6], 2))


def test_least_seconds_names_its_bound():
    peak = peak_for("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.least_seconds(197e12, 1.0, 1, peak)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(1.0, 4 * 819e9, 4, peak)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peak_for("TPU v9 imaginary")


# -- traffic --------------------------------------------------------------- #
MIX = traffic.load(BENCH / "traffic" / "chat.json")


def test_schedule_deterministic_per_seed():
    a = traffic.schedule(MIX, 45, 2**31 + 12345, 256_000)
    b = traffic.schedule(MIX, 45, 2**31 + 12345, 256_000)
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.schedule(MIX, 45, 2**31 + 12346, 256_000)
    assert [p.due_s for p in a] != [p.due_s for p in c]


def test_schedule_stays_inside_its_clips_and_window():
    plan = traffic.schedule(MIX, 45, 99, 256_000)
    assert len(plan) == round(MIX["rate_per_s"] * 45)
    lens = [len(p.prompt) for p in plan]
    outs = [p.max_new_tokens for p in plan]
    assert MIX["prompt"]["min"] <= min(lens) and max(lens) <= MIX["prompt"]["max"]
    assert MIX["output"]["min"] <= min(outs) and max(outs) <= MIX["output"]["max"]
    dues = [p.due_s for p in plan]
    assert dues[0] == 0 and dues == sorted(dues) and dues[-1] < 45
    assert all(0 <= int(t) < 256_000 for p in plan for t in p.prompt)
    assert all(p.prompt.dtype == np.int32 for p in plan)
    server = MIX["server"]
    assert max(lens) + max(outs) <= server["max_len"]


def test_every_seed_asks_for_the_same_work():
    a = traffic.schedule(MIX, 45, 1, 256_000)
    b = traffic.schedule(MIX, 45, 2, 256_000)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in b)
    gaps = lambda plan: sorted(np.diff([p.due_s for p in plan]).round(9))
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2   # one gap is left out


def test_quantile_lengths_median():
    # a lognormal of mean m has its median at m exp(-sigma^2 / 2)
    x = traffic.quantile_lengths({"dist": "lognormal", "mean": 200,
                                  "sigma": 0.8, "min": 1, "max": 10**6}, 1001)
    assert np.median(x) == round(200 * np.exp(-0.32))
    assert np.mean(x) == pytest.approx(200, rel=0.02)


# -- end-to-end arithmetic from request records ---------------------------- #
def rec(due, admit, first, gap, want, plen=10):
    """A request served in full: its first token at ``first``, then one
    every ``gap`` seconds."""
    r = RequestRecord(due, plen, want, sent=due, admit=admit)
    r.stamps = [first + gap * i for i in range(want)]
    return r


def test_ttft_itl_and_queue_wait_from_records():
    recs = [rec(0.0, 0.5, 2.0, 0.03, 10), rec(1.0, 1.0, 2.5, 0.04, 20)]
    assert records.ttft_s(recs, 10.0) == [2.0, 1.5]
    gaps = records.itl_ms(recs, 10.0)
    assert len(gaps) == 9 + 19
    assert gaps == pytest.approx([30.0] * 9 + [40.0] * 19)
    assert records.queue_wait_share(recs, 10.0) == pytest.approx(
        100 * 0.5 / 3.5)
    assert records.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)


def test_unanswered_request_counts_as_waiting_until_the_end():
    r = RequestRecord(1.0, 10, 5, sent=1.0)
    assert records.ttft_s([r], 30.0) == [29.0]
    assert records.itl_ms([r], 30.0) == [pytest.approx(29e3)]
    assert records.queue_wait_share([r], 30.0) == pytest.approx(100.0)
    r.stamps = [2.0, 2.5]           # two of its five tokens, then nothing
    assert records.itl_ms([r], 30.0) == pytest.approx([500.0, 27.5e3])


def test_a_stall_shows_in_itl_p99():
    # 20 requests in flight together, 100 tokens each, 30 ms apart; a
    # stalled step holds up one gap of every request in flight
    steady = [rec(0, 0, 1, 0.03, 100) for _ in range(20)]
    base = records.percentile(records.itl_ms(steady, 200), 99)
    stalled = [rec(0, 0, 1, 0.03, 100) for _ in range(20)]
    for r in stalled:
        for k in (30, 60):           # two stalls of 2 s: 40 of 1980 gaps
            r.stamps[k:] = [t + 2.0 for t in r.stamps[k:]]
    hit = records.percentile(records.itl_ms(stalled, 200), 99)
    assert base == pytest.approx(30.0)
    assert hit == pytest.approx(2030.0)


def test_generator_lateness():
    recs = [RequestRecord(1.0, 5, 5, sent=1.002),
            RequestRecord(2.0, 5, 5, sent=2.010)]
    mean, worst = records.lateness(recs)
    assert mean == pytest.approx(0.006) and worst == pytest.approx(0.010)


class OneTokenLoop:
    """A server whose every step takes 5 ms and gives each admitted
    request one token."""

    def __init__(self, slots: int = 2) -> None:
        self.queue, self.active = [], [None] * slots

    def submit(self, req) -> None:
        self.queue.append(req)

    def run(self, max_steps: int = 1) -> list:
        for s, r in enumerate(self.active):
            if r is None and self.queue:
                self.active[s] = self.queue.pop(0)
        time.sleep(0.005)
        done = []
        for s, r in enumerate(self.active):
            if r is not None:
                r.out.append(0)
                if len(r.out) == r.max_new_tokens:
                    done.append(r)
                    self.active[s] = None
        return done


def test_the_clock_stands_still_while_the_profiler_collects():
    class Profiler:
        def start(self):
            pass

        def stop(self):
            time.sleep(0.3)

    plan = [traffic.Planned(0.0, np.zeros(1, np.int32), 20),
            traffic.Planned(0.01, np.zeros(1, np.int32), 20)]
    reqs = [SimpleNamespace(out=[], max_new_tokens=20) for _ in plan]
    run = drive(OneTokenLoop(), plan, reqs, 0.05, trace=(0.0, 0.02),
                profiler=Profiler())
    assert run.paused_s >= 0.3
    assert all(r.complete for r in run.records)
    assert max(records.itl_ms(run.records, run.end)) < 100
    assert run.end < 0.3


# -- trace reduction --------------------------------------------------------- #
def synthetic_trace(devices=1):
    ops = {f"/device:TPU:{i}": [("fusion.1", 100, 50), ("fusion.2", 140, 30),
                                ("dot.3", 300, 100)] for i in range(devices)}
    mods = {f"/device:TPU:{i}": [("jit_decode_step(1)", 100, 70),
                                 ("jit_decode_step(1)", 300, 100)]
            for i in range(devices)}
    host = [("bench.step", 90, 200), ("np.asarray", 200, 50),
            ("bench.track", 290, 5), ("bench.step", 295, 200)]
    return xplane.Trace(ops=ops, modules=mods, host=host)


@pytest.mark.parametrize("devices", [1, 4])
def test_trace_reduction_of_known_intervals(devices):
    r = xplane.reduce(synthetic_trace(devices))
    ns = 1e-9
    assert r["window_s"] == pytest.approx(405 * ns)     # 90 .. 495
    assert r["busy_s"] == pytest.approx(170 * ns)       # [100,170] + [300,400]
    assert r["idle_share"] == pytest.approx(1 - 170 / 405)
    assert r["program_s"] == pytest.approx(170 * ns)
    assert r["steps"] == 2
    assert r["host_per_step_s"] == pytest.approx((400 - 170) / 2 * ns)
    assert r["device_ops"][0] == ("dot.3", pytest.approx(100 * ns))
    gaps = dict(r["idle_gaps"])
    assert gaps["np.asarray"] == pytest.approx(130 * ns)   # 170 .. 300
    assert gaps["bench.step"] == pytest.approx(105 * ns)   # 90..100, 400..495


def test_nested_device_ops_count_their_self_time():
    tr = synthetic_trace()
    tr.ops["/device:TPU:0"] = [
        ("%while.6 = (s32[], bf16[2]) while(...)", 100, 300),
        ("%fusion.1 = bf16[28,9216]{1,0} fusion(...)", 120, 100),
        ("%fusion.2 = bf16[28]{0} fusion(...)", 250, 100)]
    tr.ops["/device:CUSTOM:Megascale Trace"] = []
    r = xplane.reduce(tr)
    ops = dict(r["device_ops"])
    ns = 1e-9
    assert ops["%fusion.1 = bf16[28,9216]"] == pytest.approx(100 * ns)
    assert ops["%fusion.2 = bf16[28]"] == pytest.approx(100 * ns)
    assert ops["%while.6 ="] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx(300 * ns)


def test_trace_reduction_without_device_events_is_empty():
    tr = synthetic_trace()
    tr.ops = {}
    assert xplane.reduce(tr) == {}


# -- the command without a TPU ----------------------------------------------- #
def test_command_without_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "minitron_4b.chat", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_architecture_exits_before_looking_for_a_chip(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        conf["architecture"] = "no_such_architecture"
        (tmp_path / c["file"]).write_text(json.dumps(conf))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "minitron_4b.chat", "--seed", str(2**31 + 5), "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    known = sorted(
        f.stem for f in (tmp_path / "benchmarks" / "chip" / "chipbench"
                         / "archs").glob("*.py") if not f.name.startswith("_"))
    assert "dense_gqa" in known
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "unknown architecture 'no_such_architecture'; known: " \
        f"{known}" in p.stderr
    assert "no TPU" not in p.stderr


def test_a_reader_that_does_not_load_stops_the_cell_before_the_chip(
        tmp_path):
    """``run.load_cell``, which runs before the look for a chip, loads
    every reader of the cell."""
    import run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]["name"]
    assert run.load_cell(cell).per_layer
    bench["per_layer"].append(dict(bench["per_layer"][0],
                                   name="no_such_reader.chat",
                                   workloads=[cell]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for c in bench["configs"]:
        (tmp_path / c["file"]).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(ROOT / c["file"], tmp_path / c["file"])
    with pytest.raises(FileNotFoundError, match="no_such_reader.chat"):
        run.load_cell(cell, tmp_path)


def test_benchmark_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        conf = json.loads((ROOT / c["file"]).read_text())
        assert (BENCH / "chipbench" / "archs"
                / f"{conf['architecture']}.py").exists()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
