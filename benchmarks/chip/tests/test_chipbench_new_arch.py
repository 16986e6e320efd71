"""A second architecture enters the harness as new files only.

A copy of the harness, its tests and ``BENCHMARK.json`` is given what a
PR that adds a model adds, and changes nothing else: an architecture
module in ``chipbench/archs/`` (``data/dense_window.py``), a
configuration naming it (``data/tiny_window.json``), a traffic file, a
limits file, a reader, and ``BENCHMARK.json`` entries for one
configuration, one one-chip cell and one per-layer metric of that cell.
The copy's own tests then pass, every cell of it resolves, and its new
cell reaches the look for a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]

CONFIG, TRAFFIC, METRIC = "tiny_window", "tiny", "reset_ms.tiny"
CELL = f"{CONFIG}.{TRAFFIC}"
READER = '''"""A state reset's host time, ms a step (``span_ms``)."""


def read(run):
    return run.spans.get("span_ms", {}).get("serve.reset")
'''

# every cell of the copy's BENCHMARK.json through ``run.load_cell``:
# {cell: [architecture module, readers]}
LOAD_ALL = """
import json, sys
sys.path.insert(0, "benchmarks/chip")
import run
cells = [run.load_cell(w["name"])
         for w in json.load(open("BENCHMARK.json"))["workloads"]]
print(json.dumps({c.name: [c.arch.__name__, [m["name"] for m in c.per_layer]]
                  for c in cells}))
"""


def grown(root: Path) -> Path:
    """The harness copied to ``root`` with a second architecture added
    as new files; returns the copy's ``benchmarks/chip``."""
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE / "data" / "dense_window.py",
                bench / "chipbench" / "archs" / "dense_window.py")
    shutil.copy(HERE / "data" / "tiny_window.json",
                bench / "configs" / f"{CONFIG}.json")
    shutil.copy(HERE / "data" / "tiny_traffic.json",
                bench / "traffic" / f"{TRAFFIC}.json")
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(
        {"gap": {"limit": 0.1}}))
    (bench / "metrics" / f"{METRIC}.py").write_text(READER)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": CONFIG, "source": "https://arxiv.org/abs/2004.05150",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "a dense decoder whose attention keeps a sliding window"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "short prompts and outputs, 4 slots: the windowed decode"})
    spec["per_layer"].append({
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "batching (runtime/serve_loop)",
        "moves": "itl_p99_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return bench


def run_in(cwd: Path, *args: str) -> subprocess.CompletedProcess:
    """``python args`` in ``cwd`` on the CPU, with the program's source
    importable and nothing of this test run's own pytest settings."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "COV_"))}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), env.get("PYTHONPATH", "")]))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=90)


def test_a_second_architecture_enters_as_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    bench = grown(root)

    for tests in ("tests/test_chipbench_units.py",
                  "tests/test_chipbench_spans.py"):
        p = run_in(bench, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                   "-p", "no:randomly", "-p", "no:xdist", tests)
        assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]

    p = run_in(root, "-c", LOAD_ALL)
    assert p.returncode == 0, p.stderr[-3000:]
    cells = json.loads(p.stdout.strip().splitlines()[-1])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(cells) == {w["name"] for w in spec["workloads"]} | {CELL}
    assert cells[CELL] == ["chipbench.archs.dense_window", [METRIC]]
    assert all(METRIC not in readers for name, (_, readers) in cells.items()
               if name != CELL)

    p = run_in(root, "benchmarks/chip/run.py", "--workload", CELL,
               "--seed", str(2**33 + 7), "--seconds", "1", "--trace", "1")
    assert p.returncode == 2, p.stderr[-3000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
