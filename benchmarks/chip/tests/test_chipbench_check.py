"""The check that decides ``correct``, on the CPU at a tiny size: the
reference draws the program's weights and computes its logits; a whole
run of the harness is correct when the timed path is sound, and not
correct under the float8 control or a planted fault."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

from chipbench.archs import dense_gqa as ref  # noqa: E402

TINY = json.loads((HERE / "data" / "tiny.json").read_text())["model"]
LIMIT = 0.1     # the tiny cell's limit on ``gap`` (data/run_tiny.py)


def program_model(param_dtype):
    from repro.configs import ModelConfig
    from repro.models import LM
    return LM(ModelConfig(**TINY), param_dtype=param_dtype, max_seq=64)


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_weight_recipe_draws_the_programs_weights(seed):
    import run
    from repro.configs import ModelConfig
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(1, 1)
    got = ref.make_weights(TINY, "bfloat16", seed, mesh)
    want = run.build_server(ModelConfig(**TINY), mesh, slots=2, max_len=16,
                            seed=seed, param_dtype=jnp.bfloat16).params
    blk = want["blocks"][0]
    pairs = {"embed": want["embed"], "lm_head": want["lm_head"],
             "final_norm": want["final_norm"],
             "attn_norm": blk["mixer"]["norm"], "wq": blk["mixer"]["wq"],
             "wk": blk["mixer"]["wk"], "wv": blk["mixer"]["wv"],
             "wo": blk["mixer"]["wo"], "ffn_norm": blk["ffn_norm"],
             "w_gate": blk["ffn"]["w_gate"], "w_up": blk["ffn"]["w_up"],
             "w_down": blk["ffn"]["w_down"]}
    assert set(pairs) == set(got)
    for name, w in pairs.items():
        assert got[name].dtype == w.dtype, name
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(w, np.float32), err_msg=name)
    # the harness's init draws what the program's own init draws
    own = jax.jit(lambda: program_model(jnp.bfloat16).init(seed))()
    np.testing.assert_array_equal(np.asarray(own["embed"], np.float32),
                                  np.asarray(want["embed"], np.float32))


def test_reference_computes_the_programs_logits_in_float32():
    model = program_model(jnp.float32)
    params = model.init(3)
    p = ref.weight_recipe(TINY, jnp.float32, 3)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 24)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.forward(params, tokens)[0]
        h = ref.hidden(TINY, p, tokens)
        got = jnp.stack([ref.logits(p, h[i]) for i in range(2)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_control_departs_from_the_reference():
    p = ref.weight_recipe(TINY, jnp.bfloat16, 4)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 512, (1, 32)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        exact = ref.logits(p, ref.hidden(TINY, p, tokens)[0])
        low = ref.logits(p, ref.hidden(TINY, p, tokens, control=True)[0],
                         control=True)
    err = float(jnp.max(jnp.abs(exact - low)))
    assert 0.05 < err < 5.0


def run_tiny(cache: Path, fault: str, chips: int = 1,
             config: str = "tiny.json") -> tuple[dict, dict, str]:
    """``data/run_tiny.py``'s run: (result line, the check's readings,
    standard error)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}").strip()
    p = subprocess.run(
        [sys.executable, str(HERE / "data" / "run_tiny.py"), str(cache),
         fault, str(chips), config],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    *_, readings, result = p.stdout.strip().splitlines()
    return json.loads(result), json.loads(readings)["readings"], p.stderr


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """``tiny(fault, chips=1)``: the tiny cell's run with ``fault``
    planted, made once for every test that asks for it."""
    cache = tmp_path_factory.mktemp("tiny") / "cache"
    runs: dict = {}

    def get(fault: str, chips: int = 1) -> tuple[dict, dict, str]:
        if (fault, chips) not in runs:
            runs[fault, chips] = run_tiny(cache, fault, chips)
        return runs[fault, chips]
    return get


def test_sound_run_is_correct_and_the_control_is_not(tiny):
    r, _, _ = tiny("none")
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ttft_p90_s", "itl_p99_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["gap"]["limit"] == LIMIT
    assert r["checks"]["gap"]["value"] < LIMIT
    # the float8 control, judged in the program's place by the cell's
    # own limits, is not correct
    c, _, _ = tiny("control")
    assert c["correct"] is False
    assert c["checks"]["gap"]["value"] == c["readings"]["control_gap"]
    assert c["readings"]["gap"] < LIMIT < c["readings"]["control_gap"]


@pytest.mark.parametrize("fault", ["token", "state", "batch"])
def test_planted_fault_is_not_correct(tiny, fault):
    r, _, _ = tiny(fault)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] > LIMIT


def test_sharded_run_is_correct_and_without_the_exchange_is_not(tiny):
    sound, _, _ = tiny("none", 4)
    assert sound["correct"] is True and sound["device"]["count"] == 4
    broken, _, _ = tiny("exchange", 4)
    assert broken["correct"] is False
    assert broken["checks"]["gap"]["value"] > LIMIT


# (correct, gap, logit_err) of each tiny run on the CPU, as the harness
# read them with its dense reference in one module of its own: reaching
# the reference through the architecture's module changes no arithmetic
DENSE_READINGS = {
    ("none", 1): (True, 0.004175901412963867, 0.03570368140935898),
    ("control", 1): (False, 0.004175901412963867, 0.03570368140935898),
    ("token", 1): (False, 4.964898109436035, 10000.016677677631),
    ("state", 1): (False, 5.185284614562988, 5.122796297073364),
    ("batch", 1): (False, 5.270834922790527, 5.934383153915405),
    ("none", 4): (True, 0.004175424575805664, 0.04033172130584717),
    ("exchange", 4): (False, 4.752098083496094, 4.474851608276367),
}


@pytest.mark.parametrize("fault,chips", list(DENSE_READINGS))
def test_verdict_and_readings_are_the_dense_modules(tiny, fault, chips):
    correct, gap, logit_err = DENSE_READINGS[fault, chips]
    r, readings, _ = tiny(fault, chips)
    assert r["correct"] is correct
    assert readings["gap"] == pytest.approx(gap, rel=1e-5)
    assert readings["logit_err"] == pytest.approx(logit_err, rel=1e-5)


def test_an_architecture_enters_as_new_files(tmp_path):
    """``data/dense_window.py`` and ``data/tiny_window.json`` alone add an
    architecture: the harness checks the windowed program against that
    module's reference and counts with its ``Shapes``.  Named
    ``dense_gqa``, the same configuration is not correct."""
    r, readings, err = run_tiny(tmp_path / "cache", "none", 1,
                                "tiny_window.json")
    assert r["correct"] is True and readings["gap"] < LIMIT
    assert "dense_window: hidden" in err.splitlines()
    assert "dense_window: Shapes.of" in err.splitlines()
    conf = json.loads((HERE / "data" / "tiny_window.json").read_text())
    conf["architecture"] = "dense_gqa"
    (tmp_path / "as_dense.json").write_text(json.dumps(conf))
    d, readings, err = run_tiny(tmp_path / "cache", "none", 1,
                                str(tmp_path / "as_dense.json"))
    assert d["correct"] is False and readings["gap"] > LIMIT
    assert "dense_window" not in err
