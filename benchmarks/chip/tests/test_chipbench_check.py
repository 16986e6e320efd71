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

from chipbench import reference as ref  # noqa: E402

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


def run_tiny(tmp_path, fault: str, chips: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={chips}").strip()
    p = subprocess.run(
        [sys.executable, str(HERE / "data" / "run_tiny.py"),
         str(tmp_path / "cache"), fault, str(chips)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    r = run_tiny(tmp_path, "none")
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ttft_p90_s", "itl_p99_ms", "setup_s"}
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert list(r)[-1] == "checks"
    assert r["checks"]["gap"]["limit"] == LIMIT
    assert r["checks"]["gap"]["value"] < LIMIT
    # the float8 control, judged in the program's place by the cell's
    # own limits, is not correct
    c = run_tiny(tmp_path, "control")
    assert c["correct"] is False
    assert c["checks"]["gap"]["value"] == c["readings"]["control_gap"]
    assert c["readings"]["gap"] < LIMIT < c["readings"]["control_gap"]


@pytest.mark.parametrize("fault", ["token", "state", "batch"])
def test_planted_fault_is_not_correct(tmp_path, fault):
    r = run_tiny(tmp_path, fault)
    assert r["correct"] is False
    assert r["checks"]["gap"]["value"] > LIMIT


def test_sharded_run_is_correct_and_without_the_exchange_is_not(tmp_path):
    sound = run_tiny(tmp_path, "none", chips=4)
    assert sound["correct"] is True and sound["device"]["count"] == 4
    broken = run_tiny(tmp_path, "exchange", chips=4)
    assert broken["correct"] is False
    assert broken["checks"]["gap"]["value"] > LIMIT
