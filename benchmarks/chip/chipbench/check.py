"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed and always holding the
one with the most tokens, is run through the float32 reference of the
configuration's architecture (``archs/<name>.py``): each prompt
followed by the tokens the server generated, in one causal pass.  Two numbers are read:

* ``gap``: over every generated token of the sample, the widest gap
  by which the token's reference logit lies below the reference's
  largest logit at that position.  Greedy decoding that follows the
  model closely picks the reference's top token or one within rounding
  of it.
* ``logit_err``: over the sample, the largest absolute difference
  between the logits the server produced at the last prompt position
  (``Request.prompt_logits``) and the reference's there.

The control puts the reference one precision lower in the program's
place (``hidden(control=True)``): at the same positions the
token it puts first is read under the float32 reference in the same
way, and its logits at the last prompt position against the
reference's.  ``verdict`` judges its readings (``as_control``) under
the same limits, and has to find them not correct.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def sample(records, k: int, seed: int) -> list[int]:
    """Indices of ``k`` complete requests: the longest one, and the
    rest drawn from the seed."""
    done = [i for i, r in enumerate(records) if r.complete]
    if not done:
        return []
    longest = max(done, key=lambda i: records[i].prompt_len + records[i].want)
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def readings(ref, model: dict, dtype: str, seed: int, mesh, served: list,
             *, max_len: int, max_out: int, control: bool = False) -> dict:
    """``ref``: the architecture's module (``archs.load``);
    ``served``: (prompt, generated tokens, prompt_logits) of each
    sampled request.  Returns the program's two numbers and, with
    ``control``, the control's."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(ref.make_weights(model, dtype, seed, mesh))
    phases = {"weights": time.perf_counter() - t0}
    k = len(served)
    tokens = np.zeros((k, max_len), np.int32)
    pos = np.zeros((k, max_out), np.int32)
    tok = np.zeros((k, max_out), np.int32)
    valid = np.zeros((k, max_out), bool)
    for i, (prompt, out, _) in enumerate(served):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        n = len(out)
        pos[i, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        tok[i, :n] = out
        valid[i, :n] = True

    with jax.default_matmul_precision("highest"):
        hid = jax.jit(lambda p, t: ref.hidden(model, p, t, control=False))(
            params, tokens)
        hid_c = (jax.jit(lambda p, t: ref.hidden(model, p, t, control=True))(
            params, tokens) if control else None)
        jax.block_until_ready((hid, hid_c))
        phases["hidden"] = time.perf_counter() - t0 - phases["weights"]

        @jax.jit
        def one(p, h, hc, pos, tok, valid):
            lg = ref.logits(p, h[pos], control=False)        # [M, V]
            best = lg.max(-1)
            picked = jnp.take_along_axis(lg, tok[:, None], 1)[:, 0]
            gap = jnp.max(jnp.where(valid, best - picked, 0.0))
            out = {"gap": gap, "row0": lg[0]}
            if hc is not None:
                lc = ref.logits(p, hc[pos], control=True)
                ctok = jnp.argmax(lc, -1)
                cp = jnp.take_along_axis(lg, ctok[:, None], 1)[:, 0]
                out["gap_c"] = jnp.max(jnp.where(valid, best - cp, 0.0))
                out["row0_c"] = lc[0]
            return out

        got = {"gap": 0.0, "logit_err": 0.0}
        if control:
            got.update({"control_gap": 0.0, "control_logit_err": 0.0})
        for i, (_, _, prompt_logits) in enumerate(served):
            r = jax.device_get(one(params, hid[i], None if hid_c is None
                                   else hid_c[i], pos[i], tok[i], valid[i]))
            row0 = np.asarray(r["row0"], np.float64)
            got["gap"] = max(got["gap"], float(r["gap"]))
            got["logit_err"] = max(got["logit_err"], float(np.max(np.abs(
                np.asarray(prompt_logits, np.float64) - row0))))
            if control:
                got["control_gap"] = max(got["control_gap"], float(r["gap_c"]))
                got["control_logit_err"] = max(
                    got["control_logit_err"],
                    float(np.max(np.abs(np.asarray(r["row0_c"], np.float64)
                                        - row0))))
    phases["logits"] = time.perf_counter() - t0 - sum(phases.values())
    got["phases_s"] = phases
    return got


def load_limits(path: Path) -> dict:
    """``{number: limit}`` of the numbers a cell compares."""
    if not path.exists():
        return {}
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


def as_control(numbers: dict) -> dict:
    """The control's readings under the program's names: judged by
    ``verdict``, they put the control in the program's place."""
    return {k.removeprefix("control_"): v for k, v in numbers.items()
            if k.startswith("control_")}


def verdict(numbers: dict, limits: dict, failed: int,
            attempted: int) -> tuple[bool, dict]:
    """``correct`` and, for each number compared, its reading and its
    limit.  Every request sent has to be answered in full, and each
    compared number has to be at or under its limit."""
    shown = {"unanswered": {"value": failed, "limit": 0}}
    ok = failed == 0 and attempted > 0 and bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        shown[name] = {"value": value, "limit": limit}
        ok = ok and value is not None and bool(np.isfinite(value)) \
            and value <= limit
    return ok, shown
