"""Open-loop traffic from a mix's parameter file and a seed.

A mix file (``traffic/<name>.json``) gives the arrival rate, the
distributions of prompt and output lengths and the server's slots and
cache length.  For a window of ``seconds`` the generator sends
``round(rate * seconds)`` requests.  Their sizes and the gaps between
arrivals are the same set for every seed: the quantiles of the stated
distributions at ``(i + 0.5) / n``.  The seed shuffles which prompt
length goes with which output length and in which order the gaps
come, and draws the token ids.  So every seed asks for the same work,
and two seeds differ only in the order it arrives.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Planned:
    """One request of the schedule: due ``due_s`` seconds into the
    window, with its prompt and the number of tokens to generate."""
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def load(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"{path}: unknown arrivals {mix.get('arrivals')!r}")
    return mix


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a clipped lognormal, given
    by the ``mean`` it has before clipping and its ``sigma``."""
    if dist.get("dist") != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    sigma = dist["sigma"]
    median = dist["mean"] * np.exp(-sigma ** 2 / 2)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(median * np.exp(sigma * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` exponential mid-quantiles scaled to add up to ``seconds``."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (seconds / g.sum())


def schedule(mix: dict, seconds: float, seed: int,
             vocab: int) -> list[Planned]:
    """The requests due in a window of ``seconds``, in order of due
    time; the first is due at 0 and the last before ``seconds``."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(quantile_lengths(mix["prompt"], n))
    outputs = rng.permutation(quantile_lengths(mix["output"], n))
    gaps = rng.permutation(quantile_gaps(n, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Planned(float(due[i]),
                    rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                    int(outputs[i]))
            for i in range(n)]
