"""What the harness records of a run, and the end-to-end arithmetic.

Times are host-clock seconds from the start of the measured window.
Every request sent in the window counts, also one that finishes in the
drain after it.  A request that never gets its first or last token is
charged the time until the drain ended, so it lies at the far end of
every tail.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class RequestRecord:
    due: float
    prompt_len: int
    want: int                       # tokens it asks for
    sent: float | None = None       # handed to the server
    admit: float | None = None      # start of the step that gave it a slot
    admit_step: int | None = None
    # the end of the step that produced each token it got, in order
    stamps: list[float] = field(default_factory=list)

    @property
    def n_out(self) -> int:
        return len(self.stamps)

    @property
    def first(self) -> float | None:
        return self.stamps[0] if self.stamps else None

    @property
    def last(self) -> float | None:
        return self.stamps[-1] if self.stamps else None

    @property
    def complete(self) -> bool:
        return self.n_out == self.want


@dataclass
class StepRecord:
    start: float
    end: float
    # the valid cache entries each admitted slot's token attends over,
    # its own included, in the order the slots' requests were admitted
    ctx_lens: tuple[int, ...]
    n_logits: int       # positions whose logits gave a token

    @property
    def n_tokens(self) -> int:
        """Tokens the step took in, one per admitted slot."""
        return len(self.ctx_lens)

    @property
    def sum_ctx(self) -> int:
        """Valid cache entries the step's tokens attend over, in all."""
        return sum(self.ctx_lens)


def percentile(values, q: float) -> float:
    """``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, float), q))


def ttft_s(recs: list[RequestRecord], end: float) -> list[float]:
    return [(r.first if r.first is not None else end) - r.due for r in recs]


def itl_ms(recs: list[RequestRecord], end: float) -> list[float]:
    """Every gap between consecutive output tokens of every request, ms.
    A request that stopped short is charged one more gap, from its last
    token (or its due time) to ``end``."""
    out = []
    for r in recs:
        out.extend(1e3 * np.diff(r.stamps))
        if not r.complete:
            out.append(1e3 * (end - (r.last if r.stamps else r.due)))
    return out


def queue_wait_share(recs: list[RequestRecord], end: float) -> float:
    """Share of all time-to-first-token spent waiting for a slot, %."""
    wait = sum((r.admit if r.admit is not None else end) - r.due
               for r in recs)
    return 100.0 * wait / sum(ttft_s(recs, end))


def lateness(recs: list[RequestRecord]) -> tuple[float, float]:
    """How late the generator handed requests over: (mean, max) s."""
    late = [r.sent - r.due for r in recs if r.sent is not None]
    return (float(np.mean(late)), float(np.max(late))) if late else (0.0, 0.0)
