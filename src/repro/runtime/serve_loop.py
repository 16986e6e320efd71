"""Batched serving loop: request queue → slot-based continuous batching.

A fixed pool of ``slots`` (the batch dimension of the jitted decode
step), requests admitted the moment a slot frees up, per-slot cache
cursors (vectorized positions through the decode path), greedy decode
until EOS/max-tokens, slot recycled.
One jitted step serves the whole pool every iteration regardless of
request boundaries — the invariant continuous batching exists to
maintain.

Restriction: attention-cache architectures only (Mamba/RWKV slots
would need per-slot state resets — documented future work).
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LM

__all__ = ["Request", "ServeLoop", "decode_program"]


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # [P] int32
    max_new_tokens: int = 16
    eos_id: int = -1                    # -1: never stops early
    out: list = field(default_factory=list)
    done: bool = False
    # decode-path logits [V] at the last prompt position (the ones the
    # first generated token is taken from)
    prompt_logits: np.ndarray | None = None


def decode_program(model: LM, cache_sharding=None):
    """The jitted serving step ``(params, cache, tokens, pos) -> (logits,
    cache)``: positions are traced, so one program serves every step;
    the cache is donated and kept on ``cache_sharding``."""
    return jax.jit(model.decode_step, donate_argnums=(1,),
                   out_shardings=(None, cache_sharding))


class ServeLoop:
    """Continuous-batching server.

    The KV cache has the model's parameter dtype.  ``cache_sharding``
    (a pytree of shardings matching ``model.init_cache``) places the
    cache on a mesh and pins it there across steps; params arrive
    already placed.  The cache is donated to each step, so one copy is
    resident.
    """

    def __init__(self, model: LM, params, *, slots: int = 4,
                 max_len: int = 64, cache_sharding=None) -> None:
        if any(s.kind != "attn" for s in model.specs):
            raise ValueError(
                "continuous batching requires attention caches "
                "(stateful SSM/RWKV slots need per-slot state resets)")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache = jax.jit(lambda: model.init_cache(slots, max_len),
                             out_shardings=cache_sharding)()
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * slots
        # per-slot cursor: index the next token will be written at
        self.pos = np.zeros(slots, np.int32)
        self.tokens = np.zeros((slots, 1), np.int32)
        self._step = decode_program(model, cache_sharding)

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _admit(self) -> None:
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                req = self.queue.popleft()
                self.active[s] = req
                self.pos[s] = 0
                self.tokens[s, 0] = req.prompt[0]

    def _advance_slot(self, s: int, logits: np.ndarray) -> None:
        req = self.active[s]
        if req is None:
            self.pos[s] = 0           # idle slots rewrite position 0
            return
        p = int(self.pos[s])
        plen = len(req.prompt)
        if p + 1 < plen:                       # still prefilling
            self.tokens[s, 0] = req.prompt[p + 1]
        else:                                  # generating
            if p + 1 == plen:
                req.prompt_logits = logits.copy()  # not a view of the batch
            tok = int(np.argmax(logits))
            req.out.append(tok)
            self.tokens[s, 0] = tok
            if (len(req.out) >= req.max_new_tokens
                    or tok == req.eos_id
                    or p + 2 >= self.max_len):
                req.done = True
                self.active[s] = None
                self.pos[s] = 0
                return
        self.pos[s] = p + 1

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Serve until queue + slots drain; returns finished requests."""
        finished: list[Request] = []
        steps = 0
        while (any(r is not None for r in self.active)
               or self.queue) and steps < max_steps:
            self._admit()
            logits, self.cache = self._step(
                self.params, self.cache, jnp.asarray(self.tokens),
                jnp.asarray(self.pos))
            logits_np = np.asarray(logits[:, -1])
            for s in range(self.slots):
                before = self.active[s]
                self._advance_slot(s, logits_np[s])
                if before is not None and before.done:
                    finished.append(before)
            steps += 1
        return finished
