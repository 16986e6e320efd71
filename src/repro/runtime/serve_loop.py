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

Observability (``repro.obs``).  Every step counts into ``METRICS``:
``serve.steps``, ``serve.admitted``, ``serve.finished``,
``serve.tokens_prefill`` (slots that fed a prompt token) and
``serve.tokens_decode`` (slots that fed a generated token).  Under an
active tracer each step is one ``serve.step`` span (``step_num`` = the
loop's step counter) with four children in order: ``serve.admit``,
``serve.launch`` (tokens and positions to the device, the decode
program's dispatch), ``serve.pull`` (the step's last-position logits
to the host) and ``serve.pick`` (argmax and slot bookkeeping).  The
step span's stats: ``admitted``, ``prefill``, ``decode``, ``busy``,
``queued``; in a step that admitted or finished requests,
``rids_admitted`` and ``rids_finished``; and, in a step in which JAX
compiled a program or loaded one from its persistent cache,
``compiled`` (how many, by ``repro.obs``'s compile counter).  With no
tracer a step reads one global and computes none of the stats.
:meth:`ServeLoop.decode_hlo` gives the decode program's compiled HLO,
whose ``op_name`` metadata carries the named scopes of
``LM.decode_step``, to map a device trace's ops to them.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LM
from repro.obs import METRICS, count_compiles, current_tracer, programs

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
        self.steps = 0                  # steps run, over every call of run
        count_compiles()

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def decode_hlo(self) -> str:
        """The decode program's compiled HLO text at this server's
        shapes, with each op's ``op_name`` metadata."""
        return self._step.lower(
            self.params, self.cache, jnp.asarray(self.tokens),
            jnp.asarray(self.pos)).compile().as_text()

    def _admit(self, rids: list | None = None) -> int:
        """Fill free slots from the queue; returns how many were
        admitted, and appends their ids to ``rids`` if given."""
        n = 0
        for s in range(self.slots):
            if self.active[s] is None and self.queue:
                req = self.queue.popleft()
                self.active[s] = req
                self.pos[s] = 0
                self.tokens[s, 0] = req.prompt[0]
                n += 1
                if rids is not None:
                    rids.append(req.rid)
        return n

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

    def _launch(self) -> jax.Array:
        """Tokens and positions to the device, and the decode program
        dispatched; returns its logits (not waited for)."""
        logits, self.cache = self._step(
            self.params, self.cache, jnp.asarray(self.tokens),
            jnp.asarray(self.pos))
        return logits

    def _pick(self, logits_np: np.ndarray,
              finished: list[Request]) -> tuple[int, int]:
        """Advance every slot on its logits; appends the requests that
        finished to ``finished``.  Returns the slots that fed a prompt
        token this step and those that fed a generated one."""
        prefill = decode = 0
        for s in range(self.slots):
            req = self.active[s]
            if req is not None:
                if self.pos[s] < len(req.prompt):
                    prefill += 1
                else:
                    decode += 1
            self._advance_slot(s, logits_np[s])
            if req is not None and req.done:
                finished.append(req)
        return prefill, decode

    def _run_step(self, finished: list[Request]) -> None:
        tracer = current_tracer()
        n_done = len(finished)
        if tracer is None:
            admitted = self._admit()
            logits = self._launch()
            prefill, decode = self._pick(np.asarray(logits[:, -1]), finished)
        else:
            rids: list[int] = []
            built = programs()
            with tracer.span("serve.step", step_num=self.steps) as step:
                with tracer.span("serve.admit"):
                    admitted = self._admit(rids)
                with tracer.span("serve.launch"):
                    logits = self._launch()
                with tracer.span("serve.pull"):
                    logits_np = np.asarray(logits[:, -1])
                with tracer.span("serve.pick"):
                    prefill, decode = self._pick(logits_np, finished)
                stats = step.attrs
                stats.update(admitted=admitted, prefill=prefill,
                             decode=decode, busy=prefill + decode,
                             queued=len(self.queue))
                if rids:
                    stats["rids_admitted"] = rids
                if len(finished) > n_done:
                    stats["rids_finished"] = [r.rid for r in finished[n_done:]]
                built = programs() - built
                if built:
                    stats["compiled"] = built
        self.steps += 1
        METRICS.counter("serve.steps")
        METRICS.counter("serve.admitted", admitted)
        METRICS.counter("serve.finished", len(finished) - n_done)
        METRICS.counter("serve.tokens_prefill", prefill)
        METRICS.counter("serve.tokens_decode", decode)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Serve until queue + slots drain; returns finished requests."""
        finished: list[Request] = []
        steps = 0
        while (any(r is not None for r in self.active)
               or self.queue) and steps < max_steps:
            self._run_step(finished)
            steps += 1
        return finished
