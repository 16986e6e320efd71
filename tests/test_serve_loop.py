"""Continuous-batching correctness: requests served concurrently in a
shared slot pool must produce exactly what they produce when served
alone (per-slot cache cursors keep requests isolated)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import LM
from repro.runtime.serve_loop import Request, ServeLoop


def make_model():
    cfg = get_smoke_config("llama3_8b")
    model = LM(cfg, param_dtype=jnp.float32, attn_chunk=8, max_seq=64)
    return cfg, model, model.init(0)


def serve(model, params, requests, slots):
    loop = ServeLoop(model, params, slots=slots, max_len=48)
    for r in requests:
        loop.submit(r)
    done = loop.run()
    return {r.rid: list(r.out) for r in done}


class TestServeLoop:
    def test_concurrent_equals_solo(self):
        cfg, model, params = make_model()
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in (3, 7, 5, 4, 6)
        ]

        def reqs():
            return [Request(i, p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]

        solo = {}
        for r in reqs():
            solo.update(serve(model, params, [r], slots=2))
        together = serve(model, params, reqs(), slots=2)

        assert together.keys() == solo.keys()
        for rid in solo:
            assert together[rid] == solo[rid], rid

    def test_more_requests_than_slots_all_finish(self):
        cfg, model, params = make_model()
        rng = np.random.default_rng(1)
        requests = [
            Request(i, rng.integers(0, cfg.vocab_size, size=4).astype(
                np.int32), max_new_tokens=4)
            for i in range(7)
        ]
        done = serve(model, params, requests, slots=3)
        assert len(done) == 7
        assert all(len(v) == 4 for v in done.values())

    def test_eos_stops_early(self):
        cfg, model, params = make_model()
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, cfg.vocab_size, size=4).astype(np.int32)
        # find which token greedy decode emits first, then use it as eos
        probe = serve(model, params,
                      [Request(0, prompt, max_new_tokens=3)], slots=1)
        first = probe[0][0]
        loop = ServeLoop(model, params, slots=1, max_len=48)
        loop.submit(Request(1, prompt, max_new_tokens=8, eos_id=first))
        done = loop.run()
        assert len(done) == 1 and done[0].out[-1] == first
        assert len(done[0].out) <= 8

    def test_stateful_arch_rejected(self):
        cfg = get_smoke_config("rwkv6_1b6")
        model = LM(cfg, param_dtype=jnp.float32, max_seq=32)
        with pytest.raises(ValueError):
            ServeLoop(model, model.init(0))


# ---------------------------------------------------------------------- #
# spans, stats and counters of the serving loop
# ---------------------------------------------------------------------- #
def _traffic(cfg):
    rng = np.random.default_rng(3)
    return [Request(i, rng.integers(0, cfg.vocab_size, size=n).astype(
        np.int32), max_new_tokens=m)
        for i, (n, m) in enumerate([(3, 4), (6, 2), (2, 5), (5, 3), (4, 4)])]


def _serve_all(model, params, requests, tracer=None):
    from repro.obs import activate
    loop = ServeLoop(model, params, slots=2, max_len=48)
    for r in requests:
        loop.submit(r)
    with activate(tracer):
        done = loop.run()
    return loop, sorted(done, key=lambda r: r.rid)


class TestServeSpans:
    def test_tracing_changes_no_token_and_no_logit(self):
        from repro.obs import Tracer
        cfg, model, params = make_model()
        _, off = _serve_all(model, params, _traffic(cfg))
        _, on = _serve_all(model, params, _traffic(cfg), Tracer())
        assert [r.out for r in on] == [r.out for r in off]
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a.prompt_logits, b.prompt_logits)

    def test_each_step_is_one_span_with_four_children_in_order(self):
        from repro.obs import Tracer
        cfg, model, params = make_model()
        tracer = Tracer()
        loop, done = _serve_all(model, params, _traffic(cfg), tracer)
        steps = [s for s in tracer.spans if s.name == "serve.step"]
        assert len(steps) == loop.steps
        assert [s.attrs["step_num"] for s in steps] == list(range(loop.steps))
        children = [s for s in tracer.spans if s.depth == 1]
        assert len(children) == 4 * len(steps)
        for k, step in enumerate(steps):
            mine = children[4 * k:4 * k + 4]
            assert [c.name for c in mine] == ["serve.admit", "serve.launch",
                                             "serve.pull", "serve.pick"]
            assert step.ts <= mine[0].ts
            for a, b in zip(mine, mine[1:]):
                assert a.ts + a.dur <= b.ts
            assert mine[-1].ts + mine[-1].dur <= step.ts + step.dur
        assert sorted(r for s in steps
                      for r in s.attrs.get("rids_admitted", ())) \
            == sorted(r for s in steps
                      for r in s.attrs.get("rids_finished", ())) \
            == [r.rid for r in done]
        # the id lists only where a step admitted or finished requests
        assert all(s.attrs.get("rids_admitted", [0]) for s in steps)
        assert all(s.attrs.get("rids_finished", [0]) for s in steps)
        assert steps[0].attrs["compiled"] >= 1     # the decode program
        assert all("compiled" not in s.attrs for s in steps[1:])

    def test_prefill_and_decode_count_the_tokens_fed(self):
        from repro.obs import METRICS, Tracer
        cfg, model, params = make_model()
        tracer = Tracer()
        before = dict(METRICS.counters)
        requests = _traffic(cfg)
        loop, done = _serve_all(model, params, requests, tracer)
        steps = [s.attrs for s in tracer.spans if s.name == "serve.step"]
        prefill = sum(a["prefill"] for a in steps)
        decode = sum(a["decode"] for a in steps)
        # a request feeds its prompt, then every generated token but
        # the last
        assert prefill == sum(len(r.prompt) for r in done)
        assert decode == sum(len(r.out) - 1 for r in done)
        assert all(a["busy"] == a["prefill"] + a["decode"] <= 2
                   for a in steps)
        assert steps[0]["queued"] == len(requests) - 2

        def grew(name):
            return METRICS.counters[name] - before.get(name, 0)
        assert grew("serve.tokens_prefill") == prefill
        assert grew("serve.tokens_decode") == decode
        assert grew("serve.steps") == loop.steps == len(steps)
        assert grew("serve.admitted") == grew("serve.finished") == len(done)

    def test_counters_count_without_a_tracer(self):
        from repro.obs import METRICS
        cfg, model, params = make_model()
        before = dict(METRICS.counters)
        _, done = _serve_all(model, params, _traffic(cfg))
        assert METRICS.counters["serve.tokens_prefill"] \
            - before.get("serve.tokens_prefill", 0) \
            == sum(len(r.prompt) for r in done)


def _decode_hlo(model, scoped: bool) -> str:
    """The smoke decode program's compiled HLO text; without its named
    scopes if not ``scoped``."""
    import contextlib

    import jax
    from repro.runtime.serve_loop import decode_program

    params = jax.eval_shape(model.init, 0)
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    args = (params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32))
    scope = jax.named_scope
    if not scoped:
        jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        return decode_program(model).lower(*args).compile().as_text()
    finally:
        jax.named_scope = scope


def _canonical(hlo: str) -> str:
    """HLO text less its metadata, debug tables and the numbering of
    its instructions."""
    import re
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    for table in ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames"):
        hlo = re.sub(rf"\n{table}\n.*?\n\n", "\n", hlo, flags=re.S)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  hlo)


class TestDecodeScopes:
    def test_the_scopes_name_the_decode_programs_ops(self):
        import re
        _, model, _ = make_model()
        names = re.findall(r'op_name="([^"]*)"', _decode_hlo(model, True))
        parts = {p for n in names for p in n.split("/")}
        assert {"layers", "attn", "kv_write", "ffn", "unembed"} <= parts
        kv = [n for n in names if "kv_write" in n]
        assert kv and all("/layers/" in n and "/attn/" in n for n in kv)
        assert any("/ffn/" in n and "dot_general" in n for n in names)

    def test_the_servers_hook_gives_its_scoped_decode_program(self):
        _, model, params = make_model()
        hlo = ServeLoop(model, params, slots=2, max_len=16).decode_hlo()
        assert "/attn/kv_write/" in hlo
        assert _canonical(hlo) == _canonical(_decode_hlo(model, True))

    def test_the_scopes_change_only_metadata(self):
        _, model, _ = make_model()
        assert _canonical(_decode_hlo(model, True)) \
            == _canonical(_decode_hlo(model, False))
