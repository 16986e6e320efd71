"""Tests of the dry-run machinery itself: sharding rules, step
builders, and the trip-count-aware HLO analyzer — on the single local
device (the 512-device pass runs via launch/dryrun.py)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_serve_step, build_train_step

import repro.configs.base as config_base

# register tiny shapes usable by the step builders
config_base.SHAPES.setdefault(
    "unit_train", ShapeConfig("unit_train", 32, 4, "train"))
config_base.SHAPES.setdefault(
    "unit_decode", ShapeConfig("unit_decode", 64, 4, "decode"))


class TestHloAnalyzer:
    def test_scan_flops_weighted_by_trip_count(self):
        def f(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=10)
            return y

        x = jnp.zeros((64, 64))
        c = jax.jit(f).lower(x, x).compile()
        st = analyze_hlo(c.as_text())
        assert st.flops == pytest.approx(10 * 2 * 64**3, rel=0.01)
        assert st.max_trip == 10

    def test_nested_scans_multiply(self):
        def g(x, w):
            def inner(c, _):
                return c @ w, None

            def outer(c, _):
                c, _ = jax.lax.scan(inner, c, None, length=5)
                return c, None

            y, _ = jax.lax.scan(outer, x, None, length=3)
            return y

        x = jnp.zeros((32, 32))
        c = jax.jit(g).lower(x, x).compile()
        st = analyze_hlo(c.as_text())
        assert st.flops == pytest.approx(15 * 2 * 32**3, rel=0.01)

    def test_xla_cost_analysis_undercounts(self):
        """The reason this analyzer exists."""
        def f(x, w):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=10)
            return y

        x = jnp.zeros((64, 64))
        c = jax.jit(f).lower(x, x).compile()
        xla_flops = c.cost_analysis()["flops"]
        ours = analyze_hlo(c.as_text()).flops
        assert ours > 5 * xla_flops

    def test_dynamic_slice_not_counted_as_full_operand(self):
        def f(big):
            def body(acc, i):
                return acc + jax.lax.dynamic_slice_in_dim(big, i, 8), None
            out, _ = jax.lax.scan(body, jnp.zeros((8, 256)),
                                  jnp.arange(64))
            return out

        big = jnp.zeros((1024, 256))
        c = jax.jit(f).lower(big).compile()
        st = analyze_hlo(c.as_text())
        # 64 iterations touching ~8x256 floats each, not 1024x256
        assert st.bytes_accessed < 64 * (8 * 256 * 4) * 12


class TestCellCaching:
    """Cache + artifact hygiene for launch/dryrun.py (no compilation)."""

    def test_ok_cell_is_cached(self, tmp_path):
        from repro.launch.dryrun import _cached_ok
        p = tmp_path / "cell.json"
        p.write_text('{"status": "ok", "arch": "a"}')
        assert _cached_ok(p)

    def test_error_cell_is_stale(self, tmp_path):
        from repro.launch.dryrun import _cached_ok
        p = tmp_path / "cell.json"
        p.write_text('{"status": "error", "error": "boom"}')
        assert not _cached_ok(p)

    def test_unreadable_cell_is_stale(self, tmp_path):
        from repro.launch.dryrun import _cached_ok
        p = tmp_path / "cell.json"
        p.write_text("{truncated")
        assert not _cached_ok(p)
        assert not _cached_ok(tmp_path / "missing.json")

    def test_write_hlo_survives_missing_zstandard(self, tmp_path):
        """zstandard is optional: the gzip fallback must round-trip."""
        import gzip
        from repro.launch.dryrun import _write_hlo
        out = _write_hlo(tmp_path / "cell.hlo", "HloModule m")
        assert out.exists()
        if out.suffix == ".gz":
            assert gzip.decompress(out.read_bytes()) == b"HloModule m"
        else:  # zstandard present in this environment
            import zstandard
            assert zstandard.ZstdDecompressor().decompress(
                out.read_bytes()) == b"HloModule m"

    def test_traceback_paths_relativized(self):
        from repro.launch.dryrun import _REPO_ROOT, _sanitize_traceback
        tb = (f'  File "{_REPO_ROOT}/src/repro/launch/dryrun.py", '
              'line 1, in main\n')
        clean = _sanitize_traceback(tb)
        assert _REPO_ROOT not in clean
        assert 'File "src/repro/launch/dryrun.py"' in clean


class TestStepBuilders:
    def test_train_bundle_lowers_and_analyzes(self):
        mesh = make_local_mesh(1, 1)
        cfg = get_smoke_config("mixtral_8x7b")
        b = build_train_step("mixtral_8x7b", "unit_train", mesh, cfg=cfg,
                             attn_chunk=16)
        with mesh:
            compiled = b.step_fn.lower(
                b.input_specs["params"], b.input_specs["opt_state"],
                b.input_specs["batch"]).compile()
        st = analyze_hlo(compiled.as_text())
        assert st.flops > 0
        assert st.bytes_accessed > 0
        assert compiled.memory_analysis().temp_size_in_bytes > 0

    def test_serve_bundle_lowers(self):
        mesh = make_local_mesh(1, 1)
        cfg = get_smoke_config("jamba_15_large")
        b = build_serve_step("jamba_15_large", "unit_decode", mesh,
                             cfg=cfg, attn_chunk=16)
        with mesh:
            compiled = b.step_fn.lower(
                b.input_specs["params"], b.input_specs["cache"],
                b.input_specs["tokens"]).compile()
        assert analyze_hlo(compiled.as_text()).flops > 0

    def test_policy_picker(self):
        from repro.launch.sharding import pick_policy
        assert pick_policy(int(1e9)) == "tp"
        assert pick_policy(int(5e10)) == "fsdp_tp"
