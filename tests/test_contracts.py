"""Static program contract checker (`repro.analysis.contracts`).

Covers the four passes with deliberately-broken fixtures — each seeded
violation must surface as its pinned finding code — plus golden
eligibility matrices, ratchet semantics end-to-end through the CLI, the
``python -O`` regression for the converted library asserts, and a
matrix-vs-execution cross-check against the fused kernel call counters.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.contracts import ast_lint, eligibility, jaxpr_lint, \
    kernel_contracts, ratchet
from repro.analysis.contracts.findings import CODES, Finding, assign_keys
from repro.kernels import specs as KS

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(findings):
    return [f.code for f in findings]


def _buf(shape, block, index_map, dtype=np.float32):
    return KS.BufferSpec(shape=shape, dtype=dtype, block_shape=block,
                         index_map=index_map)


def _capture(inputs, outputs, grid, scratch=(), prefetch=()):
    return KS.KernelCapture(name="fixture", grid=grid, inputs=list(inputs),
                            outputs=list(outputs), scratch=list(scratch),
                            num_scalar_prefetch=len(prefetch),
                            prefetch=tuple(prefetch), interpret=True)


class TestSeededKernelViolations:
    """Pass 1 fixtures: each broken capture yields its pinned code."""

    def test_oob_index_map_caught(self):
        # grid runs to 4 but the operand only has 3 rows: classic
        # off-by-one a missing clamp would produce
        cap = _capture(
            inputs=[_buf((3, 8), (1, 8), lambda i: (i, 0))],
            outputs=[_buf((4, 8), (1, 8), lambda i: (i, 0))],
            grid=(4,))
        out = kernel_contracts.check_capture(cap)
        assert "KC001" in _codes(out)

    def test_bad_prefetch_table_caught(self):
        # the block table points one page past the pool — the null-page
        # clamp idiom exists to make this impossible
        table = np.array([0, 1, 4], np.int32)          # pool has 4 pages
        cap = _capture(
            inputs=[_buf((4, 8, 16), (1, 8, 16),
                         lambda i, t: (t[i], 0, 0))],
            outputs=[_buf((3, 8, 16), (1, 8, 16), lambda i, t: (i, 0, 0))],
            grid=(3,), prefetch=(table,))
        out = kernel_contracts.check_capture(cap)
        assert "KC001" in _codes(out)

    def test_vmem_over_budget_caught(self):
        cap = _capture(
            inputs=[_buf((128, 128), (128, 128), lambda i: (0, 0))],
            outputs=[_buf((128, 128), (128, 128), lambda i: (0, 0))],
            grid=(1,), scratch=[((128, 128), np.float32)])
        out = kernel_contracts.check_capture(cap, vmem_budget=64 * 1024)
        assert "KC002" in _codes(out)

    def test_divisibility_caught(self):
        cap = _capture(
            inputs=[_buf((8, 8), (3, 8), lambda i: (i, 0))],
            outputs=[_buf((8, 8), (8, 8), lambda i: (0, 0))],
            grid=(1,))
        out = kernel_contracts.check_capture(cap)
        assert "KC003" in _codes(out)

    def test_f16_accumulator_caught(self):
        def bad(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float16)

        out = []
        kernel_contracts.check_accumulators(
            bad, (jnp.zeros((4, 4), jnp.float16),
                  jnp.zeros((4, 4), jnp.float16)), {}, "fixture.f16", out)
        assert "KC004" in _codes(out)

    def test_int8_dot_without_int32_caught(self):
        def bad(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        out = []
        kernel_contracts.check_accumulators(
            bad, (jnp.zeros((4, 4), jnp.int8),
                  jnp.zeros((4, 4), jnp.int8)), {}, "fixture.int8", out)
        assert "KC005" in _codes(out)

    def test_shipped_kernels_are_clean(self):
        """Acceptance: zero findings over the whole capture registry at
        default block sizes and the default VMEM budget."""
        out = kernel_contracts.check_kernels()
        assert out == [], [f"{f.code} {f.scope}: {f.message}" for f in out]


class TestSeededAstViolations:
    """Pass 4 fixtures run through ``lint_source`` directly."""

    def test_bare_assert_caught(self):
        src = textwrap.dedent("""
            def free(self, block):
                assert block in self.used
                self.used.remove(block)
        """)
        out = ast_lint.lint_source(src, "src/repro/fixture.py")
        assert _codes(out) == ["RR001"]
        assert out[0].scope == "free"

    def test_mutable_dataclass_default_caught(self):
        src = textwrap.dedent("""
            from dataclasses import dataclass

            @dataclass
            class Cfg:
                layers: list = []
                names: dict = dict()
        """)
        out = ast_lint.lint_source(src, "src/repro/fixture.py")
        assert _codes(out) == ["RR002", "RR002"]

    def test_interpret_true_default_caught(self):
        src = "def run(x, interpret=True):\n    return x\n"
        out = ast_lint.lint_source(src, "src/repro/fixture.py")
        assert _codes(out) == ["RR003"]

    def test_interpret_none_default_clean(self):
        src = "def run(x, interpret=None):\n    return x\n"
        assert ast_lint.lint_source(src, "src/repro/fixture.py") == []

    def test_time_time_caught(self):
        src = "import time\n\ndef step():\n    return time.time()\n"
        out = ast_lint.lint_source(src, "src/repro/fixture.py")
        assert _codes(out) == ["RR004"]


class TestSeededJaxprViolations:
    """Pass 3 rules on synthetic traced programs."""

    def test_f16_dot_caught(self):
        closed = jax.make_jaxpr(
            lambda a, b: jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float16))(
            jnp.zeros((4, 4), jnp.float16), jnp.zeros((4, 4), jnp.float16))
        assert "JX002" in _codes(jaxpr_lint.lint_jaxpr(closed, "fixture"))

    def test_convert_round_trip_caught(self):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))(
            jnp.zeros((8,), jnp.float32))
        out = jaxpr_lint.lint_jaxpr(closed, "fixture")
        assert "JX003" in _codes(out)

    def test_widening_round_trip_clean(self):
        # f32 -> f64-wide is impossible without x64; bf16 -> f32 -> bf16
        # widens in transit and must NOT fire
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float32).astype(jnp.bfloat16))(
            jnp.zeros((8,), jnp.bfloat16))
        assert jaxpr_lint.lint_jaxpr(closed, "fixture") == []

    def test_host_callback_caught(self):
        closed = jax.make_jaxpr(
            lambda x: jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((8,), jnp.float32), x))(
            jnp.zeros((8,), jnp.float32))
        assert "JX004" in _codes(jaxpr_lint.lint_jaxpr(closed, "fixture"))

    def test_f64_caught(self):
        with jax.enable_x64():
            closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64))(
                jnp.zeros((8,), jnp.float32))
        assert "JX001" in _codes(jaxpr_lint.lint_jaxpr(closed, "fixture"))


class TestEligibility:
    """Pass 2: golden matrices + the completeness invariant."""

    @pytest.mark.parametrize("name", ["llama3_8b", "jamba_1_5_large_398b"])
    def test_golden_matrix(self, name):
        with open(os.path.join(GOLDEN, f"eligibility_{name}.json")) as f:
            golden = json.load(f)
        got = json.loads(json.dumps(eligibility.audit_config(name)))
        assert got == golden

    def test_every_reference_cell_explained(self):
        assert eligibility.check_eligibility() == []

    def test_unexplained_reference_cell_is_el001(self):
        matrix = {"cfg": {"qkv": {"status": "reference", "kernel": None,
                                  "wiring": "merged_wqkv", "layers": 4,
                                  "reasons": []}}}
        # check_eligibility audits real configs; the invariant itself is
        # what the fixture exercises, via the same cell walk
        out = []
        for cfg_name, sites in matrix.items():
            for site, cell in sites.items():
                if cell["status"] == "reference" and not cell["reasons"]:
                    out.append(Finding("EL001", f"configs/{cfg_name}", site,
                                       "unexplained reference cell"))
        assert _codes(out) == ["EL001"]

    def test_disabled_stamp_is_all_reference_with_reasons(self):
        from repro.core.stamp import StampConfig
        m = eligibility.audit_config(
            "llama3_8b", stamp=StampConfig(enabled=False))
        assert all(c["status"] == "reference" for c in m.values())
        assert all("stamp_disabled" in c["reasons"] for c in m.values())

    def test_matrix_document_schema(self):
        m = eligibility.audit_all(["llama3_8b"])
        doc = eligibility.matrix_document(m)
        assert doc["version"] == 1
        assert doc["stamp"]["execution"] == "fused"
        assert set(doc["configs"]) == {"llama3_8b"}


class TestMatrixMatchesExecution:
    """Cross-check: the audited matrix agrees with the kernels the fused
    prefill actually dispatches (same counter idiom as
    test_stamp_fused.TestNoReferenceRoundTrips)."""

    def _counted(self, monkeypatch):
        from repro.kernels import ops as kops
        counts = {"single": 0, "dual": 0}
        real_single, real_dual = (kops.stamp_quant_matmul,
                                  kops.stamp_quant_dual_matmul)

        def single(*a, **k):
            counts["single"] += 1
            return real_single(*a, **k)

        def dual(*a, **k):
            counts["dual"] += 1
            return real_dual(*a, **k)

        monkeypatch.setattr(kops, "stamp_quant_matmul", single)
        monkeypatch.setattr(kops, "stamp_quant_dual_matmul", dual)
        return counts

    def test_dense_layer_matrix_matches_counters(self, monkeypatch):
        from repro.core.stamp import StampConfig
        from repro.models import lm
        from repro.models.config import ModelConfig
        from repro.serving import kvcache as KV
        cfg = ModelConfig(name="xcheck", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=128, qkv_bias=True)
        stamp = StampConfig(num_hi_tokens=8, execution="fused")
        matrix = lm.fused_site_matrix(cfg, stamp)
        n_single = sum(1 for c in matrix.values()
                       if c["kernel"] == "stamp_quant_matmul")
        n_dual = sum(1 for c in matrix.values()
                     if c["kernel"] == "stamp_quant_dual_matmul")
        assert all(c["status"] == "fused" for c in matrix.values())

        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        pf = lm.prepare_fused_weights(params, stamp)
        counts = self._counted(monkeypatch)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 128, (1, 64)), jnp.int32)
        logits, _ = lm.prefill(
            params=pf, batch={"tokens": toks}, cfg=cfg,
            serve=lm.ServeConfig(stamp=stamp,
                                 kv=KV.KVCacheConfig(quantized=True,
                                                     num_hi=16),
                                 cache_capacity=96))
        assert bool(jnp.isfinite(logits).all())
        # the scanned period traces each fused site exactly once
        assert counts["single"] == n_single
        assert counts["dual"] == n_dual

    def test_reference_matrix_means_no_fused_calls(self, monkeypatch):
        from repro.models import lm
        from repro.models.config import ModelConfig
        from repro.serving import kvcache as KV
        cfg = ModelConfig(name="xcheck-ref", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=128)
        matrix = lm.fused_site_matrix(cfg, None)
        assert all(c["status"] == "reference" for c in matrix.values())
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        counts = self._counted(monkeypatch)
        toks = jnp.zeros((1, 32), jnp.int32)
        lm.prefill(params=params, batch={"tokens": toks}, cfg=cfg,
                   serve=lm.ServeConfig(
                       stamp=None, kv=KV.KVCacheConfig(quantized=True,
                                                       num_hi=16),
                       cache_capacity=64))
        assert counts == {"single": 0, "dual": 0}


class TestRatchet:
    def _findings(self):
        return [Finding("RR001", "src/repro/a.py", "f", "assert one"),
                Finding("RR001", "src/repro/a.py", "f", "assert two"),
                Finding("RR004", "src/repro/b.py", "g", "time.time")]

    def test_keys_are_stable_and_ordinal(self):
        fs = self._findings()
        assign_keys(fs)
        assert fs[0].key == "RR001:src/repro/a.py:f#0"
        assert fs[1].key == "RR001:src/repro/a.py:f#1"
        assert fs[2].key == "RR004:src/repro/b.py:g#0"

    def test_grandfather_new_stale(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        fs = self._findings()
        ratchet.write_baseline(path, fs, vmem_budget=1)
        baseline = ratchet.load_baseline(path)

        # same findings: all grandfathered
        new, grand, stale = ratchet.ratchet(self._findings(), baseline)
        assert (len(new), len(grand), stale) == (0, 3, [])

        # one extra finding in an allowlisted scope: only IT is new
        more = self._findings() + [
            Finding("RR001", "src/repro/a.py", "f", "assert three")]
        new, grand, stale = ratchet.ratchet(more, baseline)
        assert [f.message for f in new] == ["assert three"]

        # one fixed: its key goes stale, nothing new
        new, grand, stale = ratchet.ratchet(self._findings()[:2], baseline)
        assert new == [] and stale == ["RR004:src/repro/b.py:g#0"]

    def test_version_mismatch_raises(self, tmp_path):
        path = tmp_path / "baseline.json"
        path.write_text('{"version": 99, "allowlist": []}')
        with pytest.raises(ValueError):
            ratchet.load_baseline(str(path))

    def test_missing_baseline_is_none(self, tmp_path):
        assert ratchet.load_baseline(str(tmp_path / "nope.json")) is None


class TestCliRatchetEndToEnd:
    """The gate as CI runs it: seeded repo fails, baseline grandfathers,
    fixing goes stale — all through the module CLI and exit codes."""

    def _run(self, tmp, *extra):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis.contracts",
             "--passes", "ast", "--root", str(tmp),
             "--baseline", str(tmp / "STATIC_ANALYSIS.json"), *extra],
            capture_output=True, text=True, env=env, cwd=REPO)

    def test_seed_baseline_fix(self, tmp_path):
        pkg = tmp_path / "src" / "repro"
        pkg.mkdir(parents=True)
        (pkg / "bad.py").write_text(
            "def f(x):\n    assert x\n    return x\n")

        r = self._run(tmp_path)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "RR001:src/repro/bad.py:f#0" in r.stderr

        r = self._run(tmp_path, "--update-baseline")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads((tmp_path / "STATIC_ANALYSIS.json").read_text())
        assert doc["allowlist"] == ["RR001:src/repro/bad.py:f#0"]

        r = self._run(tmp_path)
        assert r.returncode == 0 and "grandfathered" in r.stdout

        (pkg / "bad.py").write_text("def f(x):\n    return x\n")
        r = self._run(tmp_path)
        assert r.returncode == 0 and "stale" in r.stdout

    def test_committed_baseline_is_green(self):
        """The repo's own STATIC_ANALYSIS.json passes the ast pass (the
        full four-pass run is the CI step; ast is the cheap sentinel)."""
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        r = subprocess.run(
            [sys.executable, "-m", "repro.analysis.contracts",
             "--passes", "ast"],
            capture_output=True, text=True, env=env, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr


class TestPythonOMinusO:
    """Satellite (a) regression: validation that used to be ``assert`` must
    still raise under ``python -O`` (where asserts vanish)."""

    CASES = {
        "wht_pow2": """
            import jax.numpy as jnp
            from repro.kernels.wht import wht_pallas
            try:
                wht_pallas(jnp.zeros((1, 24, 8)), axis=-2, block=8)
            except ValueError:
                print("RAISED")
        """,
        "stamp_bits": """
            import jax.numpy as jnp
            from repro.core.stamp import prepare_linear
            try:
                prepare_linear(jnp.zeros((8, 8)), bits=16)
            except ValueError:
                print("RAISED")
        """,
        "matmul_k": """
            import jax.numpy as jnp
            from repro.kernels.stamp_matmul import stamp_quant_matmul_pallas
            try:
                stamp_quant_matmul_pallas(
                    jnp.zeros((1, 8, 16)), jnp.zeros((24, 8), jnp.int8),
                    jnp.ones((1, 8)), jnp.zeros((1, 8)),
                    jnp.zeros((1, 8)), num_hi=4)
            except ValueError:
                print("RAISED")
        """,
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_valueerror_survives_dash_o(self, name):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        r = subprocess.run(
            [sys.executable, "-O", "-c", textwrap.dedent(self.CASES[name])],
            capture_output=True, text=True, env=env)
        assert r.returncode == 0, r.stderr
        assert "RAISED" in r.stdout, r.stdout + r.stderr


class TestFindingCodes:
    def test_unknown_code_rejected(self):
        with pytest.raises(ValueError):
            Finding("ZZ999", "p", "s", "m")

    def test_codes_cover_all_passes(self):
        prefixes = {c[:2] for c in CODES}
        assert prefixes == {"KC", "EL", "JX", "RR"}
