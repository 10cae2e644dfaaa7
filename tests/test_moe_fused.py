"""Fused grouped-MoE path: the grouped expert kernel
`stamp_quant_ragged_matmul` vs the unfused oracle (ragged groups, empty
groups and tiles, rows past a tile's count), the dropless
`moe_ffn_dropless_fused` vs the dense loop `moe_ffn_dropless` (odd token
counts, a share of the experts, pad tokens), the call-counter proof that
fused MoE prefill issues zero dense-loop expert einsums, the router-stats
telemetry ride-along, expert-parallel sharding of the prepared int8
buffers, and the single-branch chunk-attention regression (the XLA
fallback must not evaluate flash AND chunked per row)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from jax.sharding import PartitionSpec as P

from repro.core.stamp import (StampConfig, prepare_linear, stamp_fake_quant,
                              token_quantize)
from repro.kernels import ops, ref
from repro.kernels import stamp_matmul as SM
from repro.models import layers as L
from repro.models import lm
from repro.obs import quantstats as QS
from repro.serving import kvcache as KV
from repro.sharding import ShardingPolicy


def rand(shape, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def make_expert_weight(e, k, n, seed=0):
    """Stacked (E, k, n) signed int8 codes + (E, 1, n) scale / shifted zp
    via the production `prepare_linear` (per-output-channel, per-expert)."""
    w = rand((e, k, n), seed=seed, scale=0.05)
    p = prepare_linear(w, bits=8)
    return p.qw, p.sw, p.zw, w


def make_groups(counts, bm, d, extra_tiles=0, seed=0):
    """Row codes grouped by expert (``counts[e]`` rows from a
    ``bm``-aligned offset, as `moe_route_dropless` lays them out) plus
    ``extra_tiles`` empty tail tiles; returns the tile table, the codes
    with their per-row scale / shifted zp, and the live-row mask."""
    rows, expert = [], []
    for e, c in enumerate(counts):
        for t in range(-(-c // bm)):
            rows.append(min(bm, c - t * bm))
            expert.append(e)
    rows += [0] * extra_tiles
    expert += [expert[-1]] * extra_tiles
    rows = jnp.asarray(rows, jnp.int32)
    table = SM.tile_table(rows, jnp.asarray(expert, jnp.int32))
    r = rows.shape[0] * bm
    qx, sx, zx = token_quantize(rand((1, r, d), seed=seed))
    live = (np.arange(r) % bm) < np.repeat(np.asarray(rows), bm)
    return table, qx[0], sx[0], zx[0], live


class TestGroupedKernel:
    """Pallas kernel (interpret mode) vs the pure-jnp oracle."""

    CASES = [
        # counts per expert, block_m, d, f, block_f, empty tail tiles
        ([8, 5, 0, 8], 8, 32, 64, 32, 1),      # empty group + empty tile
        ([16, 3], 8, 32, 64, 64, 0),           # an expert over two tiles
        ([10, 7, 1, 0], 8, 32, 96, 96, 2),     # partial tiles
        ([8, 8], 8, 64, 128, 512, 0),          # block_f clamps to f
    ]

    @pytest.mark.parametrize("counts,bm,d,f,bf,extra", CASES)
    def test_matches_oracle(self, counts, bm, d, f, bf, extra):
        e = len(counts)
        table, qx, sx, zx, live = make_groups(counts, bm, d, extra, seed=1)
        qg, sg, zg, _ = make_expert_weight(e, d, f, seed=2)
        qu, su, zu, _ = make_expert_weight(e, d, f, seed=3)
        qd, sd, zd, _ = make_expert_weight(e, f, d, seed=4)
        args = (table, qx, sx, zx, qg, sg, zg, qu, su, zu, qd, sd, zd)
        y = ops.stamp_quant_ragged_matmul(*args, block_m=bm, block_f=bf,
                                          interpret=True)
        yr = ref.stamp_quant_ragged_matmul_ref(*args, block_m=bm,
                                               block_f=bf)
        assert y.shape == (qx.shape[0], d)
        # the oracle derives the silu-mul requantize codes from an f32
        # einsum while the kernel uses exact int32 GEMMs — .5-boundary
        # code flips bound the gap, not kernel indexing; rows of empty
        # tiles are undefined
        assert rel_err(np.asarray(y)[live], np.asarray(yr)[live]) < 2e-3

    def test_rows_past_count_exactly_zero(self):
        table, qx, sx, zx, live = make_groups([8, 5, 0, 2], 8, 32, seed=5)
        qg, sg, zg, _ = make_expert_weight(4, 32, 64, seed=6)
        qu, su, zu, _ = make_expert_weight(4, 32, 64, seed=7)
        qd, sd, zd, _ = make_expert_weight(4, 64, 32, seed=8)
        y = np.asarray(ops.stamp_quant_ragged_matmul(
            table, qx, sx, zx, qg, sg, zg, qu, su, zu, qd, sd, zd,
            block_m=8, block_f=32, interpret=True))
        assert np.all(y[~live] == 0.0)
        assert np.all(y[live] != 0.0)

    def test_registered_in_contract_checker(self):
        """Satellite: the capture registry proves KC001–KC005 on the
        concrete tile table (incl. an empty tile)."""
        from repro.kernels.specs import KERNEL_EXAMPLES, kernel_spec
        assert "stamp_matmul.grouped" in KERNEL_EXAMPLES
        ex = kernel_spec("stamp_matmul.grouped")
        cap = ex.captures[0]
        assert cap.num_scalar_prefetch == 1
        table = cap.prefetch[0]
        assert 0 in table          # the checker sees an empty tile


class TestFusedMoEParity:
    """`moe_ffn_dropless_fused` (grouped int8 kernel over ragged row
    groups) vs the dense loop `moe_ffn_dropless` running the SAME
    prepared-int8 expert weights (dequantized for the loop) — the gap is
    the token quantize + in-kernel requantize only."""

    def _setup(self, t, d, f, e, held, seed=0):
        x = rand((t, d), seed=seed)
        gate_w = rand((d, e), seed=seed + 1)
        prep, deq = {}, {}
        for name, (k, n, s) in {"g": (d, f, 2), "u": (d, f, 3),
                                "d": (f, d, 4)}.items():
            qw, sw, zw, _ = make_expert_weight(held, k, n, seed=seed + s)
            prep[name] = {"iq": qw, "isw": sw, "izw": zw}
            deq[name] = (qw.astype(jnp.float32) - zw) * sw
        return x, gate_w, prep, deq

    def _pair(self, x, gate_w, prep, deq, k, offset, valid=None):
        valid = jnp.ones(x.shape[:1], bool) if valid is None else valid
        y_ref, c_ref = L.moe_ffn_dropless(x, gate_w, deq["g"], deq["u"],
                                          deq["d"], k, valid, offset=offset)
        y_fused, c_fused = L.moe_ffn_dropless_fused(
            x, gate_w, prep["g"], prep["u"], prep["d"], k, valid,
            offset=offset)
        np.testing.assert_array_equal(np.asarray(c_fused),
                                      np.asarray(c_ref))
        return y_ref, y_fused

    CASES = [
        # t, d, f, e, k, held, offset
        (74, 32, 64, 4, 2, 4, 0),       # odd token count, tail tile
        (64, 32, 64, 4, 2, 2, 1),       # a share: experts 1-2 of 4
        (66, 32, 64, 8, 2, 8, 0),       # many small groups
        (48, 64, 128, 4, 1, 4, 0),      # top-1
    ]

    @pytest.mark.parametrize("t,d,f,e,k,held,offset", CASES)
    def test_fused_matches_reference(self, t, d, f, e, k, held, offset):
        x, gate_w, prep, deq = self._setup(t, d, f, e, held, seed=10)
        y_ref, y_fused = self._pair(x, gate_w, prep, deq, k, offset)
        assert y_fused.shape == y_ref.shape == (t, d)
        assert rel_err(y_fused, y_ref) < 0.06

    def test_pad_tokens_route_nowhere(self):
        """Tokens masked invalid (pads, idle slots) take no expert row:
        exact-zero outputs in BOTH paths, no count, and the real tokens'
        outputs are those of the unmasked call."""
        x, gate_w, prep, deq = self._setup(32, 32, 64, 4, 4, seed=20)
        valid = jnp.arange(32) % 3 != 0
        y_ref, y_fused = self._pair(x, gate_w, prep, deq, 2, 0, valid)
        pad = ~np.asarray(valid)
        assert np.all(np.asarray(y_ref)[pad] == 0.0)
        assert np.all(np.asarray(y_fused)[pad] == 0.0)
        _, c = L.moe_ffn_dropless(x, gate_w, deq["g"], deq["u"], deq["d"],
                                  2, valid, offset=0)
        assert int(c[0]) == 2 * int(valid.sum())
        _, y_all = self._pair(x, gate_w, prep, deq, 2, 0)
        assert rel_err(np.asarray(y_fused)[~pad],
                       np.asarray(y_all)[~pad]) < 1e-6

    def test_num_hi_exceeds_seq(self):
        """The stamped round trip ahead of routing with num_hi >= seq:
        every token re-codes at hi_bits, both paths consume the same hq."""
        x, gate_w, prep, deq = self._setup(24, 32, 64, 4, 4, seed=30)
        st = StampConfig(num_hi_tokens=512)
        hq = stamp_fake_quant(x[None], st, site=None)[0]
        y_ref, y_fused = self._pair(hq, gate_w, prep, deq, 2, 0)
        assert rel_err(y_fused, y_ref) < 0.06

    def test_route_occupancy_is_contiguous_prefix(self):
        """The kernel's scalar-prefetch contract: occupied capacity slots
        of every (group, expert) bucket form a prefix [0, count)."""
        x = rand((3, 16, 32), seed=40)
        gate_w = rand((32, 4), seed=41)
        valid = jnp.ones((3, 16), jnp.float32)
        combine, dispatch, counts = L.moe_route(x, gate_w, 2, 1.0, valid)
        occupied = np.asarray(dispatch).sum(axis=1) > 0      # (b, E, C)
        slot = np.arange(occupied.shape[-1])[None, None, :]
        np.testing.assert_array_equal(
            occupied, slot < np.asarray(counts)[:, :, None])


class TestFusedMoEWiring:
    """End-to-end: fused MoE prefill issues ZERO dense-loop expert einsums
    and exactly one grouped-kernel call per traced MoE layer."""

    CFG = lm.ModelConfig(name="moe-count-test", family="moe", num_layers=2,
                         d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                         vocab_size=128, num_experts=4, experts_per_token=2,
                         moe_group_size=32)

    def test_prefill_zero_reference_expert_einsums(self, monkeypatch):
        from repro.kernels import ops as kops
        params = lm.init_params(jax.random.PRNGKey(0), self.CFG)
        stf = StampConfig(num_hi_tokens=8, execution="fused")
        pf = lm.prepare_fused_weights(params, stf)
        counts = {"grouped": 0}
        real = kops.stamp_quant_ragged_matmul

        def grouped(*a, **k):
            counts["grouped"] += 1
            return real(*a, **k)

        def boom(*a, **k):
            raise AssertionError("dense-loop expert einsums ran")

        monkeypatch.setattr(kops, "stamp_quant_ragged_matmul", grouped)
        monkeypatch.setattr(L, "moe_ffn_dropless", boom)
        monkeypatch.setattr(L, "moe_ffn", boom)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 128, (1, 48)), jnp.int32)
        logits, _ = lm.prefill(params=pf, batch={"tokens": toks},
                               cfg=self.CFG,
                               serve=lm.ServeConfig(
                                   stamp=stf,
                                   kv=KV.KVCacheConfig(quantized=True,
                                                       num_hi=16),
                                   cache_capacity=64))
        assert bool(jnp.isfinite(logits).all())
        # the scanned period traces the layer body once: one grouped call
        assert counts["grouped"] == 1

    def test_prepare_fused_weights_stacks_experts(self):
        params = lm.init_params(jax.random.PRNGKey(0), self.CFG)
        stf = StampConfig(num_hi_tokens=8, execution="fused")
        pf = lm.prepare_fused_weights(params, stf)
        layer = jax.tree.map(lambda a: a, pf["period"][0])
        # stacked (nper, E, din, dout): the whole scanned period prepares
        # in one prepare_linear pass and slices per layer under lax.scan
        for key, (din, dout) in (("we_gate", (64, 128)),
                                 ("we_up", (64, 128)),
                                 ("we_down", (128, 64))):
            w = layer[key]
            assert set(w) == {"iq", "isw", "izw"}
            assert w["iq"].shape == (2, 4, din, dout)
            assert w["iq"].dtype == jnp.int8
            assert w["isw"].shape == (2, 4, 1, dout)

    def test_eligibility_matrix_moe_fused(self):
        stf = StampConfig(num_hi_tokens=8, execution="fused")
        m = lm.fused_site_matrix(self.CFG, stf)
        assert m["moe"]["status"] == "fused"
        assert m["moe"]["kernel"] == "stamp_quant_ragged_matmul"
        assert m["moe"]["reasons"] == []
        # disabled stamp still demotes the cell with a reason (EL001)
        m_off = lm.fused_site_matrix(self.CFG, None)
        assert m_off["moe"]["status"] == "reference"
        assert m_off["moe"]["reasons"] == ["stamp_disabled"]


class TestRouterTelemetry:
    def test_moe_route_records_pseudo_site(self):
        x = rand((2, 16, 32), seed=50)
        gate_w = rand((32, 4), seed=51)
        valid = jnp.ones((2, 16), jnp.float32)
        QS.begin()
        try:
            _, _, counts = L.moe_route(x, gate_w, 2, 0.75, valid)
            raw = QS.end()
        finally:
            if QS.active():
                QS.end()
        assert "moe_router" in raw
        r = raw["moe_router"]
        assert r["expert_tokens"].shape == (4,)
        np.testing.assert_allclose(np.asarray(r["expert_tokens"]).sum(),
                                   np.asarray(counts).sum())
        assert float(r["dropped_tokens"]) >= 0.0
        # summarize passes vector leaves through instead of crashing
        summ = QS.summarize({"moe_router": r})
        assert len(summ["moe_router"]["expert_tokens"]) == 4

    def test_absorb_reduces_stacked_router_stats(self):
        """Scan ride-along: period-stacked router stats sum over the layer
        axis like any quant counter (key-driven reduction)."""
        stacked = {"moe_router": {
            "expert_tokens": jnp.asarray([[1., 2.], [3., 4.]]),
            "dropped_tokens": jnp.asarray([1., 2.]),
            "capacity_slots": jnp.asarray([8., 8.]),
        }}
        QS.begin()
        try:
            QS.absorb(stacked)
            out = QS.end()
        finally:
            if QS.active():
                QS.end()
        np.testing.assert_allclose(
            np.asarray(out["moe_router"]["expert_tokens"]), [4., 6.])
        assert float(out["moe_router"]["dropped_tokens"]) == 3.0

    def test_engine_publishes_router_gauges(self):
        from repro.obs.metrics import MetricsRegistry
        from repro.serving.engine import _EngineBase

        class Stub:
            metrics = MetricsRegistry()

        stub = Stub()
        _EngineBase._absorb_router_stats(stub, {
            "expert_tokens": np.asarray([6.0, 2.0]),
            "dropped_tokens": np.asarray(2.0),
            "capacity_slots": np.asarray(16.0),
        })
        g0 = stub.metrics.gauge("moe_expert_tokens", labels={"expert": "0"})
        assert g0.value == 6.0
        assert stub.metrics.counter("moe_dropped_tokens").value == 2.0
        assert stub.metrics.gauge("moe_capacity_occupancy").value == 0.5
        np.testing.assert_allclose(
            stub.metrics.gauge("moe_drop_rate").value, 0.2)


class TestExpertParallelSharding:
    """Prepared int8 expert buffers shard expert-parallel over 'model'
    through the same suffix-strip rules as the raw weights."""

    POL = ShardingPolicy(mesh=None)

    def test_prepared_expert_codes(self):
        # stacked period leaf: (nper, E, d, f)
        assert self.POL.param_spec("period/we_gate/iq", 4) == \
            P(None, "model", "data", None)
        assert self.POL.param_spec("period/we_down/iq", 4) == \
            P(None, "model", None, "data")

    def test_prepared_expert_scales(self):
        # (nper, E, 1, dout): expert axis stays on 'model'; the scale
        # keeps only the parent's output-dim sharding
        assert self.POL.param_spec("period/we_gate/isw", 4) == \
            P(None, "model", None, None)
        assert self.POL.param_spec("period/we_down/izw", 4) == \
            P(None, "model", None, "data")


class TestChunkAttentionSingleBranch:
    """Satellite regression: the XLA prefill fallback must run ONE
    chunked call per step — no flash variant evaluated alongside and
    discarded by a `jnp.where` (the double-FLOP bug)."""

    def test_no_flash_dispatch_during_paged_prefill(self, monkeypatch):
        from repro.serving.engine import PagedEngineConfig, \
            PagedServingEngine
        calls = {"flash": 0, "chunked": 0}
        real_flash = L.flash_attention
        real_chunked = L.chunked_prefill_attention

        def flash(*a, **k):
            calls["flash"] += 1
            return real_flash(*a, **k)

        def chunked(*a, **k):
            calls["chunked"] += 1
            return real_chunked(*a, **k)

        monkeypatch.setattr(L, "flash_attention", flash)
        monkeypatch.setattr(L, "chunked_prefill_attention", chunked)
        # unique shapes so the engine traces fresh programs in this test
        cfg = lm.ModelConfig(name="attn-branch-test", family="dense",
                             num_layers=2, d_model=96, num_heads=6,
                             num_kv_heads=3, d_ff=160, vocab_size=96)
        params = lm.init_params(jax.random.PRNGKey(7), cfg)
        rng = np.random.default_rng(8)
        for mode in ("unified", "two_call"):
            eng = PagedServingEngine(
                params, cfg,
                lm.ServeConfig(stamp=None,
                               kv=KV.KVCacheConfig(quantized=True,
                                                   num_hi=16)),
                PagedEngineConfig(max_slots=2, prefill_chunk=16,
                                  max_seq=64, block_size=16,
                                  step_mode=mode))
            for n in (30, 17):
                eng.submit(rng.integers(0, 96, n), max_new_tokens=4)
            eng.run()
        assert calls["chunked"] > 0, "prefill never traced chunk attention"
        assert calls["flash"] == 0, \
            "prefill fallback still evaluates the flash branch"
