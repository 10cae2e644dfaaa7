"""Observability-layer tests: metrics registry (bucket semantics +
percentiles vs a numpy oracle, Prometheus exposition), structured events
(legacy tuple compat per kind), step-phase timing on a fake tick clock,
Chrome-trace export schema, quantization-health stats against an fp32
numpy oracle (including deliberately clipped injected scales), and the
engine-level contract: one registry-backed ``stats`` surface on BOTH
engines and ZERO extra device dispatches when telemetry is on."""

import contextlib
import json

import jax
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

import jax.numpy as jnp                                        # noqa: E402

from repro.core import quant as Q                              # noqa: E402
from repro.core.stamp import StampConfig                       # noqa: E402
from repro.models import lm                                    # noqa: E402
from repro.models.config import ModelConfig                    # noqa: E402
from repro.obs import quantstats as QS                         # noqa: E402
from repro.obs.metrics import (LATENCY_BUCKETS, Histogram,     # noqa: E402
                               MetricsRegistry, exponential_buckets)
from repro.obs.trace import Event, StepTimer, export_chrome_trace  # noqa: E402
from repro.serving import kvcache as KV                        # noqa: E402
from repro.serving.engine import (BucketedEngine, EngineConfig,  # noqa: E402
                                  PagedEngineConfig, PagedServingEngine)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_bucket_edges_le_semantics(self):
        h = Histogram((1.0, 2.0, 4.0))
        for v in (0.5, 1.0):      # v <= 1.0 -> bucket 0 (le semantics)
            h.observe(v)
        h.observe(1.5)            # bucket 1
        h.observe(4.0)            # exactly the last edge -> bucket 2
        h.observe(9.0)            # overflow
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        assert h.sum == pytest.approx(0.5 + 1.0 + 1.5 + 4.0 + 9.0)

    def test_percentile_vs_numpy_oracle(self):
        """Dense geometric buckets: the interpolated estimate must land
        within one bucket width of numpy's exact quantile."""
        rng = np.random.default_rng(7)
        xs = rng.lognormal(mean=-3.0, sigma=1.0, size=4000)
        edges = exponential_buckets(1e-4, 1.15, 80)
        h = Histogram(edges)
        for v in xs:
            h.observe(v)
        for q in (0.5, 0.9, 0.99):
            exact = float(np.quantile(xs, q))
            est = h.percentile(q)
            i = int(np.searchsorted(edges, exact))
            lo = edges[i - 1] if i > 0 else 0.0
            hi = edges[min(i, len(edges) - 1)]
            assert lo * 0.999 <= est <= hi * 1.001, \
                f"q={q}: est {est} outside covering bucket [{lo}, {hi}]"

    def test_percentile_edge_cases(self):
        h = Histogram((1.0, 2.0))
        assert h.percentile(0.5) == 0.0          # empty
        h.observe(100.0)                         # overflow only
        assert h.percentile(0.5) == 2.0          # reports last finite edge
        with pytest.raises(ValueError):
            h.percentile(1.5)

    def test_exponential_buckets(self):
        edges = exponential_buckets(0.5, 2.0, 4)
        assert edges == (0.5, 1.0, 2.0, 4.0)
        with pytest.raises(ValueError):
            exponential_buckets(0.0, 2.0, 4)
        with pytest.raises(ValueError):
            exponential_buckets(0.5, 1.0, 4)

    def test_unsorted_edges_rejected(self):
        with pytest.raises(ValueError):
            Histogram((2.0, 1.0))


class TestRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("ops")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_returns_same_child(self):
        reg = MetricsRegistry()
        a = reg.counter("x", labels={"site": "qkv"})
        b = reg.counter("x", labels={"site": "qkv"})
        other = reg.counter("x", labels={"site": "wo"})
        assert a is b and a is not other

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("n")
        with pytest.raises(ValueError):
            reg.gauge("n")

    def test_invalid_name_raises(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("bad name!")

    def test_reset_excludes(self):
        reg = MetricsRegistry()
        reg.counter("recompiles").inc(5)
        reg.counter("steps").inc(9)
        reg.histogram("ttft_s").observe(0.1)
        reg.reset(exclude=("recompiles",))
        assert reg.counter("recompiles").value == 5
        assert reg.counter("steps").value == 0
        assert reg.histogram("ttft_s").count == 0

    def test_snapshot_and_json(self):
        reg = MetricsRegistry(clock=lambda: 123.0)
        reg.counter("steps").inc(2)
        reg.gauge("load", labels={"k": "waiting"}).set(3)
        reg.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
        snap = reg.snapshot()
        assert snap["t"] == 123.0
        assert snap["counters"]["steps"] == 2
        assert snap["gauges"]['load{k="waiting"}'] == 3
        hist = snap["histograms"]["lat"]
        assert hist["edges"] == [1.0, 2.0]
        assert hist["counts"] == [0, 1, 0]
        assert hist["count"] == 1
        assert json.loads(reg.to_json()) == json.loads(reg.to_json())

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("steps", help="engine steps").inc(2)
        h = reg.histogram("lat", buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(1.5)
        h.observe(9.0)
        text = reg.to_prometheus()
        assert "# HELP steps engine steps" in text
        assert "# TYPE steps counter" in text
        assert "steps 2" in text
        # cumulative le buckets + the +Inf bucket equal to count
        assert 'lat_bucket{le="1"} 1' in text
        assert 'lat_bucket{le="2"} 2' in text
        assert 'lat_bucket{le="+Inf"} 3' in text
        assert "lat_sum 11" in text
        assert "lat_count 3" in text


# ---------------------------------------------------------------------------
# events + step timer
# ---------------------------------------------------------------------------

class TestEvent:
    @pytest.mark.parametrize("ev,payload", [
        (Event(3, "prefill_chunk", uid=1, fields={"start": 0, "end": 16}),
         (1, 0, 16)),
        (Event(4, "decode", fields={"uids": (1, 2, 5)}), (1, 2, 5)),
        (Event(5, "demote", fields={"to": "reference"}), "reference"),
        (Event(6, "fault_exhaust"), 6),
        (Event(7, "fail", uid=2, fields={"error": "deadline"}),
         (2, "deadline")),
        (Event(8, "finish", uid=3), 3),
        (Event(9, "admit", uid=4), 4),
    ])
    def test_legacy_payload_shapes(self, ev, payload):
        step, kind, p = ev             # tuple unpacking via __iter__
        assert (step, kind, p) == (ev.step, ev.kind, payload)


class TickClock:
    """Deterministic clock: each read advances by ``tick`` and counts."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += self.tick
        return self.t


class TestStepTimer:
    def test_exact_phase_timing_two_reads_per_phase(self):
        clk = TickClock(tick=1.0)
        reg = MetricsRegistry()
        slices = []
        timer = StepTimer(reg, clk, on_phase=lambda n, t0, d:
                          slices.append((n, t0, d)))
        with timer.phase("plan"):
            pass
        with timer.phase("dispatch"):
            pass
        assert clk.reads == 4                       # exactly 2 per phase
        assert slices == [("plan", 1.0, 1.0), ("dispatch", 3.0, 1.0)]
        h = reg.histogram("step_phase_s", labels={"phase": "plan"})
        assert h.count == 1 and h.sum == pytest.approx(1.0)

    def test_observes_even_on_exception(self):
        reg = MetricsRegistry()
        timer = StepTimer(reg, TickClock())
        with pytest.raises(RuntimeError):
            with timer.phase("post"):
                raise RuntimeError("boom")
        assert reg.histogram("step_phase_s",
                             labels={"phase": "post"}).count == 1

    def test_nested_phases_time_into_their_own_labels(self):
        """A phase inside a phase observes its own label, the outer sum
        still covers it, each phase reads the clock twice inside its span
        ``engine.<name>``, and only the top-level phase reaches the ring."""
        clk = TickClock(tick=1.0)
        reg = MetricsRegistry()
        slices, spans = [], []

        @contextlib.contextmanager
        def annotate(name):
            spans.append(("open", name, clk.reads))
            yield
            spans.append(("close", name, clk.reads))

        timer = StepTimer(reg, clk, annotate=annotate,
                          on_phase=lambda n, t0, d: slices.append((n, t0, d)))
        with timer.phase("dispatch"):
            with timer.phase("upload"):
                pass
            with timer.phase("launch"):
                pass
        assert clk.reads == 6                       # exactly 2 per phase
        sums = {ph: reg.histogram("step_phase_s", labels={"phase": ph}).sum
                for ph in ("dispatch", "upload", "launch")}
        assert sums == {"dispatch": 5.0, "upload": 1.0, "launch": 1.0}
        assert slices == [("dispatch", 1.0, 5.0)]
        assert spans == [("open", "engine.dispatch", 0),
                         ("open", "engine.upload", 1),
                         ("close", "engine.upload", 3),
                         ("open", "engine.launch", 3),
                         ("close", "engine.launch", 5),
                         ("close", "engine.dispatch", 6)]

    def test_depth_recovers_after_an_exception(self):
        """A nested phase that raises leaves the next phase top-level."""
        slices = []
        timer = StepTimer(MetricsRegistry(), TickClock(),
                          on_phase=lambda n, t0, d: slices.append(n))
        with pytest.raises(RuntimeError):
            with timer.phase("dispatch"):
                with timer.phase("launch"):
                    raise RuntimeError("boom")
        with timer.phase("post"):
            pass
        assert slices == ["dispatch", "post"]


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _lifecycle_events():
    """A hand-built ring: one request through submit -> admit -> chunk ->
    first token -> preempt -> admit -> finish, with step-phase slices."""
    return [
        Event(0, "submit", uid=1, t=0.0, fields={"prompt_len": 20}),
        Event(1, "phase", t=0.5, dur=0.2, phase="plan"),
        Event(1, "admit", uid=1, t=1.0),
        Event(1, "prefill_chunk", uid=1, t=1.0, dur=0.5,
              fields={"start": 0, "end": 16}),
        Event(2, "first_token", uid=1, t=2.0),
        Event(3, "preempt", uid=1, t=3.0),
        Event(4, "admit", uid=1, t=4.0),
        Event(4, "resume", uid=1, t=4.0),
        Event(5, "finish", uid=1, t=5.0),
    ]


class TestChromeTrace:
    def test_schema(self):
        doc = export_chrome_trace(_lifecycle_events(), engine="test")
        assert set(doc) == {"traceEvents", "displayTimeUnit", "metadata"}
        assert doc["metadata"]["engine"] == "test"
        for ev in doc["traceEvents"]:
            assert ev["ph"] in ("M", "X", "i")
            assert {"name", "pid", "tid"} <= set(ev)
            if ev["ph"] != "M":
                assert ev["ts"] >= 0
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
            if ev["ph"] == "i":
                assert ev["s"] == "t"
        assert json.loads(json.dumps(doc)) == doc     # JSON-serializable

    def test_lifecycle_spans(self):
        doc = export_chrome_trace(_lifecycle_events())
        spans = [(e["name"], e["ts"], e["dur"]) for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["tid"] == 2]     # uid 1 -> tid 2
        names = [n for n, _, _ in spans]
        # submit->admit WAITING, admit->first_token PREFILLING, then
        # DECODING, preempt puts it back to WAITING, and after the second
        # admit it resumes DECODING until the terminal
        assert names.count("WAITING") == 2
        assert "PREFILLING" in names
        assert names.count("DECODING") == 2
        assert any(n.startswith("prefill[0:16)") for n in names)
        wait = next(s for s in spans if s[0] == "WAITING")
        assert wait[1] == 0 and wait[2] == 1_000_000      # 0 -> 1s, in µs
        instants = [e["name"] for e in doc["traceEvents"] if e["ph"] == "i"]
        assert "first token" in instants
        assert "terminal: finish" in instants
        assert any("preempt" in n for n in instants)

    def test_phase_slices_on_step_thread(self):
        doc = export_chrome_trace(_lifecycle_events())
        phases = [e for e in doc["traceEvents"]
                  if e["ph"] == "X" and e["tid"] == 0]
        assert [p["name"] for p in phases] == ["plan"]
        assert phases[0]["dur"] == 200_000               # 0.2 s in µs

    def test_empty_ring(self):
        doc = export_chrome_trace([])
        assert doc["traceEvents"] == []

    def test_open_request_closed_at_last_timestamp(self):
        doc = export_chrome_trace([
            Event(0, "submit", uid=1, t=0.0),
            Event(1, "admit", uid=1, t=1.0),
            Event(2, "first_token", uid=1, t=2.0),
        ])
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        open_span = [e for e in spans if e["args"].get("open")]
        assert len(open_span) == 1 and open_span[0]["name"] == "DECODING"


# ---------------------------------------------------------------------------
# quant-health stats vs fp32 numpy oracle
# ---------------------------------------------------------------------------

class TestSiteStats:
    def _oracle(self, x, bits, scale, zp):
        n = 2.0 ** bits - 1.0
        q = np.round(x / scale) + zp
        clipped = int(np.sum((q < -0.5) | (q > n + 0.5)))
        qc = np.clip(q, 0.0, n)
        saturated = int(np.sum((qc <= 0.5) | (qc >= n - 0.5)))
        return clipped, saturated

    def test_minmax_scales_never_clip(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 16, 32)), jnp.float32)
        out = QS.site_stats(x, bits=4.0, hi_bits=8)
        assert float(out["clipped"]) == 0.0
        assert float(out["elems"]) == x.size
        assert float(out["tokens"]) == 32
        assert float(out["saturated"]) > 0       # min/max always on rails

    def test_clip_rate_vs_oracle_with_tight_scales(self):
        """Inject deliberately narrow quantizer params so real clipping
        occurs, and check the device counts against a numpy oracle."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(1, 8, 64)).astype(np.float32)
        bits = 4.0
        scale = np.full((1, 8, 1), 0.08, np.float32)   # much too narrow
        zp = np.full((1, 8, 1), 7.0, np.float32)
        clipped, saturated = self._oracle(x, bits, scale, zp)
        assert clipped > 0, "oracle setup must actually clip"
        out = QS.site_stats(jnp.asarray(x), bits, hi_bits=8,
                            scale=jnp.asarray(scale), zp=jnp.asarray(zp))
        assert int(out["clipped"]) == clipped
        assert int(out["saturated"]) == saturated

    def test_hi_token_coverage_with_bits_vector(self):
        x = jnp.ones((2, 8, 16), jnp.float32)
        bits = Q.mixed_precision_bits(8, num_hi=2, hi_bits=8, lo_bits=4)
        out = QS.site_stats(x, bits, hi_bits=8)
        # 2 hi tokens of 8, times 2 batch rows
        assert float(out["hi_tokens"]) == 4.0
        assert float(out["tokens"]) == 16.0
        summ = QS.summarize({"qkv": out})["qkv"]
        assert summ["hi_coverage"] == pytest.approx(0.25)
        assert 0.0 <= summ["clip_rate"] <= 1.0

    def test_collector_scope(self):
        assert not QS.active()
        QS.begin()
        QS.record("qkv", jnp.ones((1, 4, 8)), 4.0, 8)
        QS.record("qkv", jnp.ones((1, 4, 8)), 4.0, 8)
        out = QS.end()
        assert not QS.active()
        assert float(out["qkv"]["tokens"]) == 8.0   # merged, not replaced
        # records outside a scope are dropped, not an error
        QS.record("qkv", jnp.ones((1, 4, 8)), 4.0, 8)
        assert QS.end() == {}


# ---------------------------------------------------------------------------
# engine-level contract
# ---------------------------------------------------------------------------

CFG = ModelConfig(name="obs-test", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128)
QUANT = KV.KVCacheConfig(quantized=True, num_hi=16)
STAMP_SERVE = lm.ServeConfig(stamp=StampConfig(num_hi_tokens=8), kv=QUANT)


@pytest.fixture(scope="module")
def params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(0, CFG.vocab_size, l) for l in (20, 33, 12)]


def _paged_cfg(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("max_seq", 96)
    kw.setdefault("block_size", 16)
    return PagedEngineConfig(**kw)


def _run(engine, prompts, max_new=6):
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    return engine.run()


class TestEngineObservability:
    def test_bucketed_engine_has_registry_surface(self, params, prompts):
        """The lockstep engine publishes the SAME stats/events surface as
        the paged engine — the old hasattr special-casing is dead."""
        eng = BucketedEngine(params, CFG, lm.ServeConfig(stamp=None,
                                                         kv=QUANT),
                             EngineConfig(max_batch=4, bucket=64,
                                          max_seq=96))
        done = _run(eng, prompts)
        st = eng.stats
        assert set(st) == set(eng.STAT_KEYS) | {"reference_fallback_sites"}
        assert st["steps"] > 0 and st["device_dispatches"] > 0
        assert st["finished"] == len(done) and st["preemptions"] == 0
        kinds = {k for _, k, _ in eng.events}
        assert {"submit", "admit", "first_token", "finish",
                "phase"} <= kinds
        assert eng.metrics.histogram("ttft_s").count == len(done)
        assert eng.metrics.histogram("latency_s").count == len(done)
        eng.reset_stats(clear_events=True)
        assert eng.stats["finished"] == 0 and len(eng.events) == 0

    def test_paged_trace_round_trip(self, params, prompts):
        """Engine ring -> export_chrome_trace: every finished request has
        a full WAITING/PREFILLING/DECODING timeline and a terminal."""
        eng = PagedServingEngine(params, CFG,
                                 lm.ServeConfig(stamp=None, kv=QUANT),
                                 _paged_cfg())
        done = _run(eng, prompts)
        doc = export_chrome_trace(eng.events, engine="paged")
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        for r in done:
            tid = r.uid + 1
            names = [e["name"] for e in spans if e["tid"] == tid]
            assert "WAITING" in names and "PREFILLING" in names
            assert "DECODING" in names
        terminals = [e["name"] for e in doc["traceEvents"]
                     if e["ph"] == "i" and e["name"].startswith("terminal")]
        assert len(terminals) == len(done)
        assert {e["name"] for e in spans if e["tid"] == 0} <= \
            {"plan", "dispatch", "post"}

    def test_quant_telemetry_zero_extra_dispatches(self, params, prompts):
        """The telemetry scalars ride in the same device program: token
        output AND dispatch count are identical with telemetry on/off."""
        import dataclasses
        runs = {}
        for on in (False, True):
            serve = dataclasses.replace(STAMP_SERVE, quant_telemetry=on)
            eng = PagedServingEngine(params, CFG, serve, _paged_cfg())
            done = _run(eng, prompts)
            runs[on] = (eng, {r.uid: list(r.out_tokens) for r in done})
        eng_off, toks_off = runs[False]
        eng_on, toks_on = runs[True]
        assert toks_on == toks_off, "telemetry changed the numerics"
        assert eng_on.stats["device_dispatches"] == \
            eng_off.stats["device_dispatches"], \
            "quant telemetry must not add device dispatches"
        snap = eng_on.metrics.snapshot()
        cov = {k: v for k, v in snap["gauges"].items()
               if k.startswith("quant_hi_coverage")}
        assert cov, "no per-site coverage gauges published"
        assert all(0.0 <= v <= 1.0 for v in cov.values())
        clip = {k: v for k, v in snap["gauges"].items()
                if k.startswith("quant_clip_rate")}
        # min-max scales clip nothing by construction
        assert clip and all(v == 0.0 for v in clip.values())
        assert not any(k.startswith("quant_") for k in
                       eng_off.metrics.snapshot()["gauges"])

    def test_clip_alert_fires_below_threshold(self, params, prompts):
        """A negative threshold guarantees every step trips the alert —
        exercises the counter + event path without pathological inputs."""
        import dataclasses
        serve = dataclasses.replace(STAMP_SERVE, quant_telemetry=True)
        eng = PagedServingEngine(params, CFG, serve,
                                 _paged_cfg(clip_alert_threshold=-1.0))
        _run(eng, prompts)
        snap = eng.metrics.snapshot()
        alerts = {k: v for k, v in snap["counters"].items()
                  if k.startswith("quant_clip_alerts")}
        assert alerts and all(v > 0 for v in alerts.values())
        assert any(k == "quant_clip_alert" for _, k, _ in eng.events)

    def test_scheduler_load_gauges(self, params, prompts):
        eng = PagedServingEngine(params, CFG,
                                 lm.ServeConfig(stamp=None, kv=QUANT),
                                 _paged_cfg())
        _run(eng, prompts)
        snap = eng.metrics.snapshot()
        for name in ("sched_waiting", "sched_active", "sched_free_slots",
                     "sched_free_hi_pages", "sched_free_lo_pages"):
            assert name in snap["gauges"]
        # drained engine: nothing waiting or active
        assert snap["gauges"]["sched_waiting"] == 0
        assert snap["gauges"]["sched_active"] == 0

    def test_occupancy_gauges_computed_when_read(self, params, prompts,
                                                 monkeypatch):
        """The ``sched_*`` and prefix-cache gauges cost no step: nothing
        walks the allocator while the engine runs, and every read of the
        registry shows the state at that moment."""
        eng = PagedServingEngine(params, CFG,
                                 lm.ServeConfig(stamp=None, kv=QUANT),
                                 _paged_cfg())
        walks = []
        cache_stats = eng.sched.alloc.cache_stats
        monkeypatch.setattr(eng.sched.alloc, "cache_stats",
                            lambda: walks.append(1) or cache_stats())
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        assert eng.metrics.snapshot()["gauges"]["sched_waiting"] \
            == len(prompts)
        n = len(walks)
        eng.step()
        assert len(walks) == n, "a step walked the allocator"
        snap = eng.metrics.snapshot()["gauges"]
        assert len(walks) == n + 1
        assert {k: snap[f"sched_{k}"] for k in eng.sched.load()} \
            == eng.sched.load()
        assert f"sched_active {eng.sched.load()['active']}" \
            in eng.metrics.to_prometheus()
        eng.run()
        assert len(walks) == n + 2       # the exposition; run() reads none
        assert eng.stats["prefix_cached_pages"] == \
            eng.sched.alloc.cache_stats()["cached_pages"]

    def test_obs_clock_isolated_from_engine_clock(self, params, prompts):
        """Deadline semantics live on the engine clock; histograms and
        event timestamps on the obs clock.  An injected obs tick-clock
        must not perturb tokens or engine-clock latencies."""
        obs = TickClock(tick=0.25)
        eng = PagedServingEngine(params, CFG,
                                 lm.ServeConfig(stamp=None, kv=QUANT),
                                 _paged_cfg(), obs_clock=obs)
        done = _run(eng, prompts)
        assert obs.reads > 0
        assert eng.metrics.histogram("ttft_s").count == len(done)
        ts = [e.t for e in eng.events]
        assert ts == sorted(ts), "obs timestamps must be monotonic"
        # engine-clock latencies are real perf_counter intervals, not the
        # virtual obs ticks
        assert all(0.0 <= r.latency_s < 60.0 for r in done)
