"""Serving engines with STaMP quantization: lockstep bucketed batching and
continuous batching over the block-paged mixed-precision cache.

Two engines share one request API (`submit` → `run` → completed
`Request`s with tokens + latency/TTFT):

* :class:`BucketedEngine` (alias ``ServingEngine``) — the slot-batching
  design: requests are grouped into fixed-size batches, prompts right-padded
  to the bucket length, prefill is one jit'd call and decode runs lockstep
  with **per-slot positions** (each request decodes at its own length, so
  padding never leaks into the math and the whole batch waits only on the
  longest *generation*, not on padded prompt positions).
* :class:`PagedServingEngine` — continuous batching: a
  `serving/scheduler.py` state machine admits/evicts requests every step
  against the block-paged cache (`serving/paged_kvcache.py`).  Prompts
  prefill in fixed-size chunks interleaved with the running decode batch
  (no bucket padding), requests join/leave the decode slot array at step
  granularity, and block exhaustion preempts the latest arrival by swapping
  its pages to host memory — resume is bit-identical, no recompute.

Both engines share the model entry points in `models/lm.py`; with
``stamp=None`` (or a prompt that fits one prefill chunk) they produce
token-identical greedy output, which the parity tests pin.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ModelConfig
from repro.obs import quantstats as QS
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Event, StepTimer
from repro.serving import paged_kvcache as PKV
from repro.serving.faults import FaultPlan, corrupt_swapped
from repro.serving.scheduler import (CANCELLED, PREFILLING, REJECTED, RUNNING,
                                     PrefillWork, SchedRequest, Scheduler,
                                     SchedulerConfig)


def _transform_window(stamp, chunk: int) -> int:
    """Transform-aware chunk-boundary window: a Haar DWT / WHT at L levels
    mixes tokens in blocks of 2^L, so non-final chunk ends align to that
    multiple (scheduler satellite).  Window > chunk cannot be aligned — the
    per-chunk transform spans the whole chunk, so there is no intra-chunk
    window to preserve (the documented fallback: no alignment)."""
    if stamp is None or not stamp.enabled or stamp.seq_transform == "none":
        return 1
    w = 2 ** stamp.resolved_levels(chunk)
    return w if w <= chunk else 1


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (len,) int32
    max_new_tokens: int = 32
    out_tokens: Optional[np.ndarray] = None
    latency_s: float = 0.0
    ttft_s: float = 0.0           # submit → first token
    preemptions: int = 0
    submit_t: float = 0.0
    obs_submit_t: float = 0.0     # observability-clock submit stamp (the
    # engine clock owns deadlines/TTFT; histograms/events use this one)
    # lifecycle: "queued" until the request reaches exactly one terminal
    # state — "finished" | "failed" | "cancelled" | "rejected".  `error`
    # says why for the failed/rejected ones.  `out_tokens` carries the
    # partial generation for failed/cancelled requests (possibly empty).
    status: str = "queued"
    error: Optional[str] = None
    deadline_s: Optional[float] = None       # total submit→finish budget
    ttft_deadline_s: Optional[float] = None  # submit→first-token budget


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    bucket: int = 128             # prompt bucket length (pad to this)
    max_seq: int = 256            # cache capacity
    eos_id: int = -1              # <0 disables EOS stopping
    max_events: int = 4096        # event-trace ring buffer (0 = unbounded)
    # quant-telemetry clip rate above which a quant_clip_alert event is
    # emitted for the offending STaMP site (ServeConfig.quant_telemetry)
    clip_alert_threshold: float = 0.05


@dataclasses.dataclass
class PagedEngineConfig:
    max_slots: int = 8            # decode batch width (static jit shape)
    prefill_chunk: int = 128      # tokens per prefill chunk row
    max_seq: int = 256            # per-request length cap (table width)
    block_size: int = 16          # tokens per cache page
    num_hi_blocks: Optional[int] = None   # pool sizes; None = enough for
    num_lo_blocks: Optional[int] = None   # max_slots full-length requests
    eos_id: int = -1
    max_prefills: int = 2         # chunk spans per unified step (≥ 1)
    step_mode: str = "unified"    # "unified" (one program per step) |
    # "two_call" (the PR-3 prefill-then-decode pair, kept for parity tests
    # and A/B benchmarking — schedules exactly like the old engine)
    max_events: int = 4096        # event-trace ring buffer (0 = unbounded)
    # -- robustness / admission control --------------------------------
    max_waiting: Optional[int] = None  # bounded waiting queue (None = ∞)
    shed_policy: str = "reject_newest"  # "reject_newest" | "shed_oldest"
    # consecutive zero-span steps before the watchdog fails the request at
    # the head of the line (livelock backstop — 0 disables)
    watchdog_steps: int = 8
    # on a NaN/Inf quarantine under a fused STaMP config, demote the whole
    # engine to reference execution (original bf16 weights, no integer
    # kernels) — the slow-but-safe escape hatch for saturating activations
    demote_on_nan: bool = True
    # forwarded to SchedulerConfig.preempt_watermark (< 1.0 enables)
    preempt_watermark: float = 1.0
    # hash-addressed prefix reuse across requests (ref-counted page
    # sharing + copy-on-write; see BlockAllocator).  Cache-on output is
    # bit-identical to cache-off — matches restart prefill on the same
    # chunk boundaries the cache-off engine would use — so it defaults
    # on.  Auto-disabled on stacks with Mamba layers (recurrent state
    # cannot skip past cached tokens) and pure-SSM stacks (no pages).
    prefix_caching: bool = True
    # quant-telemetry clip rate above which a quant_clip_alert event is
    # emitted for the offending STaMP site (ServeConfig.quant_telemetry)
    clip_alert_threshold: float = 0.05


class _EngineBase:
    """Shared request plumbing: fused-weight preparation + submit queue +
    the observability surface both engines expose identically
    (``metrics`` registry, ``stats`` view, ``events`` ring of typed
    :class:`Event` records, step-phase timer).

    ``clock`` is the engine's *semantic* time source (default
    ``time.perf_counter``): deadlines, `Request.ttft_s`/`latency_s`.
    Injectable so deadline tests and the degraded-mode bench advance time
    deterministically instead of sleeping.  ``obs_clock`` is a SEPARATE
    source for event timestamps and phase/latency histograms — adding
    observability must never change how often the semantic clock is read
    (an injected tick-clock test would otherwise measure different
    deadlines with telemetry on).  Event appends read no clock at all:
    they reuse ``_obs_now``, cached at tick points (submit, step-phase
    boundaries)."""

    # every legacy ``stats`` key, now a registry counter; the dict-shaped
    # ``stats`` property renders exactly these
    STAT_KEYS = ("steps", "decode_tokens", "prefill_chunks", "preemptions",
                 "device_dispatches", "recompiles", "swap_bytes",
                 "finished", "failed", "cancelled", "rejected", "shed",
                 "deadline_misses", "nan_quarantines", "demotions",
                 "watchdog_trips", "stalled_steps", "swap_corruptions",
                 "prefix_cache_queries", "prefix_cache_hits",
                 "prefix_tokens_reused", "cow_copies",
                 "attn_pages_walked", "attn_pages_reserved",
                 # dropless MoE, summed over layer calls: token × choice
                 # pairs routed over every expert, those landing on this
                 # chip's experts, held experts with rows, rows lost (0)
                 "moe_rows_routed", "moe_rows_held", "moe_expert_groups",
                 "moe_rows_dropped")

    def __init__(self, params, cfg: ModelConfig, serve: lm.ServeConfig,
                 clock: Optional[Callable[[], float]] = None,
                 obs_clock: Optional[Callable[[], float]] = None):
        self._clock = clock if clock is not None else time.perf_counter
        self._obs_clock = obs_clock if obs_clock is not None \
            else time.perf_counter
        self._obs_now = 0.0
        self._step_i = 0
        self.metrics = MetricsRegistry()
        for k in self.STAT_KEYS:
            self.metrics.counter(k, help=f"engine {k.replace('_', ' ')}")
        # host spans on the profiler's clock (``engine.<phase>``): one
        # TraceMe each, and nothing is recorded unless a trace is running
        self._annotate = jax.profiler.TraceAnnotation
        self._timer = StepTimer(self.metrics, self._tick,
                                on_phase=self._on_phase,
                                annotate=self._annotate)
        self.events: collections.deque = collections.deque()
        self.metrics.on_read(self._refresh_derived_gauges)
        # the pre-`prepare_fused_weights` weights: fused preparation merges
        # wq/wk/wv into one int8 wqkv (destructively, per site), so demoting
        # a misbehaving engine back to reference execution needs this copy
        self._raw_params = params
        if serve.stamp is not None and serve.stamp.enabled and \
                serve.stamp.execution == "fused":
            # hoist every fused site's weights into cached int8 buffers once
            # (lm.FUSED_SITES: merged QKV+bias, attention out-proj, gate/up
            # pairs, MLP down, mamba in/out); prefill then runs the integer
            # kernels per STaMP linear — the gate/up pair through ONE
            # dual-output call — and decode consumes the same buffers
            # through the single-token integer kernel
            # (kernels/decode_matmul.py) instead of re-dequantizing them to
            # bf16 every step.
            params = lm.prepare_fused_weights(params, serve.stamp)
            serve = dataclasses.replace(serve, fused_decode_matmul=True)
        self.params = params
        self.cfg = cfg
        self.serve = serve
        self._uid = 0
        self._refresh_eligibility()

    def _refresh_eligibility(self) -> None:
        """Recompute the per-site fused/reference matrix for the *current*
        serve config (at construction, and again after a fused → reference
        demotion) and publish the ``reference_fallback_sites`` gauge so a
        silent fall-off-the-fused-path shows up on the metrics surface, not
        just in step latency."""
        self.eligibility = lm.fused_site_matrix(self.cfg, self.serve.stamp)
        n_ref = sum(1 for c in self.eligibility.values()
                    if c["status"] == "reference")
        self.metrics.gauge(
            "reference_fallback_sites",
            help="linear sites running the reference (non-fused) path"
        ).set(n_ref)

    # -- observability core ---------------------------------------------
    def _init_events(self, max_events: int) -> None:
        """Size the event ring: unbounded growth over a long serving run
        is a memory leak, so the trace keeps the newest ``max_events``."""
        self.events = collections.deque(
            maxlen=max_events if max_events > 0 else None)

    def _tick(self) -> float:
        """Advance + cache the observability clock.  Everything between
        two ticks (event appends above all) shares the cached stamp, so
        instrumenting a new event never costs a clock read."""
        self._obs_now = self._obs_clock()
        return self._obs_now

    def _event(self, kind: str, uid: Optional[int] = None,
               dur: Optional[float] = None, phase: Optional[str] = None,
               **fields) -> None:
        self.events.append(Event(step=self._step_i, kind=kind, uid=uid,
                                 t=self._obs_now, dur=dur, phase=phase,
                                 fields=fields))

    def _on_phase(self, name: str, t0: float, dur: float) -> None:
        self.events.append(Event(step=self._step_i, kind="phase",
                                 t=t0, dur=dur, phase=name))

    def _inc(self, stat: str, n: int = 1) -> None:
        self.metrics.counter(stat).inc(n)

    @property
    def stats(self) -> Dict[str, int]:
        """The legacy dict view over the registry counters (read-only
        snapshot — mutate through the registry / ``reset_stats``), plus
        the ``reference_fallback_sites`` eligibility gauge."""
        out = {k: int(self.metrics.counter(k).value)
               for k in self.STAT_KEYS}
        out["reference_fallback_sites"] = int(
            self.metrics.gauge("reference_fallback_sites").value)
        return out

    def reset_stats(self, keep: tuple = ("recompiles",),
                    clear_events: bool = False) -> None:
        """Zero every metric except ``keep`` (default: the cumulative
        compile counter, which warmup legitimately owns), optionally
        clearing the event ring — the benchmark warmup/measure boundary
        for BOTH engines."""
        self.metrics.reset(exclude=keep)
        self._refresh_eligibility()   # reset() zeroes gauges; re-publish
        if clear_events:
            self.events.clear()

    def _refresh_derived_gauges(self) -> None:
        """Hook for gauges derived from live engine state, run whenever
        the registry is read (``metrics.snapshot``/``to_prometheus``,
        ``stats``) rather than on every step: a read always shows the
        state as it is, and a ``metrics.reset`` cannot zero what the state
        still says.  The paged engine publishes its scheduler occupancy
        and prefix-cache gauges here; the base has none."""

    def _observe_latency(self, name: str, seconds: float) -> None:
        self.metrics.histogram(name, help=f"request {name}").observe(
            max(seconds, 0.0))

    def _absorb_telemetry(self, raw) -> None:
        """Fold one step's quant-telemetry site dict into the registry:
        monotonic counters for the raw counts, gauges for the per-step
        rates, and a ``quant_clip_alert`` event for any site whose clip
        rate crosses the config threshold."""
        if not raw:
            return
        raw = dict(raw)
        router = raw.pop("moe_router", None)
        if router is not None:
            self._absorb_router_stats(router)
        summ = QS.summarize(raw)
        thresh = getattr(self.ecfg, "clip_alert_threshold", 0.05)
        for site, s in summ.items():
            lbl = {"site": site}
            for key in ("clipped", "saturated", "elems", "hi_tokens",
                        "tokens"):
                self.metrics.counter(
                    f"quant_{key}_total", labels=lbl,
                    help=f"quant telemetry: cumulative {key}").inc(s[key])
            for key in ("clip_rate", "sat_rate", "hi_coverage",
                        "scale_log2_range"):
                self.metrics.gauge(
                    f"quant_{key}", labels=lbl,
                    help=f"quant telemetry: last-step {key}").set(s[key])
            if s["clip_rate"] > thresh:
                self.metrics.counter(
                    "quant_clip_alerts", labels=lbl,
                    help="clip-rate threshold crossings").inc()
                self._event("quant_clip_alert", site=site,
                            clip_rate=s["clip_rate"], threshold=thresh)

    def _absorb_router_stats(self, router: dict) -> None:
        """Publish the MoE router's load counters (recorded by the router
        under the ``moe_router`` pseudo-site): per-expert load-balance
        gauges, the cumulative dropped-token counter, and the step's
        occupancy of the expert rows computed / drop rate."""
        expert_tokens = np.asarray(router.get("expert_tokens", []),
                                   np.float64).reshape(-1)
        dropped = float(np.asarray(router.get("dropped_tokens", 0.0)))
        slots = float(np.asarray(router.get("capacity_slots", 0.0)))
        for i, n in enumerate(expert_tokens):
            self.metrics.gauge(
                "moe_expert_tokens", labels={"expert": str(i)},
                help="MoE router: tokens dispatched to this expert "
                     "(last step, summed over layers)").set(float(n))
        self.metrics.counter(
            "moe_dropped_tokens",
            help="MoE router: cumulative dropped tokens").inc(
            dropped)
        routed = float(expert_tokens.sum())
        self.metrics.gauge(
            "moe_capacity_occupancy",
            help="MoE router: routed rows / row slots (last step)"
        ).set(routed / slots if slots > 0 else 0.0)
        total = routed + dropped
        self.metrics.gauge(
            "moe_drop_rate",
            help="MoE router: dropped / (kept + dropped) (last step)"
        ).set(dropped / total if total > 0 else 0.0)

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               deadline_s: Optional[float] = None,
               ttft_deadline_s: Optional[float] = None) -> int:
        """Queue one request; returns its uid.

        Malformed inputs fail fast HERE with an actionable ValueError —
        an empty prompt, a non-positive token budget, or a prompt the
        engine's tables cannot hold would otherwise surface as an opaque
        kernel shape error (or silent truncation) steps later.  Deadlines
        are budgets in clock seconds from this call; the paged engine
        fails the request at the first planning step past the budget.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt: a request needs at least one "
                             "prompt token")
        if max_new_tokens <= 0:
            raise ValueError(f"max_new_tokens must be positive, got "
                             f"{max_new_tokens}")
        limit = self._max_prompt_len()
        if prompt.size > limit:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the engine's limit of "
                f"{limit} tokens (raise max_seq, or chunk the prompt)")
        self._uid += 1
        # perf_counter, not time.time: TTFT / latency are *intervals*, and
        # wall-clock steps (NTP slew) would skew the bench percentiles
        req = Request(self._uid, prompt, max_new_tokens,
                      submit_t=self._clock(), obs_submit_t=self._tick(),
                      deadline_s=deadline_s,
                      ttft_deadline_s=ttft_deadline_s)
        self._event("submit", uid=req.uid, prompt_len=int(prompt.size))
        self._enqueue(req)
        return self._uid

    def _max_prompt_len(self) -> int:
        raise NotImplementedError

    def _enqueue(self, req: Request) -> None:
        raise NotImplementedError


class BucketedEngine(_EngineBase):
    """Lockstep slot-batching (the pre-paging design, kept as the simple
    baseline, the numerics oracle, and the only engine covering enc-dec
    cross-attention caches)."""

    def __init__(self, params, cfg: ModelConfig, serve: lm.ServeConfig,
                 ecfg: Optional[EngineConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 obs_clock: Optional[Callable[[], float]] = None):
        super().__init__(params, cfg, serve, clock=clock,
                         obs_clock=obs_clock)
        # NOTE: default constructed per instance — a dataclass default
        # instance in the signature would be shared across engines (mutable
        # default), letting one engine's config edits leak into another.
        self.ecfg = ecfg if ecfg is not None else EngineConfig()
        self._init_events(self.ecfg.max_events)
        self.queue: List[Request] = []
        serve = dataclasses.replace(self.serve,
                                    cache_capacity=self.ecfg.max_seq)
        self.serve = serve
        self._collect = lm._collect_telemetry(serve)
        cfgm = self.cfg
        self._prefill = jax.jit(
            lambda p, b, lp: lm.prefill(p, b, cfgm, serve, last_pos=lp))
        self._decode = jax.jit(
            lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfgm, serve))

    def _max_prompt_len(self) -> int:
        # the bucket is the prompt capacity; one position must stay free
        # for the first generated token's K/V write
        return min(self.ecfg.bucket, self.ecfg.max_seq - 1)

    def _enqueue(self, req: Request) -> None:
        self.queue.append(req)

    # ------------------------------------------------------------------
    def run(self) -> List[Request]:
        """Drain the queue; returns completed requests."""
        done: List[Request] = []
        while self.queue:
            batch = self.queue[: self.ecfg.max_batch]
            self.queue = self.queue[self.ecfg.max_batch:]
            done.extend(self._run_batch(batch))
        return done

    def _run_batch(self, reqs: List[Request]) -> List[Request]:
        t0 = self._clock()
        b = len(reqs)
        bucket = self.ecfg.bucket
        self._step_i += 1
        self._inc("steps")
        with self._timer.phase("plan"):
            prompts = np.zeros((b, bucket), np.int32)
            lens = np.zeros((b,), np.int32)
            for i, r in enumerate(reqs):
                p = r.prompt[-bucket:]
                prompts[i, : len(p)] = p          # right-pad
                lens[i] = len(p)
            for r in reqs:
                self._event("admit", uid=r.uid)
                self._observe_latency("queue_wait_s",
                                      self._obs_now - r.obs_submit_t)
        # Right-padding + per-slot decode positions: pad tokens sit AFTER
        # every prompt position, so causal attention never sees them, the
        # next-token logits are read at each row's true last token, and the
        # first generated token overwrites the pad K/V at position len —
        # the output is identical to serving the request unpadded (and to
        # the paged engine's chunked prefill of the same prompt).
        with self._timer.phase("dispatch"):
            out = self._prefill(self.params,
                                {"tokens": jnp.asarray(prompts)},
                                jnp.asarray(lens - 1))
            if self._collect:
                logits, cache, telem = out
            else:
                logits, cache = out
                telem = None
            self._inc("device_dispatches")
            self._inc("prefill_chunks", b)
            max_new = max(r.max_new_tokens for r in reqs)
            max_new = min(max_new, self.ecfg.max_seq - int(lens.max()))
            outs = np.zeros((b, max_new), np.int32)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # force the async-dispatched prefill before timestamping, so
            # TTFT measures execution (as the paged engine's np.argmax
            # does)
            jax.block_until_ready(tok)
        if telem is not None:
            self._absorb_telemetry(telem)
        t_first = self._clock()
        for r in reqs:
            r.ttft_s = t_first - r.submit_t
            self._event("first_token", uid=r.uid)
            self._observe_latency("ttft_s", self._obs_now - r.obs_submit_t)
        alive = np.ones(b, bool)
        for step in range(max_new):
            outs[:, step] = np.where(alive, np.asarray(tok), 0)
            if self.ecfg.eos_id >= 0:
                alive &= outs[:, step] != self.ecfg.eos_id
                if not alive.any():
                    outs = outs[:, : step + 1]
                    break
            with self._timer.phase("dispatch"):
                self._step_i += 1
                self._inc("steps")
                logits, cache = self._decode(self.params, cache, tok,
                                             jnp.asarray(lens + step))
                self._inc("device_dispatches")
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self._inc("decode_tokens", int(alive.sum()))
        dt = self._clock() - t0
        self._tick()
        for i, r in enumerate(reqs):
            r.out_tokens = outs[i][: r.max_new_tokens]
            r.latency_s = dt
            r.status = "finished"
            self._inc("finished")
            self._event("finish", uid=r.uid)
            self._observe_latency("latency_s",
                                  self._obs_now - r.obs_submit_t)
        return reqs


# backward-compatible name: the bucketed engine is the original design
ServingEngine = BucketedEngine


class PagedServingEngine(_EngineBase):
    """Continuous batching over the block-paged mixed-precision cache.

    Each engine step the scheduler admits waiting requests into free slots
    and reserves pages (preempting later arrivals on exhaustion), then the
    whole step's work — up to ``max_prefills`` prefill chunks AND the
    decode slot array — runs as **one ragged batched forward**
    (`lm.paged_unified_step`): every step dispatches exactly one device
    program and streams the weights once, where the two-call design paid
    two dispatches and two cold weight passes on every mixed step while
    decode slots idled during prefill.  Shapes are bucketed on the number
    of chunk rows (0, 1, 2, 4, … up to ``max_prefills``), so the compile
    count per engine lifetime is fixed (``stats["recompiles"]`` /
    :meth:`compile_count`; the first/continuation-chunk distinction is a
    traced mask, not a shape).  ``step_mode="two_call"`` keeps the PR-3
    prefill-then-decode pair — scheduling-identical (one chunk per step,
    no boundary alignment) — as the parity oracle and A/B baseline.
    ``events`` records the admission / join / leave / preemption trace in
    a ring buffer capped at ``max_events``.

    **Hybrid and pure-SSM stacks are first-class**: Mamba layers keep
    their recurrent state in a slot-dense pool next to the paged K/V
    (fixed bytes per slot — `stats` surface it via the scheduler's
    ``state_bytes_per_slot``), prefill chunks carry conv/SSM state across
    chunk boundaries through the request's slot row, decode advances the
    recurrence with inactive slots masked, and preemption swaps the slot
    state to host together with the victim's pages (bit-identical
    resume).  A stack with no attention layers skips page reservation
    entirely — slots are then the only capacity dimension.  Enc-dec
    stacks still need :class:`BucketedEngine`.
    """

    def __init__(self, params, cfg: ModelConfig, serve: lm.ServeConfig,
                 ecfg: Optional[PagedEngineConfig] = None,
                 fault: Optional[FaultPlan] = None,
                 clock: Optional[Callable[[], float]] = None,
                 obs_clock: Optional[Callable[[], float]] = None):
        super().__init__(params, cfg, serve, clock=clock,
                         obs_clock=obs_clock)
        self.ecfg = ecfg if ecfg is not None else PagedEngineConfig()
        e = self.ecfg
        if e.shed_policy not in ("reject_newest", "shed_oldest"):
            raise ValueError(f"unknown shed_policy {e.shed_policy!r}")
        self.fault = fault
        quant = self.serve.kv
        num_hi = quant.num_hi if quant.quantized else 0
        if quant.quantized and num_hi % e.block_size:
            raise ValueError("num_hi must be a multiple of block_size")
        hi_per_seq = num_hi // e.block_size if quant.quantized else 0
        lo_per_seq = -(-(e.max_seq - num_hi) // e.block_size)
        n_hi = e.num_hi_blocks if e.num_hi_blocks is not None \
            else e.max_slots * hi_per_seq + 1
        n_lo = e.num_lo_blocks if e.num_lo_blocks is not None \
            else e.max_slots * lo_per_seq + 1
        self.pcfg = PKV.PagedCacheConfig(
            block_size=e.block_size, num_lo_blocks=n_lo,
            num_hi_blocks=max(n_hi, 1), max_blocks_per_seq=lo_per_seq,
            quant=quant)
        self.serve = dataclasses.replace(self.serve, paged=self.pcfg,
                                         cache_capacity=None)
        # stack composition decides the state families: attention layers
        # read/write the page pools, mamba layers the slot-dense SSM pool
        # (fixed-size per slot, no paging — its null slot is row max_slots).
        # Enc-dec stacks are the one remaining gap (init_paged_cache raises
        # the actionable NotImplementedError before any device allocation).
        pro, period, _ = cfg.layer_plan()
        specs = list(period) + list(pro)
        self._has_attn = any(s.mixer == "attn" for s in specs)
        self._has_mamba = any(s.mixer == "mamba" for s in specs)
        self.pools = lm.init_paged_cache(cfg, self.pcfg,
                                         num_slots=e.max_slots)
        if e.step_mode not in ("unified", "two_call"):
            raise ValueError(f"unknown step_mode {e.step_mode!r}")
        unified = e.step_mode == "unified"
        # prefix reuse skips prefill compute for cached tokens, which a
        # Mamba layer cannot (its recurrent state lives outside the page
        # pools and must advance through every token); pure-SSM stacks
        # have no pages to share at all
        self._prefix_on = bool(e.prefix_caching and self._has_attn
                               and not self._has_mamba)
        self.sched = Scheduler(
            SchedulerConfig(
                max_slots=e.max_slots, prefill_chunk=e.prefill_chunk,
                max_prefills=max(e.max_prefills, 1) if unified else 1,
                transform_window=_transform_window(
                    self.serve.stamp, e.prefill_chunk) if unified else 1,
                state_bytes_per_slot=PKV.ssm_state_bytes_per_slot(
                    self.pools),
                needs_kv_pages=self._has_attn,
                preempt_watermark=e.preempt_watermark,
                prefix_caching=self._prefix_on),
            self.pcfg, swap_out=self._swap_out, swap_in=self._swap_in,
            cow=self._cow_copy, on_prefix=self._on_prefix_lookup)
        if fault is not None:
            # the allocator consults the plan on every probe: injected
            # exhaustion flows through the REAL preemption/degradation
            # paths, not a mock
            self.sched.alloc.fault = fault.exhausted
        self._requests: Dict[int, Request] = {}
        self._init_events(e.max_events)
        self._stall = 0              # consecutive zero-span steps
        self._swap_failed: List[tuple] = []   # (sreq, error) from _swap_in
        self._terminal_done: List[Request] = []  # rejected/cancelled/failed
        self._demoted = False
        # shape buckets for the chunk-row count: 0 (all-decode), powers of
        # two, and max_prefills — the full set of compiled variants
        mp = max(e.max_prefills, 1) if unified else 1
        buckets = {0, mp}
        b = 1
        while b < mp:
            buckets.add(b)
            b *= 2
        self._npf_buckets = sorted(buckets)
        # chunk-row bucket -> abstract arguments of its compiled step
        self._compiled_keys: dict = {}
        self._build_step_fns()

    # -- prefix caching -------------------------------------------------
    def _on_prefix_lookup(self, sreq: SchedRequest, match) -> None:
        """Scheduler callback on every fresh-admission cache lookup."""
        self._inc("prefix_cache_queries")
        if match is None:
            return
        self._inc("prefix_cache_hits")
        self._inc("prefix_tokens_reused", match.matched)
        self._event("prefix_hit", uid=sreq.uid, matched=match.matched,
                    pages=len(match.hi_pages) + len(match.lo_pages))

    def _cow_copy(self, sreq: SchedRequest, pool: str, src: int,
                  dst: int) -> None:
        """Scheduler callback: device-copy one page before the request's
        first divergent write lands in it (partial-page prefix match)."""
        self.pools = PKV.copy_page(self.pools, pool, src, dst)
        self._inc("cow_copies")
        self._event("cow", uid=sreq.uid, pool=pool, src=src, dst=dst)

    def _refresh_derived_gauges(self) -> None:
        """Publish the scheduler occupancy gauges (``sched_*``) and the
        prefix-cache gauges from LIVE scheduler and allocator state (and
        the hit-rate from the counters), at read time: ``cache_stats()``
        walks every page's refs, which no step should pay for.  Like
        ``reference_fallback_sites`` these are recomputed — never carried —
        so ``reset_stats`` and a fused → reference demotion cannot zero
        what the allocator still holds."""
        for name, v in self.sched.load().items():
            self.metrics.gauge(f"sched_{name}",
                               help=f"scheduler {name}").set(v)
        cs = self.sched.alloc.cache_stats()
        q = self.metrics.counter("prefix_cache_queries").value
        h = self.metrics.counter("prefix_cache_hits").value
        self.metrics.gauge(
            "prefix_cache_hit_rate",
            help="prefix cache: hits / lookups").set(h / q if q else 0.0)
        self.metrics.gauge(
            "kv_pages_shared",
            help="pages currently referenced by 2+ requests").set(
            cs["kv_pages_shared"])
        self.metrics.gauge(
            "sink_pages_pinned",
            help="hi-precision (int8 sink) pages cached AND referenced — "
                 "the mixed-precision cost a shared prefix pins for every "
                 "child").set(cs["sink_pages_pinned"])
        self.metrics.gauge(
            "prefix_cached_pages",
            help="pages registered in the prefix cache").set(
            cs["cached_pages"])

    @property
    def stats(self) -> Dict[str, int]:
        self._refresh_derived_gauges()
        out = _EngineBase.stats.fget(self)
        g = self.metrics.gauge
        out["prefix_cache_hit_rate"] = float(
            g("prefix_cache_hit_rate").value)
        out["kv_pages_shared"] = int(g("kv_pages_shared").value)
        out["sink_pages_pinned"] = int(g("sink_pages_pinned").value)
        out["prefix_cached_pages"] = int(g("prefix_cached_pages").value)
        return out

    def _build_step_fns(self) -> None:
        """(Re)build the jit'd step entry points from the CURRENT
        ``self.serve``.  Called at construction and again on fused →
        reference demotion, which swaps the params/serve config underneath
        (old compiled variants are dropped; the recompile counter starts
        over for the new config)."""
        self._compiled_keys = {}
        unified = self.ecfg.step_mode == "unified"
        cfgm, serve_p = self.cfg, self.serve
        # static: whether the step fns return an extra quant-telemetry
        # element (recomputed here so demotion keeps arity consistent
        # with the rebuilt serve config)
        self._collect = lm._collect_telemetry(serve_p)
        self._kernel_attn = lm.paged_kernel(self.pcfg, cfgm,
                                            serve_p.fused_cache_attention)
        if unified:
            self._unified = jax.jit(
                lambda p, pools, pt, ps, pln, pf, pli, psl, dt, dp, da, ht,
                lt, pg, off, ih:
                lm.paged_unified_step(p, pools, pt, ps, pln, pf, pli, psl,
                                      dt, dp, da, ht, lt, pg, off, ih,
                                      cfgm, serve_p))
        else:
            self._prefill_first = jax.jit(
                lambda p, pools, t, s, ht, lt, pg, off, ih, li, sl:
                lm.paged_prefill_chunk(p, pools, t, s, ht, lt, pg, off, ih,
                                       li, cfgm, serve_p, first=True,
                                       slot=sl))
            self._prefill_cont = jax.jit(
                lambda p, pools, t, s, ht, lt, pg, off, ih, li, sl:
                lm.paged_prefill_chunk(p, pools, t, s, ht, lt, pg, off, ih,
                                       li, cfgm, serve_p, first=False,
                                       slot=sl))
            self._decode = jax.jit(
                lambda p, pools, t, pos, ht, lt, pg, off, ih, act:
                lm.paged_decode_step(p, pools, t, pos, ht, lt, pg, off, ih,
                                     cfgm, serve_p, active=act))

    def step_program(self):
        """The compiled unified step for the largest chunk-row bucket this
        engine has run: ``as_text()`` shows which kernels it calls and
        ``memory_analysis()`` its device footprint.  Lowered from the
        abstract arguments (shapes, dtypes, shardings) of that bucket's
        first call, so it is the program that call compiled."""
        n_pf = max(self._compiled_keys)
        return self._unified.lower(*self._compiled_keys[n_pf]).compile()

    def compile_count(self) -> int:
        """Compiled variants of the unified step this engine has built
        (shape-bucketed chunk-row counts).  Prefers jit's own lowering
        cache; falls back to the host-side bucket set."""
        fn = getattr(self, "_unified", None)
        if fn is not None and hasattr(fn, "_cache_size"):
            return fn._cache_size()
        return len(self._compiled_keys)

    # ------------------------------------------------------------------
    def _max_prompt_len(self) -> int:
        # one position stays free for the first generated token's K/V write
        return self.ecfg.max_seq - 1

    def _capacity_reason(self, req: Request) -> Optional[str]:
        """None if the request can EVER run to completion alone on this
        engine; otherwise why not.  The check mirrors the scheduler's
        reservation arithmetic: the deepest position it will reserve is
        ``prompt_len + gen - 1`` (the page for the last generated token's
        K/V write), so a request whose page demand at that position
        exceeds the whole pool would previously livelock or crash the
        step loop — now it never enters the queue."""
        if not self._has_attn:
            return None              # pure-SSM: slots are the only capacity
        plen = int(req.prompt.shape[0])
        gen = min(req.max_new_tokens, self.ecfg.max_seq - plen)
        nh, nl = PKV.pages_needed(plen + gen - 1, self.pcfg)
        cap_hi, cap_lo = self.sched.alloc.capacity()
        if nh > cap_hi or nl > cap_lo:
            # Credit the cached prefix before rejecting: the worst case
            # assumes the full max_new_tokens budget is spent, but warm
            # shared-prefix traffic routinely stops at EOS long before
            # that depth — rejecting it on the cold worst case alone
            # throws away exactly the requests the cache makes cheap.
            # Only FULLY shared pages count (a mid-page CoW divergence
            # nets zero: the copy costs the page the share saved).  A
            # credited request that does run to worst-case depth degrades
            # through the normal exhaustion path (preempt-self, watchdog)
            # instead of being refused up front.
            matched = self.sched.probe_prefix(req.prompt)
            bs = self.pcfg.block_size
            ch, cl = PKV.pages_needed(matched // bs * bs, self.pcfg)
            if nh - ch > cap_hi or nl - cl > cap_lo:
                return (f"capacity-infeasible: needs {nh} hi + {nl} lo "
                        f"pages at peak but the pools hold only {cap_hi} "
                        f"hi + {cap_lo} lo — the request could never run "
                        f"even alone")
        return None

    def _enqueue(self, req: Request) -> None:
        self._requests[req.uid] = req
        reason = self._capacity_reason(req)
        if reason is not None:
            self._terminate(req, REJECTED, reason, stat="rejected",
                            kind="reject")
            return
        e = self.ecfg
        if e.max_waiting is not None and \
                len(self.sched.waiting) >= e.max_waiting:
            if e.shed_policy == "shed_oldest":
                # prefer shedding a queued request that has not run at all
                # (a preempted one holds real generation progress)
                fresh = [r for r in self.sched.waiting if r.swapped is None
                         and r.pos == 0 and not r.generated]
                if fresh:
                    victim = fresh[0]
                    self.sched.cancel(victim.uid, state=REJECTED,
                                      error="shed: waiting queue full")
                    vreq = self._requests[victim.uid]
                    self._terminate(vreq, REJECTED,
                                    "shed: waiting queue full",
                                    stat="shed", kind="shed",
                                    sreq=victim)
                else:
                    self._terminate(req, REJECTED,
                                    "shed: waiting queue full",
                                    stat="shed", kind="shed")
                    return
            else:                    # reject_newest
                self._terminate(req, REJECTED,
                                f"waiting queue full "
                                f"({e.max_waiting} requests)",
                                stat="shed", kind="shed")
                return
        self.sched.submit(SchedRequest(
            uid=req.uid, prompt=req.prompt,
            max_new_tokens=req.max_new_tokens, arrival=req.uid))

    def _terminate(self, req: Request, status: str, error: Optional[str],
                   stat: str, kind: str,
                   sreq: Optional[SchedRequest] = None) -> None:
        """Move one Request to a terminal state outside the normal finish
        path (reject/shed/cancel/fail) and queue it for the caller's done
        list."""
        req.status = status
        req.error = error
        if req.out_tokens is None:
            gen = sreq.generated[: sreq.max_new_tokens] if sreq else []
            req.out_tokens = np.asarray(gen, np.int32)
        if sreq is not None:
            req.preemptions = sreq.preemptions
        req.latency_s = self._clock() - req.submit_t
        self._inc(stat)
        if error:
            self._event(kind, uid=req.uid, error=error)
        else:
            self._event(kind, uid=req.uid)
        self._observe_latency("latency_s", self._obs_now - req.obs_submit_t)
        self._terminal_done.append(req)

    def _swap_out(self, sreq: SchedRequest) -> None:
        # slot still assigned here (the scheduler swaps before it frees),
        # so the per-slot SSM state rides along with the pages
        sreq.swapped = PKV.extract_pages(self.pools, sreq.hi_pages,
                                         sreq.lo_pages, slot=sreq.slot)
        self._event("preempt", uid=sreq.uid)
        self._inc("preemptions")
        self._inc("swap_bytes", PKV.swapped_bytes(sreq.swapped))

    def _swap_in(self, sreq: SchedRequest) -> None:
        # sreq.slot is the NEW placement — SSM state restores there, pages
        # at whatever ids the allocator handed back (tables indirect)
        swapped = sreq.swapped
        if self.fault is not None and self.fault.corrupt_swap(sreq.uid):
            swapped = corrupt_swapped(swapped, self.fault.seed)
            self._event("fault_corrupt", uid=sreq.uid)
        try:
            self.pools = PKV.insert_pages(self.pools, swapped,
                                          sreq.hi_pages, sreq.lo_pages,
                                          slot=sreq.slot)
        except PKV.SwapCorruption as exc:
            # insert_pages verifies checksums BEFORE touching the pools, so
            # nothing was restored.  The scheduler is mid-_admit and will
            # finish placing this request; _step fails it (releasing the
            # just-granted slot/pages) right after plan_step returns —
            # everyone else keeps running.
            self._swap_failed.append((sreq, str(exc)))
            return
        self._event("resume", uid=sreq.uid)

    # ------------------------------------------------------------------
    def run(self) -> List[Request]:
        """Drain the engine.  Every submitted request comes back in exactly
        one terminal state (`Request.status`); per-request problems —
        rejection, deadline miss, swap corruption, NaN quarantine, livelock
        — fail THAT request and never raise out of run()."""
        t0 = self._clock()
        done: List[Request] = []
        self._drain_terminal(done)   # submit-time rejects / early cancels
        while self.sched.has_work():
            done += self.step()
        dt = self._clock() - t0
        for r in done:
            r.latency_s = r.latency_s or dt
        return done

    def step(self) -> List[Request]:
        """Run one engine step (one device program in unified mode) and
        return the requests that reached a terminal state in it, finished
        or not.  Synchronous: the step's logits are on the host when it
        returns.  ``run()`` is this in a loop until no work is left."""
        done: List[Request] = []
        with self._annotate("engine.step"):
            self._step(done)
            self._drain_terminal(done)
        return done

    def _drain_terminal(self, done: List[Request]) -> None:
        if self._terminal_done:
            done.extend(self._terminal_done)
            self._terminal_done = []

    def cancel(self, uid: int) -> bool:
        """Terminate one request wherever it is — queued, mid-prefill,
        mid-decode, or preempted — releasing exactly the slot/pages it
        holds.  Partial tokens are kept on the Request.  Returns False for
        an unknown or already-terminal uid."""
        sreq = self.sched.cancel(uid)
        if sreq is None:
            return False
        self._terminate(self._requests[uid], CANCELLED, None,
                        stat="cancelled", kind="cancel", sreq=sreq)
        return True

    def request(self, uid: int) -> Optional[Request]:
        """The Request record for a uid (terminal or not)."""
        return self._requests.get(uid)

    def _fail(self, sreq: SchedRequest, error: str,
              kind: str = "fail") -> None:
        """Quarantine one scheduler request: release its resources, mark
        the Request failed, keep everyone else running."""
        self.sched.fail(sreq, error)
        self._terminate(self._requests[sreq.uid], "failed", error,
                        stat="failed", kind=kind, sreq=sreq)

    def _check_deadlines(self) -> None:
        """Plan-time deadline enforcement: a request past its total or
        TTFT budget fails BEFORE this step plans, so its pages/slot go to
        requests that can still meet theirs."""
        now = self._clock()
        for sreq in list(self.sched.active) + list(self.sched.waiting):
            req = self._requests[sreq.uid]
            waited = now - req.submit_t
            miss = None
            if req.deadline_s is not None and waited > req.deadline_s:
                miss = (f"deadline miss: {waited:.3f}s elapsed > "
                        f"{req.deadline_s:.3f}s total budget")
            elif req.ttft_deadline_s is not None and not sreq.generated \
                    and waited > req.ttft_deadline_s:
                miss = (f"deadline miss: no first token after "
                        f"{waited:.3f}s > {req.ttft_deadline_s:.3f}s "
                        f"TTFT budget")
            if miss is not None:
                self._inc("deadline_misses")
                self._event("deadline_miss", uid=sreq.uid)
                self._fail(sreq, miss)

    def _watchdog(self, progress: bool) -> None:
        """Livelock backstop: ``has_work()`` plus N consecutive zero-span
        steps means nothing can be placed or advanced (injected
        exhaustion, a resume that can never re-allocate, admission
        thrash).  Fail the request at the head of the line — the one FCFS
        is stuck behind — not the engine."""
        if progress:
            self._stall = 0
            return
        if not self.sched.has_work():
            return
        self._stall += 1
        self._inc("stalled_steps")
        n = self.ecfg.watchdog_steps
        if n <= 0 or self._stall < n:
            return
        self._stall = 0
        self._inc("watchdog_trips")
        blockers = sorted(self.sched.waiting + self.sched.active,
                          key=lambda r: (r.arrival, r.uid))
        if blockers:
            self._fail(blockers[0],
                       f"watchdog: no scheduling progress for {n} "
                       f"consecutive steps", kind="watchdog")

    # -- numerics guard -------------------------------------------------
    def _next_token(self, sreq: SchedRequest, row: np.ndarray) -> bool:
        """Greedy-sample one span's logits row, behind the NaN/Inf guard.
        Returns False when the request was quarantined instead."""
        if self.fault is not None and \
                self.fault.nan_logits(sreq.uid, len(sreq.generated)):
            row = np.full_like(row, np.nan)
            self._event("fault_nan", uid=sreq.uid)
        if self.serve.numerics_guard and not np.isfinite(row).all():
            self._quarantine(sreq, f"non-finite logits at generated index "
                                   f"{len(sreq.generated)}")
            return False
        sreq.generated.append(int(np.argmax(row)))
        return True

    def _quarantine(self, sreq: SchedRequest, error: str) -> None:
        self._inc("nan_quarantines")
        self._event("nan_quarantine", uid=sreq.uid)
        self._fail(sreq, error)
        self._maybe_demote()

    def _maybe_demote(self) -> None:
        """Fused → reference graceful degradation: after a NaN quarantine
        under a fused STaMP config, rebuild the engine on the retained
        original weights with reference-path execution (no integer
        kernels).  Slower, but an activation distribution that saturates
        the int4/int8 path cannot take the whole fleet slice with it.
        One-shot per engine; in-flight caches are kept (page layout does
        not depend on the execution path)."""
        st = self.serve.stamp
        if (not self.ecfg.demote_on_nan or self._demoted or st is None
                or not st.enabled or st.execution != "fused"):
            return
        self._demoted = True
        self.params = self._raw_params
        self.serve = dataclasses.replace(
            self.serve,
            stamp=dataclasses.replace(st, execution="reference"),
            fused_decode_matmul=False)
        self._build_step_fns()
        self._refresh_eligibility()
        self._inc("demotions")
        self._event("demote", to="reference")

    # ------------------------------------------------------------------
    def _tables_np(self, sreqs: List[SchedRequest]) -> tuple:
        """Host-built block tables over the full slot array (unmapped → 0)."""
        e, pc = self.ecfg, self.pcfg
        ht = np.zeros((e.max_slots, max(pc.hi_blocks_per_seq, 1)), np.int32)
        lt = np.zeros((e.max_slots, pc.max_blocks_per_seq), np.int32)
        for sreq in sreqs:
            if sreq.slot < 0:
                continue
            ht[sreq.slot, : len(sreq.hi_pages)] = sreq.hi_pages
            lt[sreq.slot, : len(sreq.lo_pages)] = sreq.lo_pages
        if pc.hi_blocks_per_seq == 0:
            ht = ht[:, :0]
        return ht, lt

    def _count_attention_pages(self, cached: np.ndarray) -> None:
        """Pages one attention layer reads this step, and the pages its
        span tables reserve, from the positions each span reads through
        its pages (``cached``, one per span).  Every span reads its sink
        pages; the paged kernel reads only a span's own int4 pages, where
        the XLA fallback gathers its whole table."""
        if not self._has_attn:
            return
        pc = self.pcfg
        nh, nl = pc.hi_blocks_per_seq, pc.max_blocks_per_seq
        reserved = len(cached) * (nh + nl)
        walked = reserved
        if self._kernel_attn:
            lo = -(-np.maximum(cached - pc.num_hi, 0) // pc.block_size)
            walked = len(cached) * nh + int(lo.sum())
        self._inc("attn_pages_walked", walked)
        self._inc("attn_pages_reserved", reserved)

    def _count_moe(self, counts: np.ndarray) -> None:
        """Add one step's MoE counts (`layers.MOE_COUNTS` order, summed
        over the step's layer calls; zeros without experts) to the engine
        counters."""
        from repro.models.layers import MOE_COUNTS
        for name, n in zip(MOE_COUNTS, counts.tolist()):
            self._inc(name, int(n))

    def _tables(self, sreqs: List[SchedRequest]) -> tuple:
        ht, lt = self._tables_np(sreqs)
        return jnp.asarray(ht), jnp.asarray(lt)

    def _write_target(self, sreq: SchedRequest, pos: int) -> tuple:
        is_hi, pidx, off = PKV.token_page_index(pos, self.pcfg)
        page = (sreq.hi_pages if is_hi else sreq.lo_pages)[pidx]
        return page, off, is_hi

    def _bucket_npf(self, n: int) -> int:
        for b in self._npf_buckets:
            if b >= n:
                return b
        return self._npf_buckets[-1]

    def _step(self, done: List[Request]) -> None:
        self._step_i += 1
        self._inc("steps")
        with self._timer.phase("plan"):
            if self.fault is not None:
                self.fault.begin_step(self._step_i)
                if self.fault.exhausted():
                    self._event("fault_exhaust")
                if self.fault.flush_prefix():
                    dropped = self.sched.alloc.flush_cache()
                    self._event("fault_prefix_flush", dropped=dropped)
            self._check_deadlines()
            plan = self.sched.plan_step()
            for sreq in plan.admitted:
                self._event("admit", uid=sreq.uid)
                req = self._requests.get(sreq.uid)
                if req is not None:
                    self._observe_latency("queue_wait_s",
                                          self._obs_now - req.obs_submit_t)
            if self._swap_failed:
                # a swap-in refused its checksum during _admit: the request
                # got a slot/pages but its cache was never restored — fail
                # it and drop it from this step's spans before anything runs
                for sreq, msg in self._swap_failed:
                    self._inc("swap_corruptions")
                    self._fail(sreq, msg, kind="swap_corrupt")
                self._swap_failed = []
                plan.prefills = [w for w in plan.prefills
                                 if w.sreq.state == PREFILLING]
                plan.decode = [r for r in plan.decode if r.state == RUNNING]

        progress = bool(plan.prefills or plan.decode)
        if self.ecfg.step_mode == "two_call":
            if plan.prefills:
                self._run_prefill_chunk(plan.prefills[0], done)
            if plan.decode:
                self._run_decode(plan.decode, done)
        elif progress:
            self._run_unified(plan, done)
        self._watchdog(progress)

    def _run_unified(self, plan, done: List[Request]) -> None:
        """Build the flattened ragged batch the scheduler planned and run
        it as ONE device program: ``n_pf`` chunk rows (bucketed; unused
        rows are null-page dummies) + the decode slot array."""
        e = self.ecfg
        c_len, s = e.prefill_chunk, e.max_slots
        works = plan.prefills
        n_pf = self._bucket_npf(len(works))
        telem = None
        with self._timer.phase("dispatch"):
            with self._timer.phase("build_inputs"):
                pf_tokens = np.zeros((n_pf, c_len), np.int32)
                pf_start = np.zeros((n_pf,), np.int32)
                pf_length = np.zeros((n_pf,), np.int32)
                pf_first = np.zeros((n_pf,), bool)
                pf_last = np.zeros((n_pf,), np.int32)
                # dummy chunk rows park on the null slot (index max_slots):
                # their SSM-state scatter lands there the way masked K/V
                # writes land on the null page
                pf_slots = np.full((n_pf,), s, np.int32)
                pages = np.zeros((n_pf * c_len + s,), np.int32)
                offs = np.zeros((n_pf * c_len + s,), np.int32)
                ishi = np.zeros((n_pf * c_len + s,), bool)
                for i, w in enumerate(works):
                    sreq, start, end = w.sreq, w.start, w.end
                    valid = end - start
                    pf_tokens[i, :valid] = sreq.prompt[start:end]
                    pf_start[i] = start
                    pf_length[i] = end
                    pf_first[i] = start == 0
                    pf_slots[i] = sreq.slot
                    # the chunk's last valid row — on a final chunk that is
                    # the prompt's last token, whose logits are the
                    # first-token distribution (pf_logits of non-final chunks
                    # are discarded)
                    pf_last[i] = valid - 1
                    base = i * c_len
                    if self._has_attn:
                        for t in range(valid):
                            pages[base + t], offs[base + t], ishi[base + t] = \
                                self._write_target(sreq, start + t)
                dec_tokens = np.zeros((s,), np.int32)
                dec_pos = np.zeros((s,), np.int32)
                dec_active = np.zeros((s,), bool)
                base = n_pf * c_len
                for sreq in plan.decode:
                    dec_tokens[sreq.slot] = sreq.generated[-1]
                    dec_pos[sreq.slot] = sreq.pos
                    dec_active[sreq.slot] = True
                    if self._has_attn:
                        pages[base + sreq.slot], offs[base + sreq.slot], \
                            ishi[base + sreq.slot] = \
                            self._write_target(sreq, sreq.pos)
                # span-ordered tables: one row per chunk span (that request's
                # own table), then the whole slot array for the decode spans
                ht_np, lt_np = self._tables_np([w.sreq for w in works]
                                               + plan.decode)
                pf_ht = np.zeros((n_pf, ht_np.shape[1]), np.int32)
                pf_lt = np.zeros((n_pf, lt_np.shape[1]), np.int32)
                for i, w in enumerate(works):
                    pf_ht[i] = ht_np[w.sreq.slot]
                    pf_lt[i] = lt_np[w.sreq.slot]
                span_ht = np.concatenate([pf_ht, ht_np], axis=0)
                span_lt = np.concatenate([pf_lt, lt_np], axis=0)
                self._count_attention_pages(
                    np.concatenate([pf_start, dec_pos + 1]))
            with self._timer.phase("upload"):
                args = (self.params, self.pools) + tuple(
                    jnp.asarray(a) for a in (
                        pf_tokens, pf_start, pf_length, pf_first, pf_last,
                        pf_slots, dec_tokens, dec_pos, dec_active, span_ht,
                        span_lt, pages, offs, ishi))
            if n_pf not in self._compiled_keys:
                self._compiled_keys[n_pf] = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype,
                        sharding=a.sharding if a.committed else None),
                    args)
                self._inc("recompiles")
            with self._timer.phase("launch"):
                *out, moe = self._unified(*args)
            if self._collect:
                pf_logits, dec_logits, self.pools, telem = out
            else:
                pf_logits, dec_logits, self.pools = out
            self._inc("device_dispatches")
            # the wait for the device, apart from the copy to the host
            # (np.asarray would block here anyway: no added sync)
            with self._timer.phase("wait"):
                jax.block_until_ready((pf_logits, dec_logits))
            with self._timer.phase("fetch_logits"):
                # the MoE counts ride the logits' copy to the host
                pf_logits, dec_logits, moe = jax.device_get(
                    (pf_logits, dec_logits, moe))
                self._count_moe(moe)
        if telem is not None:
            self._absorb_telemetry(telem)

        with self._timer.phase("post"):
            for i, w in enumerate(works):
                sreq = w.sreq
                try:
                    sreq.pos = w.end
                    # completed prompt pages become addressable for later
                    # arrivals (before _maybe_finish can release them)
                    self.sched.register_prefix(sreq)
                    self._inc("prefill_chunks")
                    self._event("prefill_chunk", uid=sreq.uid,
                                start=w.start, end=w.end)
                    if w.end == sreq.prompt_len:
                        if not self._next_token(sreq, pf_logits[i]):
                            continue  # quarantined — resources released
                        sreq.state = RUNNING
                        req = self._requests[sreq.uid]
                        req.ttft_s = self._clock() - req.submit_t
                        self._event("first_token", uid=sreq.uid)
                        self._observe_latency(
                            "ttft_s", self._obs_now - req.obs_submit_t)
                        self._maybe_finish(sreq, done)
                except Exception as exc:  # noqa: BLE001 — isolation boundary
                    self._fail(sreq,
                               f"prefill postprocessing error: {exc!r}")
            if plan.decode:
                self._event("decode",
                            uids=tuple(sorted(r.uid for r in plan.decode)))
                for sreq in plan.decode:
                    try:
                        sreq.pos += 1      # last token is now cached
                        if not self._next_token(sreq,
                                                dec_logits[sreq.slot]):
                            continue
                        self._inc("decode_tokens")
                        self._maybe_finish(sreq, done)
                    except Exception as exc:   # noqa: BLE001
                        self._fail(sreq,
                                   f"decode postprocessing error: {exc!r}")

    # -- two_call mode (the PR-3 step pair, kept for parity/AB) ---------
    def _run_prefill_chunk(self, work: PrefillWork,
                           done: List[Request]) -> None:
        e = self.ecfg
        sreq, start, end = work.sreq, work.start, work.end
        valid = end - start
        chunk = np.zeros((1, e.prefill_chunk), np.int32)
        chunk[0, :valid] = sreq.prompt[start:end]
        pages = np.zeros((e.prefill_chunk,), np.int32)
        offs = np.zeros((e.prefill_chunk,), np.int32)
        ishi = np.zeros((e.prefill_chunk,), bool)
        if self._has_attn:
            for i in range(valid):
                pages[i], offs[i], ishi[i] = \
                    self._write_target(sreq, start + i)
        ht_all, lt_all = self._tables([sreq])
        slot_sel = np.asarray([sreq.slot], np.int32)
        ht, lt = ht_all[slot_sel], lt_all[slot_sel]
        last_index = (sreq.prompt_len - 1) - start if end == sreq.prompt_len \
            else valid - 1
        fn = self._prefill_first if start == 0 else self._prefill_cont
        telem = None
        with self._timer.phase("dispatch"):
            *out, moe = fn(
                self.params, self.pools, jnp.asarray(chunk),
                jnp.int32(start), ht, lt, jnp.asarray(pages),
                jnp.asarray(offs), jnp.asarray(ishi),
                jnp.int32(last_index), jnp.int32(sreq.slot))
            if self._collect:
                logits, self.pools, telem = out
            else:
                logits, self.pools = out
            self._inc("device_dispatches")
            # the MoE counts ride the logits' copy to the host
            logits, moe = jax.device_get((logits, moe))
            self._count_moe(moe)
        if telem is not None:
            self._absorb_telemetry(telem)
        with self._timer.phase("post"):
            sreq.pos = end
            self.sched.register_prefix(sreq)
            self._inc("prefill_chunks")
            self._event("prefill_chunk", uid=sreq.uid, start=start, end=end)
            if end == sreq.prompt_len:
                if not self._next_token(sreq, logits[0]):
                    return           # quarantined
                sreq.state = RUNNING
                req = self._requests[sreq.uid]
                req.ttft_s = self._clock() - req.submit_t
                self._event("first_token", uid=sreq.uid)
                self._observe_latency("ttft_s",
                                      self._obs_now - req.obs_submit_t)
                self._maybe_finish(sreq, done)

    def _run_decode(self, running: List[SchedRequest],
                    done: List[Request]) -> None:
        e = self.ecfg
        s = e.max_slots
        tokens = np.zeros((s,), np.int32)
        positions = np.zeros((s,), np.int32)
        active = np.zeros((s,), bool)
        pages = np.zeros((s,), np.int32)
        offs = np.zeros((s,), np.int32)
        ishi = np.zeros((s,), bool)
        for sreq in running:
            tokens[sreq.slot] = sreq.generated[-1]
            positions[sreq.slot] = sreq.pos
            active[sreq.slot] = True
            if self._has_attn:
                pages[sreq.slot], offs[sreq.slot], ishi[sreq.slot] = \
                    self._write_target(sreq, sreq.pos)
        ht, lt = self._tables(running)
        with self._timer.phase("dispatch"):
            logits, self.pools, moe = self._decode(
                self.params, self.pools, jnp.asarray(tokens),
                jnp.asarray(positions), ht, lt, jnp.asarray(pages),
                jnp.asarray(offs), jnp.asarray(ishi), jnp.asarray(active))
            self._inc("device_dispatches")
            logits, moe = jax.device_get((logits, moe))
            self._count_moe(moe)
        with self._timer.phase("post"):
            self._event("decode",
                        uids=tuple(sorted(r.uid for r in running)))
            for sreq in running:
                sreq.pos += 1                  # last token is now cached
                if not self._next_token(sreq, logits[sreq.slot]):
                    continue
                self._inc("decode_tokens")
                self._maybe_finish(sreq, done)

    def _maybe_finish(self, sreq: SchedRequest, done: List[Request]) -> None:
        eos = self.ecfg.eos_id
        hit_eos = eos >= 0 and sreq.generated and sreq.generated[-1] == eos
        cap = min(sreq.max_new_tokens,
                  self.ecfg.max_seq - sreq.prompt_len)
        if hit_eos or len(sreq.generated) >= cap:
            out = sreq.generated[: sreq.max_new_tokens]
            req = self._requests[sreq.uid]
            req.out_tokens = np.asarray(out, np.int32)
            req.latency_s = self._clock() - req.submit_t
            req.preemptions = sreq.preemptions
            req.status = "finished"
            self.sched.finish(sreq)
            self._inc("finished")
            self._event("finish", uid=sreq.uid)
            self._observe_latency("latency_s",
                                  self._obs_now - req.obs_submit_t)
            done.append(req)
