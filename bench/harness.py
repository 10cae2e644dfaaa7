"""One run of one benchmark cell: set up the served model from the seed,
warm every step shape, drive a closed loop of clients through the engine
one step at a time, measure for ``seconds``, check the served tokens
against the plain reference, and build the result line.

Everything a cell is made of is found by name: its configuration in
``bench/configs/<config>.json`` (with ``bench/families/<family>.py`` for
the program's side and ``bench/reference/<family>.py`` for the plain
reference), its traffic in ``bench/traffic/<mix>.json``, each of its metrics,
end-to-end and per-layer, in ``bench/metrics/<metric>.py``, and its correctness limits in
``bench/limits/<cell>.json``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_SECONDS = 4.0           # the traced part of a --trace 1 window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompiledInWindow(RuntimeError):
    """Something compiled or traced inside the measured window."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """Import a file by path (metric and family files are named after
    benchmark names, which may hold dots and dashes)."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # the BENCHMARK.json entries this cell reports
    per_layer: list
    limits: dict


def cell(bench: dict, name: str) -> Cell:
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=w["chips"],
                config=load_json(ROOT / cfg["file"]),
                mix=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=per_layer,
                limits=load_json(BENCH / "limits" / f"{name}.json"))


class CompileCounter:
    """Counts compilations and traces through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.count += 1


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    prefill_chunks: int
    work: list                # [(pos0, pos1, new_tokens)] per request
    pages_used: int


class Loop:
    """Closed-loop clients over one engine: each client sends its next
    request from the mix's pool when its previous one finishes."""

    def __init__(self, engine, adapter, pool: list, mix: dict):
        self.engine, self.adapter = engine, adapter
        self.pool, self.next = pool, 0
        self.mix = mix
        self.client: dict = {}       # uid -> client
        self.submit_t: dict = {}
        self.prompt: dict = {}
        self.stamps: dict = {}       # uid -> [token times]
        self.finished: dict = {}     # uid -> (out_tokens, status, t)
        self.first: list = []        # uid of each client's first request
        self.steps: list = []

    def submit(self, client: int, first: bool = False) -> int:
        from traffic import first_output
        req = self.pool[self.next % len(self.pool)]
        self.next += 1
        budget = req.max_new
        if first:
            budget = first_output(budget, client, self.mix["clients"])
        uid = self.engine.submit(req.prompt, max_new_tokens=budget)
        self.client[uid] = client
        self.submit_t[uid] = time.perf_counter()
        self.prompt[uid] = req.prompt
        self.stamps[uid] = []
        return uid

    def start(self) -> None:
        self.first = [self.submit(c, first=True)
                      for c in range(self.mix["clients"])]

    def warm_up(self) -> None:
        """Step until every client's first request has its first token:
        the prompts submitted together at the start queue for prefill, and
        their first tokens are no sample of steady state."""
        while not all(self.stamps[uid] for uid in self.first):
            self.step()

    def step(self) -> StepRecord:
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            res = self.adapter.step(self.engine)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.bookkeeping"):
            work, chunks = [], 0
            for p in res.progress:
                new = p.gen1 - p.gen0
                self.stamps[p.uid].extend([t1] * new)
                work.append((p.pos0, p.pos1, new))
                chunks += p.prefill
            for uid, out, status in res.finished:
                self.finished[uid] = (out, status, t1)
                self.submit(self.client[uid])
            used, _ = self.adapter.pool_pages(self.engine)
        rec = StepRecord(t0, t1, chunks, work, used)
        self.steps.append(rec)
        return rec

    def run_for(self, seconds: float, on_step=None) -> tuple[float, float]:
        """Step until ``seconds`` have passed; returns the (start, end) of
        the span the steps covered."""
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            rec = self.step()
            end = rec.t1
            if on_step is not None:
                on_step(rec)
        return start, end


def warm_buckets(engine, adapter, mix: dict, vocab: int, seed: int) -> None:
    """Run every step shape the traffic will use once, so that it compiles
    (or loads from the compile cache) in set-up: n requests of one chunk
    each for every n up to ``max_prefills`` (each planned chunk-row count),
    then their decode steps (the decode-only shape)."""
    rng = np.random.default_rng([seed, 7])
    for n in range(1, mix["max_prefills"] + 1):
        for _ in range(n):
            engine.submit(rng.integers(0, vocab, mix["prefill_chunk"],
                                       dtype=np.int32), max_new_tokens=2)
        while not adapter.idle(engine):
            adapter.step(engine)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def window_numbers(loop: Loop, w0: float, w1: float) -> dict:
    """Raw per-request samples of the window: output tokens stamped in it,
    TTFT of every request whose first token falls in it, every
    inter-token gap that ends in it."""
    inside = lambda t: w0 < t <= w1                          # noqa: E731
    tokens, ttft, gaps = 0, [], []
    for uid, st in loop.stamps.items():
        tokens += sum(1 for t in st if inside(t))
        if st and inside(st[0]):
            ttft.append(st[0] - loop.submit_t[uid])
        gaps += [b - a for a, b in zip(st, st[1:]) if inside(b)]
    return {"seconds": w1 - w0, "tokens": tokens, "ttft": ttft, "gaps": gaps}


@dataclasses.dataclass
class WindowContext:
    """What an end-to-end metric reader may read."""

    cell: Cell
    nums: dict                # window_numbers()
    setup_s: float


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    steps: list               # StepRecords of the traced part
    seconds: float            # its length on the host clock
    counters: dict            # engine counter deltas over it
    pool_pages: int           # pages the KV pools hold
    trace: Optional[object]   # tracereduce.Summary, or None
    peaks: Optional[dict]


def read_metrics(metrics: list, ctx) -> dict:
    """Each metric from its reader ``bench/metrics/<name>.py``; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def engine_counters(engine) -> dict:
    """The engine's ``stats`` and its step-phase time sums."""
    out = {k: float(v) for k, v in engine.stats.items()}
    for ph in ("plan", "dispatch", "post"):
        out[f"{ph}_s"] = engine.metrics.histogram(
            "step_phase_s", labels={"phase": ph}).sum
    return out


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _check_serving(c: dict, serve) -> list:
    """Where the program's calibrated serving precision departs from what
    the configuration file states (and the reference computes)."""
    s, kv = c["serving"]["stamp"], c["serving"]["kv_cache"]
    st, q = serve.stamp, serve.kv
    pairs = [("transform", st.seq_transform, s["transform"]),
             ("levels", st.levels, s["levels"]),
             ("num_hi", st.num_hi_tokens, s["num_hi"]),
             ("hi_bits", st.hi_bits, s["hi_bits"]),
             ("lo_bits", st.lo_bits, s["lo_bits"]),
             ("skip_first_token", st.skip_first_token, s["skip_first_token"]),
             ("weight_bits", serve.weight_bits, c["serving"]["weight_bits"]),
             ("kv_num_hi", q.num_hi, kv["num_hi"]),
             ("kv_hi_bits", q.hi_bits, kv["hi_bits"]),
             ("kv_lo_bits", q.lo_bits, kv["lo_bits"])]
    return [f"{k}: program {a!r}, configuration {b!r}" for k, a, b in pairs
            if a != b]


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          t_start: float, compiles: CompileCounter,
          require_tpu: bool = True) -> dict:
    """Set up, warm, and measure; return plain results only, so that the
    program's device state is free once this returns."""
    import jax
    import engine_adapter as adapter
    import tracereduce
    import traffic
    from repro.launch.serve import (build_model, enable_compile_cache,
                                    with_execution)
    from repro.serving.engine import PagedEngineConfig, PagedServingEngine

    devices = jax.devices()
    peaks = None
    if require_tpu:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            raise NoChip(f"JAX found {len(devices)} {devices[0].platform} "
                         f"device(s); the cell needs {cell.chips} TPU chip(s)")
        peaks = peaks_for(devices[0].device_kind)
    print(f"[bench] compile cache: {enable_compile_cache()}", file=sys.stderr)
    c, mix = cell.config, cell.mix
    family = load_module(BENCH / "families" / f"{c['family']}.py")
    mcfg = family.model_config(c)
    # the program's init takes a 32-bit key seed; larger seeds fold in
    params, serve_cfg, _ = build_model(mcfg, seed % (2 ** 32))
    mismatch = _check_serving(c, serve_cfg)
    serve_cfg = with_execution(serve_cfg, "fused")
    num_hi = serve_cfg.kv.num_hi
    engine = PagedServingEngine(params, mcfg, serve_cfg, PagedEngineConfig(
        max_slots=mix["slots"], prefill_chunk=mix["prefill_chunk"],
        max_seq=mix["max_seq"], max_prefills=mix["max_prefills"],
        # a page holds one precision, so pages tile the int8 sink region
        block_size=math.gcd(16, num_hi) if num_hi else 16))
    del params
    warm_buckets(engine, adapter, mix, mcfg.vocab_size, seed)
    pool = traffic.requests(mix, mcfg.vocab_size, seed)
    loop = Loop(engine, adapter, pool, mix)
    loop.start()
    loop.warm_up()
    _, pool_pages = adapter.pool_pages(engine)

    compiled0 = compiles.count
    recompiles0 = engine.stats["recompiles"]
    counters0 = engine_counters(engine)
    n_before = len(loop.steps)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    traced = {}

    def on_step(rec):
        if tdir and "end" not in traced and \
                rec.t1 - traced["start"] >= min(TRACE_SECONDS, seconds):
            jax.profiler.stop_trace()
            traced.update(end=rec.t1, steps=len(loop.steps),
                          counters=engine_counters(engine))

    if tdir:
        jax.profiler.start_trace(tdir)
    w0 = time.perf_counter()
    traced["start"] = w0
    setup_s = w0 - t_start
    _, w1 = loop.run_for(seconds, on_step)
    if compiles.count != compiled0 or \
            engine.stats["recompiles"] != recompiles0:
        raise CompiledInWindow(
            f"{compiles.count - compiled0} compile/trace events and "
            f"{engine.stats['recompiles'] - recompiles0} new step shapes "
            f"inside the window")
    stats = devices[0].memory_stats() or {}
    out = {
        "nums": window_numbers(loop, w0, w1),
        "setup_s": setup_s,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
        "finished": [(loop.prompt[u], out, st, t)
                     for u, (out, st, t) in loop.finished.items()
                     if w0 < t <= w1],
        "mismatch": mismatch,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": cell.chips},
    }
    if tdir:
        steps = loop.steps[n_before:traced["steps"]]
        counters = {k: traced["counters"][k] - counters0[k]
                    for k in counters0}
        summary = tracereduce.summarize(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = Context(cell=cell, steps=steps,
                      seconds=traced["end"] - w0, counters=counters,
                      pool_pages=pool_pages, trace=summary, peaks=peaks)
        out["per_layer"] = read_metrics(cell.per_layer, ctx)
        out["trace"] = summary
    return out


def sample(finished: list, n: int, seed: int) -> list:
    """The correctness sample: the longest request the window finished,
    and ``n - 1`` others drawn from the seed; as (prompt, served)."""
    ok = [(p, np.asarray(o)) for p, o, st, _ in finished if st == "finished"]
    if not ok:
        return []
    order = sorted(range(len(ok)), key=lambda i: -(len(ok[i][0])
                                                   + len(ok[i][1])))
    rest = order[1:]
    rng = np.random.default_rng([seed, 3])
    pick = [order[0]] + list(rng.choice(rest, size=min(n - 1, len(rest)),
                                        replace=False))
    picked = [ok[i] for i in pick]
    while len(picked) < n:               # fixed shapes: repeat if short
        picked.append(picked[len(picked) % len(pick)])
    return picked


def check(cell: Cell, seed: int, picked: list, control: bool = False) -> dict:
    """Gaps of the served tokens (and the control's) below the reference's
    best logit; see `bench/reference`."""
    import jax
    ref = load_module(BENCH / "reference" / f"{cell.config['family']}.py")
    mix = cell.mix
    chunk = mix["prefill_chunk"]
    batch = ref.pack(picked, chunk,
                     -(-mix["prompt_tokens"]["max"] // chunk),
                     mix["output_tokens"]["max"] - 1)
    with jax.default_matmul_precision("highest"):
        return ref.logit_gaps(cell.config, seed % (2 ** 32), chunk, batch,
                               control=control)


def verdict(checks: dict) -> bool:
    """Correct where every number compared was read and lies within its
    limit."""
    return all(v["value"] is not None and v["value"] <= v["limit"]
               for v in checks.values())


def run(cell_obj: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, compiles: CompileCounter,
        require_tpu: bool = True, control: bool = False) -> dict:
    """One whole run; returns the result line's object.  With ``control``
    the control's tokens stand where the served ones go: the line's
    ``correct`` is then the control's verdict, and the served tokens'
    gaps are still under ``gaps``."""
    import jax
    res = serve(cell_obj, seed, seconds, trace, t_start, compiles,
                require_tpu=require_tpu)
    gc.collect()
    jax.clear_caches()
    picked = sample(res["finished"], cell_obj.mix["check_requests"], seed)
    # no finished request to compare leaves the gap unread: not correct
    gaps = check(cell_obj, seed, picked, control=control) if picked else {}
    judged = gaps.get("control" if control else "served", {})
    n_failed = sum(1 for *_, st, _ in res["finished"] if st != "finished")
    checks = {"mean_logit_gap": {"value": judged.get("mean_gap"),
                                 "limit": cell_obj.limits["mean_logit_gap"]},
              "failed_requests": {"value": n_failed, "limit": 0},
              "precision_mismatches": {"value": len(res["mismatch"]),
                                       "limit": 0}}
    for m in res["mismatch"]:
        print(f"[bench] serving precision differs: {m}", file=sys.stderr)
    correct = verdict(checks)
    device = dict(res["device"], memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": correct,
            "attempted": len(res["finished"]),
            "failed": n_failed}
    if trace:
        line["metrics"] = res["per_layer"]
        s = res["trace"]
        device.update(busy_s=s.busy_s, window_s=s.window_s)
        line["device"] = device
        line["breakdown"] = s.breakdown()
    else:
        line["metrics"] = read_metrics(
            cell_obj.end_to_end,
            WindowContext(cell_obj, res["nums"], res["setup_s"]))
        line["device"] = device
    line["gaps"] = gaps
    line["checks"] = checks
    return line
