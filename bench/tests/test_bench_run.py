"""Whole benchmark runs on the CPU at a test size: the harness's look for
a chip is skipped and everything else runs — set-up, warm-up, the closed
loop, the window, the reference check.  A sound run is correct; the
control (the reference one precision lower) and a served token altered
where it is produced are not; a compile inside the window fails the run;
and off the TPU, or without the program, the command prints no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))

import harness                                                 # noqa: E402

# At this size (d 256, 2 layers, vocab 4096) the program's mean served-
# token gap read 0.036-0.115 over seeds 5-9 and the control's 0.272-0.622
# (CPU); the limit lies between, and a token altered where it is produced
# reads far above.
LIMIT = 0.2
SEED = 5


def tiny_cell() -> harness.Cell:
    bench = harness.load_json(ROOT / "BENCHMARK.json")
    return harness.Cell(
        name="tiny", chips=1,
        config=harness.load_json(DATA / "tiny-dense.json"),
        mix=harness.load_json(DATA / "tiny-mix.json"),
        end_to_end=[m for m in bench["end_to_end"]],
        per_layer=bench["per_layer"],
        limits={"mean_logit_gap": LIMIT})


def run(trace=False, control=False, seconds=1.0):
    return harness.run(tiny_cell(), SEED, seconds, trace,
                       time.perf_counter(), harness.CompileCounter(),
                       require_tpu=False, control=control)


@pytest.fixture(scope="module")
def sound():
    return run()


@pytest.fixture(scope="module")
def control():
    return run(control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "checks"
    assert sound["checks"]["mean_logit_gap"]["value"] <= LIMIT
    assert set(sound["metrics"]) == {"tokens_per_s", "ttft_p90_s",
                                     "itl_p95_ms", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(sound["device"])


def test_control_is_not_correct(control):
    # the control's tokens go through the benchmark's own check
    assert not control["correct"]
    assert control["checks"]["mean_logit_gap"]["value"] \
        == control["gaps"]["control"]["mean_gap"] > LIMIT
    # the same run's served tokens pass it
    assert control["gaps"]["served"]["mean_gap"] <= LIMIT


def test_verdict_needs_every_number_within_its_limit():
    ok = {"a": {"value": 0.1, "limit": 0.2}, "b": {"value": 0, "limit": 0}}
    assert harness.verdict(ok)
    assert not harness.verdict(dict(ok, a={"value": 0.3, "limit": 0.2}))
    assert not harness.verdict(dict(ok, a={"value": None, "limit": 0.2}))


def test_control_script_fails_where_a_control_passes(monkeypatch, capsys):
    ctl = harness.load_module(BENCH / "control.py")
    limit = harness.cell(harness.load_json(ROOT / "BENCHMARK.json"),
                         "mistral-nemo-12b-l20.long_prompt"
                         ).limits["mean_logit_gap"]

    def fake_run(cell, seed, seconds, trace, t0, compiles, control=False):
        gaps = {"served": {"mean_gap": 0.5 * limit},
                "control": {"mean_gap": seed * limit}}
        checks = {"mean_logit_gap": {
            "value": gaps["control" if control else "served"]["mean_gap"],
            "limit": limit}, "failed_requests": {"value": 0, "limit": 0}}
        return {"correct": harness.verdict(checks), "attempted": 3,
                "gaps": gaps, "checks": checks}

    monkeypatch.setattr(harness, "run", fake_run)
    monkeypatch.setattr(harness, "CompileCounter", lambda: None)
    argv = ["--workload", "mistral-nemo-12b-l20.long_prompt",
            "--seconds", "1", "--seeds", "2", "3", "--control"]
    assert ctl.main(argv + ["2", "3"]) == 0
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["control_correct"] == [False, False]
    assert last["lower"] == 0.5 * limit and last["upper"] == 2 * limit
    # a control of seed 0 reads a gap of 0, within the limit: exit 1
    assert ctl.main(argv[:-3] + ["0", "2", "--control", "0"]) == 1


def test_warm_up_ends_once_every_first_request_has_its_first_token():
    class Engine:
        def __init__(self):
            self.uids = []

        def submit(self, prompt, max_new_tokens):
            self.uids.append(len(self.uids))
            return self.uids[-1]

    class Adapter:
        """Gives the oldest request without a token its first one every
        other step."""

        def __init__(self):
            self.n, self.started = 0, 0

        def step(self, engine):
            self.n += 1
            moved = []
            if self.n % 2 == 0:
                moved = [types.SimpleNamespace(uid=self.started, gen0=0,
                                               gen1=1, pos0=0, pos1=4,
                                               prefill=True)]
                self.started += 1
            return types.SimpleNamespace(progress=moved, finished=[])

        def pool_pages(self, engine):
            return 0, 1

    pool = [types.SimpleNamespace(prompt=np.zeros(4, np.int32), max_new=8)]
    adapter = Adapter()
    loop = harness.Loop(Engine(), adapter, pool, {"clients": 3})
    loop.start()
    loop.warm_up()
    assert loop.first == [0, 1, 2]
    assert all(loop.stamps[u] for u in loop.first)
    assert adapter.n == 6          # three first tokens, one every other step


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.engine import PagedServingEngine
    orig = PagedServingEngine._next_token

    def altered(self, sreq, row):
        ok = orig(self, sreq, row)
        if ok and len(sreq.generated) % 2 == 0:
            sreq.generated[-1] = (sreq.generated[-1] + 1) % row.shape[-1]
        return ok

    monkeypatch.setattr(PagedServingEngine, "_next_token", altered)
    line = run()
    assert not line["correct"]
    assert line["checks"]["mean_logit_gap"]["value"] > LIMIT


def test_compile_in_window_fails_the_run(monkeypatch):
    import jax
    import jax.numpy as jnp

    import engine_adapter
    orig = engine_adapter.step
    calls = [0]

    def step(engine):
        calls[0] += 1
        jax.jit(lambda x: x * 2)(jnp.ones(calls[0])).block_until_ready()
        return orig(engine)

    monkeypatch.setattr(engine_adapter, "step", step)
    with pytest.raises(harness.CompiledInWindow):
        run()


def test_traced_run_reads_per_layer_metrics():
    line = run(trace=True)
    assert line["correct"]
    m = line["metrics"]
    assert {"engine.host_ms_per_step", "engine.decode_batch",
            "kv.pool_use"} <= set(m)
    assert 0 < m["kv.pool_use"]["value"] <= 100
    # no device plane on the CPU: nothing read, nothing reported as 0
    assert "stamp_prefill_roofline" not in m and "mfu" not in m
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _command(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mistral-nemo-12b-l20.long_prompt", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_no_tpu_no_result_line():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "NoChip" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_benchmark_json_finds_every_piece():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = harness.cell(bench, w["name"])
        assert c.limits["mean_logit_gap"] > 0
        assert c.config["name"] == w["config"]
        assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
        assert c.per_layer
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for cfg in bench["configs"]:
        c = json.loads((ROOT / cfg["file"]).read_text())
        assert c["source"] == cfg["source"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert (BENCH / "families" / f"{c['family']}.py").is_file()
        assert (BENCH / "reference" / f"{c['family']}.py").is_file()


def test_sample_keeps_the_longest():
    fin = [(np.zeros(n, np.int32), np.zeros(k, np.int32), "finished", 0.0)
           for n, k in ((10, 3), (50, 9), (20, 2), (5, 1))]
    fin.append((np.zeros(99, np.int32), np.zeros(9, np.int32), "failed", 0))
    picked = harness.sample(fin, 3, 1)
    assert len(picked) == 3 and len(picked[0][0]) == 50
