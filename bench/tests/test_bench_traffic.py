"""The traffic generator: every seed the same sizes, in another order."""

import collections
import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic                                                 # noqa: E402


@pytest.mark.parametrize("mix", ["long_prompt", "chat_decode"])
def test_same_work_for_every_seed(mix):
    m = traffic.load_mix(mix)
    a = traffic.requests(m, 102400, 1)
    b = traffic.requests(m, 102400, 2 ** 31 + 77)
    size = lambda rs: [(len(r.prompt), r.max_new) for r in rs]  # noqa: E731
    assert size(a) == size(b)
    assert len(collections.Counter(size(a))) > len(a) // 4
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    for r in a:
        assert m["prompt_tokens"]["min"] <= len(r.prompt) \
            <= m["prompt_tokens"]["max"]
        assert m["output_tokens"]["min"] <= r.max_new \
            <= m["output_tokens"]["max"]
        assert len(r.prompt) + r.max_new <= m["max_seq"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 102400


def test_same_seed_same_requests():
    m = traffic.load_mix("long_prompt")
    a, b = traffic.requests(m, 512, 9), traffic.requests(m, 512, 9)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))


@pytest.mark.parametrize("mix,key,median,clipped", [
    ("long_prompt", "prompt_tokens", 1500, (0.05, 0.10)),  # the source's
    ("long_prompt", "output_tokens", 13, (0.01, 0.04)),    # medians
    ("chat_decode", "prompt_tokens", 1020, (0.05, 0.10)),
    ("chat_decode", "output_tokens", 129, (0.02, 0.06)),
])
def test_heavy_tailed_sizes_at_the_source_medians(mix, key, median, clipped):
    m = traffic.load_mix(mix)
    sizes = traffic._sizes(m[key], m["requests"], 0.0)
    assert np.median(sizes) == pytest.approx(median, abs=1)
    assert np.mean(sizes) > np.median(sizes)
    assert clipped[0] < np.mean(sizes == m[key]["max"]) < clipped[1]


def test_first_requests_stagger():
    got = [traffic.first_output(100, c, 4) for c in range(4)]
    assert got == [13, 38, 63, 88]
    assert json.loads(json.dumps(got)) == got
