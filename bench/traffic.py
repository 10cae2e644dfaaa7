"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws its requests from the run's seed.

Every seed gets the same request sizes in the same order; the seed only
draws the token ids.  So two seeds do the same work, and a difference
between their runs is noise, not a different load (a window serves a few
dozen requests of the pool, so a seed-drawn order changed the load: on
the chip it moved tokens/s by 15% and the TTFT tail by 50% between seeds
where two runs of one seed agreed to 1%).

Keys of a mix file:

* ``loop``: ``"closed"`` — each of ``clients`` callers sends its next
  request when its previous one has finished;
* ``slots``: decode slots of the engine (its batch width);
* ``prompt_tokens`` / ``output_tokens``: lognormal sizes, ``{"median",
  "sigma", "min", "max"}``, clipped to ``[min, max]``;
* ``prefill_chunk``, ``max_prefills``, ``max_seq``: the engine's step
  geometry for this traffic;
* ``requests``: size of the pool the clients draw from, in order;
* ``check_requests``: requests in the correctness sample; the longest
  the window finished is one of them.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclasses.dataclass
class Request:
    prompt: np.ndarray        # (len,) int32
    max_new: int


def load_mix(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def _sizes(spec: dict, n: int, offset: float) -> np.ndarray:
    """``n`` sizes at stratified quantiles of the distribution: the same
    for every seed.  ``offset`` decorrelates two size lists drawn from the
    same strata."""
    u = np.mod((np.arange(n) + 0.5) / n + offset, 1.0)
    z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.floor(raw), spec["min"], spec["max"]).astype(int)


def requests(mix: dict, vocab: int, seed: int) -> list:
    """The mix's request pool for ``seed``, in the order clients take it."""
    n = mix["requests"]
    plen = _sizes(mix["prompt_tokens"], n, 0.0)
    olen = _sizes(mix["output_tokens"], n, _GOLDEN)
    # a second, independent shuffle of the output sizes keeps prompt and
    # output lengths uncorrelated whatever the strata
    olen = olen[np.random.default_rng(0).permutation(n)]
    order = np.random.default_rng(1).permutation(n)
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                    int(olen[i])) for i in order]


def first_output(full: int, client: int, clients: int) -> int:
    """Output budget of a client's first request: cut to a staggered share
    so that the clients finish at spread-out times from the start, as they
    would in steady state, instead of all at once."""
    return max(1, math.ceil(full * (client + 0.5) / clients))
