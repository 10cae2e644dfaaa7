"""The one place the benchmark reaches past `PagedServingEngine`'s public
API.  The engine drains its whole queue in `run()` and stamps no token
with a time; a closed loop needs one step at a time and the moment each
token appeared.  So this module drives `engine._step` and reads each
request's progress from `engine.sched`, and hands the harness plain data.
Once the engine has a public ``step()`` and per-token timestamps, only
this module changes.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Progress:
    """One request's work in one step: positions ``[pos0, pos1)`` went
    into the cache, and its served tokens went from ``gen0`` to ``gen1``."""

    uid: int
    prompt_len: int
    pos0: int
    pos1: int
    gen0: int
    gen1: int

    @property
    def prefill(self) -> bool:
        """The step ran a prompt chunk of this request (not a decode)."""
        return self.pos0 < self.prompt_len


@dataclasses.dataclass
class StepResult:
    progress: list            # [Progress] of every request that moved
    finished: list            # [(uid, out_tokens, status)]


def step(engine) -> StepResult:
    """Run exactly one engine step (one device program) and report who
    moved.  The step is synchronous: the engine pulls the step's logits to
    the host before it returns."""
    live = list(engine.sched.active) + list(engine.sched.waiting)
    before = {r.uid: (r.pos, len(r.generated)) for r in live}
    done: list = []
    engine._step(done)
    engine._drain_terminal(done)
    moved = []
    for r in live:
        pos0, gen0 = before[r.uid]
        if r.pos != pos0 or len(r.generated) != gen0:
            moved.append(Progress(r.uid, r.prompt_len, pos0, r.pos, gen0,
                                  len(r.generated)))
    finished = [(r.uid, r.out_tokens, r.status) for r in done]
    return StepResult(moved, finished)


def pool_pages(engine) -> tuple[int, int]:
    """(pages referenced now, pages the hi and lo pools hold in all).
    Zero-ref pages the prefix cache keeps are reclaimable, so they count
    as free."""
    cap_hi, cap_lo = engine.sched.alloc.capacity()
    avail_hi, avail_lo = engine.sched.alloc.available_counts()
    return (cap_hi - avail_hi) + (cap_lo - avail_lo), cap_hi + cap_lo


def idle(engine) -> bool:
    return not engine.sched.has_work()
