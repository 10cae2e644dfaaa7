"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed a whole run (set-up, warm-up, a window of
``--seconds`` at the cell's own load, the reference check), printing the
program's mean served-token gap and, for the ``--control`` seeds, the
control's (the reference one precision lower, int4 for int8).  The
benchmark's own runs do not run the control.

    python bench/control.py --workload <cell> --seconds 12 \
        --seeds 101 102 ... --control 101 102 103

Prints one JSON object per seed and a last one with the lower reading
(the largest program mean gap) and the upper (the smallest control
mean gap).  A control seed's run puts the control's tokens where the
served ones go, through the same check as the benchmark's runs: its
``control_correct`` is that verdict.  Exits 1 where a control run comes
out correct.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import harness
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.cell(bench, args.workload)
    compiles = harness.CompileCounter()
    served, control, control_correct = [], [], []
    for seed in args.seeds:
        t0 = time.perf_counter()
        is_control = seed in args.control
        line = harness.run(cell, seed, args.seconds, False, t0, compiles,
                           control=is_control)
        gaps = line["gaps"]
        checks = line["checks"]
        value = gaps.get("served", {}).get("mean_gap")
        rec = {"seed": seed, "served": value,
               # the program's verdict on its own tokens
               "correct": harness.verdict(dict(checks, mean_logit_gap=dict(
                   checks["mean_logit_gap"], value=value))),
               "attempted": line["attempted"],
               "seconds": time.perf_counter() - t0, "gaps": gaps}
        if value is not None:
            served.append(value)
        if is_control:
            rec["control"] = checks["mean_logit_gap"]["value"]
            rec["control_correct"] = line["correct"]
            control_correct.append(line["correct"])
            if rec["control"] is not None:      # no number: it failed
                control.append(rec["control"])
        print(json.dumps(rec), flush=True)
    print(json.dumps({"workload": args.workload,
                      "limit": cell.limits["mean_logit_gap"],
                      "lower": max(served) if served else None,
                      "upper": min(control) if control else None,
                      "served": served, "control": control,
                      "control_correct": control_correct}))
    return 1 if any(control_correct) else 0


if __name__ == "__main__":
    sys.exit(main())
