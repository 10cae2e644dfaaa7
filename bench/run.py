"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic mix, metrics, limits) comes from
``BENCHMARK.json`` at the root of the checkout.  One process does the
whole run and starts no other.  The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last the ``checks``
compared, each beside its limit); the same checks close standard error.
Without a TPU, with fewer chips than the cell asks for, or when anything
compiles inside the measured window, it exits non-zero and prints no
result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import json                                                    # noqa: E402
import pathlib                                                 # noqa: E402
import sys                                                     # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.cell(bench, args.workload)
    compiles = harness.CompileCounter()
    try:
        line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, compiles)
    except (harness.NoChip, harness.CompiledInWindow) as exc:
        print(f"[bench] {type(exc).__name__}: {exc}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"[bench] check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
