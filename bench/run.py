#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload yi-6b.chat --seed 7 --seconds 45 --trace 0

``--trace 0`` measures the cell's end-to-end metrics with every tracer
off; ``--trace 1`` serves the same window under the JAX profiler and the
program's phase tracer and reports the per-layer metrics instead. Either
way the output is checked against the configuration's plain reference, and
the numbers compared are printed beside their limits, as the last lines on
standard error and under ``checks`` in the result line.

Without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside ``bench/``, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from bench import harness

    def log(msg: str) -> None:
        print(msg, flush=True)

    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START, log=log)
    except harness.Unavailable as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
