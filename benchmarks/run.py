"""Benchmark driver: one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV rows. A module that raises prints
``<module>,ERROR,<msg>``, the others still run, and ``main`` then exits
non-zero."""
from __future__ import annotations

import sys
import time


MODULES = [
    "table1_perf",
    "sched_bench",
    "serve_bench",
    "table4_memory",
    "fig10_speedup",
    "fig11_access",
    "kernel_bench",
    "table3_quant",
    "table2_compression",
    "fig12_n_sweep",
]


def main() -> int:
    from repro.launch import compile_cache

    compile_cache.enable()
    only = sys.argv[1:] if len(sys.argv) > 1 else None
    failed = []
    print("name,us_per_call,derived")
    for modname in MODULES:
        if only and modname not in only:
            continue
        t0 = time.time()
        mod = __import__(f"benchmarks.{modname}", fromlist=["run"])
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{modname},ERROR,{str(e)[:120]}")
            failed.append(modname)
            continue
        dt = (time.time() - t0) * 1e6
        for r in rows:
            name = r.pop("name")
            us = r.pop("us_per_call", round(dt / max(len(rows), 1), 1))
            derived = ";".join(f"{k}={v}" for k, v in r.items())
            print(f"{name},{us},{derived}", flush=True)
    if failed:
        print(f"benchmarks failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
