#!/usr/bin/env python3
"""Measure what a cell's data files state: its knee and its limits.

    python3 bench/calibrate.py --workload yi-6b.chat --saturate 16 \
        --rate-shares 0.8,1.0 --check-seeds 11,12,13

Not part of a benchmark run. One process (it holds the chip) does:

  1. the knee: the cell served at once (``--saturate N`` requests of the
     mix, all due at 0) gives the request rate it completes with its
     queue never empty, which is stored as the cell's knee in
     ``bench/cells/<cell>.json``; open-loop windows at each of
     ``--rate-shares`` of the knee then show whether the backlog grows
     (queue wait of the last quarter of arrivals against the first);
  2. the limits: for each check seed, traffic drawn from it, a window
     served at the cell's own load, and the logit gaps of the served
     tokens read beside the control's: the reference computed one
     precision lower, at the same positions.

The weights are the configuration's (``weight_seed``), booted from its
serving artifact as a benchmark run boots them.

Windows last ``--seconds``. Each reading is a JSON line on standard output.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROL = "float8_e4m3fn"


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def serve(srv, planned):
    from repro.serve import Request

    reqs = [Request(p.rid, p.prompt, p.max_new_tokens, arrival=p.arrival)
            for p in planned]
    t = time.monotonic()
    rep = srv.run(reqs)
    return rep, time.monotonic() - t


def summary(planned, rep, wall: float, seconds: float) -> dict:
    """Rates and tails of one served window, and the queue wait of the
    first and of the last quarter of its arrivals."""
    arr = {p.rid: p.arrival for p in planned}
    # the report lists queue waits in finish order, like its outputs
    waits = sorted(zip([arr[r] for r in rep.outputs], rep.queue_wait_s))
    k = max(1, len(waits) // 4)
    return {"requests": len(planned), "tokens": rep.total_tokens,
            "wall_s": wall, "drain_s": wall - seconds,
            "tokens_per_s": rep.total_tokens / rep.wall_s,
            "req_per_s": len(planned) / rep.wall_s,
            "ttft_p50_s": float(np.percentile(rep.ttft_s, 50)),
            "ttft_p90_s": float(np.percentile(rep.ttft_s, 90)),
            "itl_p50_ms": float(np.percentile(rep.tpot_s, 50)) * 1e3,
            "itl_p95_ms": float(np.percentile(rep.tpot_s, 95)) * 1e3,
            "decode_steps": rep.n_decode_steps,
            "queue_first_quarter_s": float(np.mean([w for _, w in waits[:k]])),
            "queue_last_quarter_s": float(np.mean([w for _, w in waits[-k:]]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--saturate", type=int, default=0)
    ap.add_argument("--rate-shares", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--check-seeds", default="")
    args = ap.parse_args(argv)

    import jax
    from bench import check, harness, traffic

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.launch import compile_cache
    from repro.models import registry

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    cfg = harness.model_config(cell.conf, registry)
    mix = cell.mix

    srv = harness.server(cfg, harness.serving_params(cfg, cell, log=print),
                         mix, 0)
    t = time.monotonic()
    n = harness.warm_up(srv, cfg, mix)
    emit({"event": "setup", "warm_up_s": time.monotonic() - t, "shapes": n,
          "since_start_s": time.monotonic() - T_START})

    def window(srv, rate, seed):
        n = max(1, int(rate * args.seconds))
        pl = traffic.plan(mix, rate, n, cfg.vocab, seed, args.seconds)
        rep, wall = serve(srv, pl)
        return pl, rep, summary(pl, rep, wall, args.seconds)

    if args.saturate:
        pl = traffic.plan(mix, float("inf"), args.saturate, cfg.vocab, 0,
                          1.0)
        rep, wall = serve(srv, pl)
        sat = summary(pl, rep, wall, 0.0)
        emit({"event": "saturate", **sat})
        cell.data = dict(cell.data, knee_req_s=sat["req_per_s"])
        with open(os.path.join(harness.BENCH_DIR, "cells",
                               args.workload + ".json"), "w") as f:
            json.dump(cell.data, f, indent=1)
    knee = float(cell.data["knee_req_s"])
    for share in [float(x) for x in args.rate_shares.split(",") if x]:
        emit({"event": "rate", "share_of_knee": share, "rate": share * knee,
              **window(srv, share * knee, 0)[2]})
    served = []
    for s in [int(x) for x in args.check_seeds.split(",") if x]:
        pl, rep, summ = window(srv, traffic.offered_rate(mix, cell.data), s)
        served.append((s, pl, rep.outputs, summ))
    # the reference runs once the server's weights are freed, as in a run
    srv = rep = None
    gc.collect()
    for s, pl, outputs, summ in served:
        t = time.monotonic()
        picked = check.sample(pl, outputs, s, harness.CHECK_TOKENS)
        g = check.gaps(cell.conf, picked, outputs, control=CONTROL,
                       pad_to=traffic.max_context(mix))
        emit({"event": "check", "seed": s, **summ, **g,
              "requests_checked": len(picked),
              "reference_s": time.monotonic() - t})
    return 0


if __name__ == "__main__":
    sys.exit(main())
