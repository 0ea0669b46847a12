"""The comparison that decides ``correct``: its control and its faults.

The control is the reference computed one precision lower (float8) in the
program's place: at the same positions its first choices must read a gap
well over the program's. The faults break the timed path underneath a
whole harness run, which must then come out not correct: a token altered
where it is produced, and a decode step whose KV write-back is dropped
(the cache returned unchanged)."""
import time

import numpy as np
import pytest

from bench import check, harness, traffic

from .conftest import SMOKE_LIMIT


def _serve(cell, seed):
    import sys
    sys.path.insert(0, harness.ROOT + "/src")
    from repro.models import registry
    from repro.serve import Request

    cfg = harness.model_config(cell.conf, registry)
    sp = harness.pack(cfg, cell.conf, cell.conf["weight_seed"])
    srv = harness.server(cfg, sp, cell.mix, seed)
    planned = traffic.plan(cell.mix, float("inf"), 8, cfg.vocab, seed, 1.0)
    rep = srv.run([Request(p.rid, p.prompt, p.max_new_tokens, p.arrival)
                   for p in planned])
    return planned, rep.outputs


def test_control_reads_far_above_the_program(smoke_cell):
    # two checkpoints, each with its own traffic
    for seed in (3, 2**31 + 11):
        smoke_cell.conf["weight_seed"] = seed
        planned, outputs = _serve(smoke_cell, seed)
        picked = check.sample(planned, outputs, seed, 10**6)
        g = check.gaps(smoke_cell.conf, picked, outputs,
                       control="float8_e4m3fn")
        assert g["served_gap_mean"] <= SMOKE_LIMIT < g["control_gap_mean"]
        assert g["control_gap_mean"] > 3 * g["served_gap_mean"]
        assert g["control_off_share"] > g["served_off_share"]


def _faulted_run(cell, peaks, monkeypatch, fault):
    from repro.serve import batching, server

    if fault == "token":
        real = server.BatchServer._sample_row

        def altered(self, logits, key):
            out = real(self, logits, key)
            if out.shape[0] > 1:  # decode steps: every lane's token moves
                out = (out + 1) % self.cfg.vocab
            return out

        monkeypatch.setattr(server.BatchServer, "_sample_row", altered)
    elif fault == "state":
        monkeypatch.setattr(batching.PagedKVCache, "write_token",
                            lambda self, *a, **k: None)
    return harness.run_cell(cell, 2**31 + 29, 1.5, False, time.monotonic(),
                            log=lambda m: None, require_tpu=False,
                            peaks_file=peaks)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_timed_path_is_not_correct(fault, smoke_cell, cpu_peaks,
                                          quiet_cache, monkeypatch):
    r = _faulted_run(smoke_cell, cpu_peaks, monkeypatch, fault)
    assert r["correct"] is False
    assert r["checks"]["mean_logit_gap"]["value"] > SMOKE_LIMIT


def test_sample_holds_the_longest_request():
    P = traffic.Planned
    planned = [P(f"r{i}", np.zeros(4 + i, np.int32), 3, 0.0)
               for i in range(10)]
    outputs = {p.rid: np.zeros(3, np.int32) for p in planned}
    picked = check.sample(planned, outputs, 5, 7)
    assert picked[0].rid == "r9" and len(picked) == 3
    assert check.sample(planned, outputs, 5, 7) == picked
