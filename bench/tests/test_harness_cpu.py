"""The harness driven end to end on the CPU at the smoke preset, and the
data-driven lookup of cells, configurations, mixes and metrics."""
import json
import os
import shutil
import time

import numpy as np

from bench import harness

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_smoke_cell_end_to_end(smoke_cell, cpu_peaks, quiet_cache):
    r = harness.run_cell(smoke_cell, 2**31 + 7, 1.5, False,
                         time.monotonic(), log=lambda m: None,
                         require_tpu=False, peaks_file=cpu_peaks)
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert r["checks"]["mean_logit_gap"]["value"] <= smoke_cell.data[
        "limits"]["mean_logit_gap"]
    json.dumps(r)


def test_artifact_packs_once_and_boots_later_runs(smoke_cell, artifacts):
    """The first run packs the configuration's checkpoint and writes its
    artifact; a second boots from it with the same packed weights; a
    changed configuration gets an artifact of its own in its place."""
    import sys
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.models import registry

    cfg = harness.model_config(smoke_cell.conf, registry)
    logs = []
    a = harness.serving_params(cfg, smoke_cell, log=logs.append)
    assert any(m.startswith("pack_s=") for m in logs)
    (first,) = os.listdir(artifacts)
    logs.clear()
    b = harness.serving_params(cfg, smoke_cell, log=logs.append)
    assert not any(m.startswith("pack_s=") for m in logs)
    assert any(m.startswith("artifact_load_s=") for m in logs)
    for x, y in zip(a.layers, b.layers):
        assert (np.asarray(x["wq"].packed[0]["blocks"])
                == np.asarray(y["wq"].packed[0]["blocks"])).all()
    smoke_cell.conf["weight_seed"] += 1
    harness.serving_params(cfg, smoke_cell, log=logs.append)
    (second,) = os.listdir(artifacts)
    assert second != first and second.startswith("smoke-")


def test_every_named_piece_exists():
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.data["knee_req_s"] > 0 and "mean_logit_gap" in \
            cell.data["limits"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "itl_p95_ms"}
        assert cell.per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for c in spec["configs"]:
        conf = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert conf["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "references", conf["reference"] + ".py"))


def test_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix, a cell and a metric added as new
    files and entries are found with no code changed."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    bench = root / "bench"
    conf = harness.load_json(bench / "configs" / "yi-6b.json")
    conf["num_hidden_layers"] = 4
    (bench / "configs" / "yi-6b-4l.json").write_text(json.dumps(conf))
    mix = harness.load_json(bench / "traffic" / "chat.json")
    mix["rate_share_of_knee"] = 0.5
    (bench / "traffic" / "chat-light.json").write_text(json.dumps(mix))
    (bench / "cells" / "yi-6b-4l.chat-light.json").write_text(json.dumps(
        {"knee_req_s": 3.0, "limits": {"mean_logit_gap": 1.0}}))
    (bench / "metrics" / "requests_finished.py").write_text(
        "def read(run):\n    return run.report.n_requests\n")
    spec["configs"].append({"name": "yi-6b-4l", "source": "test",
                            "file": "bench/configs/yi-6b-4l.json",
                            "reduced": ["num_hidden_layers"], "why": "test"})
    spec["workloads"].append({"name": "yi-6b-4l.chat-light",
                              "config": "yi-6b-4l", "traffic": "chat-light",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "requests_finished", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serving loop",
        "moves": "itl_p95_ms", "workloads": ["yi-6b-4l.chat-light"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("yi-6b-4l.chat-light", root=str(root),
                             bench_dir=str(bench))
    assert cell.conf["num_hidden_layers"] == 4
    assert cell.mix["rate_share_of_knee"] == 0.5
    assert cell.data["knee_req_s"] == 3.0
    assert "requests_finished" in [m["name"] for m in cell.per_layer]
    other = harness.load_cell("yi-6b.chat", root=str(root),
                              bench_dir=str(bench))
    assert "requests_finished" not in [m["name"] for m in other.per_layer]
    read = harness.reader("requests_finished", bench_dir=str(bench))

    class Report:
        n_requests = 12

    class Run:
        report = Report()

    assert read(Run()) == 12
