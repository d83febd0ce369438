"""A new cell, configuration, traffic mix and metric are found by name from
new files plus a BENCHMARK.json entry, with no existing file edited."""
from __future__ import annotations

import hashlib
import json

import conftest
import harness


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    root = conftest.make_root(tmp_path)
    before = _digests(root)
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["model"]["num_hidden_layers"] = 3
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (b / "traffic" / "burst.json").write_text(json.dumps({
        "kind": "open_loop", "slots": 2, "rate_per_s": 50.0,
        "prompt": {"dist": "uniform", "min": 8, "max": 9},
        "output": {"dist": "uniform", "min": 2, "max": 3}}))
    (b / "cells" / "throwaway.burst.json").write_text(json.dumps({
        "mean_logit_gap": 0.5, "sample_tokens": 1, "sample_requests": 1,
        "prompt_bucket": 64}))
    (b / "metrics" / "steps_seen.py").write_text(
        "def read(rec):\n    return float(len(rec['steps']))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "throwaway", "source": "test",
                            "file": "bench/configs/throwaway.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway.burst", "config": "throwaway",
                              "traffic": "burst", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine loop", "moves": "setup_s",
                              "workloads": ["throwaway.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.Cell(root, "throwaway.burst")
    assert cell.model["num_hidden_layers"] == 3
    assert cell.traffic["rate_per_s"] == 50.0
    assert cell.check["mean_logit_gap"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert cell.reader("steps_seen")({"steps": [1, 2]}) == 2.0
    after = _digests(root)
    assert {k: after[k] for k in before} == before


def test_every_named_metric_has_a_reader():
    spec = json.loads((conftest.REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.Cell(conftest.REPO, w["name"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.reader(m["name"]))
        assert (conftest.BENCH / "cells" / f"{w['name']}.json").exists()
