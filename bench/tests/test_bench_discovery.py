"""A new cell, configuration, model family, traffic mix and metric are
found by name from new files plus BENCHMARK.json entries, and run, with no
existing file edited; a configuration must name a family that exists."""
from __future__ import annotations

import hashlib
import json
import re

import pytest

import conftest
import harness
import run


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


# Other names for the Qwen family's published keys, and the Qwen family
# under them: a family the harness has never seen, which counts each of its
# functions it is called through.
KEYS = {"n_layer": "num_hidden_layers", "n_embd": "hidden_size",
        "n_head": "num_attention_heads", "n_kv_head": "num_key_value_heads",
        "d_head": "head_dim", "d_mlp": "intermediate_size",
        "n_vocab": "vocab_size"}
RENAMED_FAMILY = f'''
import collections
import pathlib

import harness

QWEN = harness.family(pathlib.Path(__file__).parents[1], "qwen_dense")
KEYS = {KEYS!r}
CALLED = collections.Counter()


def _qwen(model):
    return {{KEYS.get(k, k): v for k, v in model.items()}}


def program_config(model, base):
    CALLED["program_config"] += 1
    return QWEN.program_config(_qwen(model), base)


def vocab(model):
    return model["n_vocab"]


def canonical(model, key):
    return QWEN.canonical(_qwen(model), key)


def program_params(model, like, seed):
    CALLED["program_params"] += 1
    return QWEN.program_params(_qwen(model), like, seed)


def logits(model, rule, w, prompt, served, **kw):
    CALLED["logits"] += 1
    return QWEN.logits(_qwen(model), rule, w, prompt, served, **kw)


gaps = QWEN.gaps


def step_flops(model, rule, step):
    CALLED["step_flops"] += 1
    return QWEN.step_flops(_qwen(model), rule, step)


def step_bytes(model, rule, step):
    return QWEN.step_bytes(_qwen(model), rule, step)
'''


def test_new_family_runs_end_to_end(monkeypatch, capsys, tmp_path):
    root = conftest.make_root(tmp_path)
    before = _digests(root)
    b = root / "bench"
    (b / "families" / "renamed_qwen.py").write_text(RENAMED_FAMILY)
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    renamed = {v: k for k, v in KEYS.items()}
    cfg["model"] = {renamed.get(k, k): v for k, v in cfg["model"].items()}
    cfg["family"] = "renamed_qwen"
    (b / "configs" / "renamed.json").write_text(json.dumps(cfg))
    (b / "traffic" / "pairs.json").write_text(json.dumps({
        "kind": "offline", "slots": 2, "requests": 4,
        "prompt": {"dist": "uniform", "min": 20, "max": 40},
        "output": {"dist": "uniform", "min": 3, "max": 5}}))
    (b / "cells" / "renamed.pairs.json").write_text(json.dumps({
        "mean_logit_gap": 1e-3, "sample_tokens": 10, "sample_requests": 2,
        "prompt_bucket": 64}))
    (b / "metrics" / "mfu.pairs.py").write_text(
        "from readers import mfu as read  # noqa: F401\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "renamed", "source": "test",
                            "file": "bench/configs/renamed.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "renamed.pairs", "config": "renamed",
                              "traffic": "pairs", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "mfu.pairs", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "unified step",
                              "moves": "setup_s",
                              "workloads": ["renamed.pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    records = []
    measure = run.measure

    def keep(*args, **kw):
        result, rec = measure(*args, **kw)
        records.append(rec)
        return result, rec

    monkeypatch.setattr(run, "init_jax", conftest.cpu_jax)
    monkeypatch.setattr(run, "measure", keep)
    assert run.main(["--workload", "renamed.pairs", "--seed",
                     str(2 ** 33 + 5), "--seconds", "1", "--trace", "1"],
                    root=root, platform="cpu") == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert list(res["metrics"]) == ["mfu.pairs"]
    assert res["metrics"]["mfu.pairs"]["value"] > 0
    called = records[0]["family"].CALLED
    assert {"program_config", "program_params", "logits", "step_flops"} \
        <= {k for k, n in called.items() if n}
    after = _digests(root)
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_config_must_name_an_existing_family(tmp_path, family):
    root = conftest.make_root(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    del cfg["family"]
    if family:
        cfg["family"] = family
    path.write_text(json.dumps(cfg))
    want = (path if family is None
            else root / "bench" / "families" / f"{family}.py")
    with pytest.raises((ValueError, FileNotFoundError),
                       match=re.escape(str(want))):
        harness.Cell(root, "tiny.tinyoff")
