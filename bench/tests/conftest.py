"""Shared set-up of the benchmark's CPU tests: the benchmark's modules on
the path, and a throwaway checkout root holding a tiny cell."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=512, torch_dtype="float32")


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout root with the real ``bench/`` files plus a tiny config,
    two tiny traffic mixes and their cells, and peaks for the CPU."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = root / "bench"
    cfg = json.loads((b / "configs" / "qwen3-0.6b.json").read_text())
    cfg["model"].update(TINY_MODEL)
    cfg["serving"]["page"] = 16
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tinyoff.json").write_text(json.dumps({
        "kind": "offline", "slots": 3, "requests": 10,
        "prompt": {"dist": "loguniform", "min": 40, "max": 300},
        "output": {"dist": "uniform", "min": 4, "max": 10}}))
    (b / "traffic" / "tinychat.json").write_text(json.dumps({
        "kind": "open_loop", "slots": 4, "rate_per_s": 20.0, "preroll_s": 0.3,
        "prompt": {"dist": "lognormal", "median": 60, "sigma": 1.0,
                   "min": 8, "max": 200},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 12}}))
    for cell in ("tiny.tinyoff", "tiny.tinychat"):
        (b / "cells" / f"{cell}.json").write_text(json.dumps({
            "mean_logit_gap": 1e-3, "sample_tokens": 30, "sample_requests": 4,
            "prompt_bucket": 256}))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["cpu"] = dict(next(iter(peaks.values())))
    (b / "peaks.json").write_text(json.dumps(peaks))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json", "reduced": [],
                            "why": "test"})
    spec["workloads"] += [
        {"name": "tiny.tinyoff", "config": "tiny", "traffic": "tinyoff",
         "chips": 1, "why": "test"},
        {"name": "tiny.tinychat", "config": "tiny", "traffic": "tinychat",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.tinyoff", "tiny.tinychat"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def cpu_jax(root, cell, platform):
    """``run.init_jax`` for the CPU tests: the platform check passes on the
    CPU and the process-wide compile cache settings are left alone."""
    import jax
    return jax
