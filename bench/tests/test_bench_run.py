"""Whole runs of a tiny cell on the CPU: the result line, the refusal to
run without a TPU, and a served token altered where it is produced
turning ``correct`` false."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import conftest
import harness
import run


def _main(monkeypatch, capsys, root, workload, trace=0, seed=31,
          records=None):
    monkeypatch.setattr(run, "init_jax", conftest.cpu_jax)
    if records is not None:
        measure = run.measure

        def keep(*args, **kw):
            result, rec = measure(*args, **kw)
            records.append(rec)
            return result, rec
        monkeypatch.setattr(run, "measure", keep)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", str(trace)], root=root,
                   platform="cpu")
    out, err = capsys.readouterr()
    return rc, json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("workload,trace", [("tiny.tinyoff", 0),
                                            ("tiny.tinychat", 1)])
def test_tiny_run_is_correct(monkeypatch, capsys, tiny_root, workload, trace):
    records = []
    rc, res, err = _main(monkeypatch, capsys, tiny_root, workload, trace,
                         records=records)
    assert rc == 0
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert err.strip().splitlines()[-1].startswith("check ")
    names = set(res["metrics"])
    if trace:
        assert "breakdown" in res and "busy_s" in res["device"]
        assert {"host_ms_per_step.chat", "mfu.chat"} <= names
        # the phase split of every traced run; the CPU's trace has no
        # device plane, so there is nothing to split
        ph = records[0]["phases"]
        assert ph is None or isinstance(ph["phase_s"], dict)
    else:
        assert {"prompt_tokens_per_s", "setup_s"} <= names
    for m in res["metrics"].values():
        assert m["value"] > 0


def test_altered_token_is_not_correct(monkeypatch, capsys, tiny_root):
    from repro.runtime import engine as engine_lib
    step = engine_lib.StemEngine._mixed_step
    done = {"n": 0}

    def altered(self):
        ran = step(self)
        for st in self.slots:
            if st is not None and len(st.tokens) == 3 and done["n"] == 0:
                st.tokens[-1] = (st.tokens[-1] + 1) % self.cfg.vocab_size
                done["n"] += 1
        return ran

    monkeypatch.setattr(engine_lib.StemEngine, "_mixed_step", altered)
    # Compare every finished request, so the altered one is among them.
    monkeypatch.setattr(harness, "pick_sample",
                        lambda finished, seed, tokens, n: [
                            p for p in finished if p[0].error is None])
    rc, res, _ = _main(monkeypatch, capsys, tiny_root, "tiny.tinyoff")
    assert done["n"] == 1
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > 1e-3


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(conftest.BENCH / "run.py"), "--workload",
         "qwen3-0.6b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[-1] \
        .startswith("{")
