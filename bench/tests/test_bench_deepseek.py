"""The DeepSeek-V2 family (``families/deepseek_v2.py``) at a tiny size on
the CPU, in float32: the plain reference matches every logit the engine
served (latent pages, head-shared Stem selection, dropless experts), the
float8 control lies far from both, the reference is pinned to recorded
logits, the weights round-trip into the program's layout, the head-sharing
rule is the program's, and the new cell, configuration and metrics are
found by name."""
from __future__ import annotations

import collections
import json
import shutil

import jax
import numpy as np
import pytest

import conftest
import harness
import run
import stem_reference
import traffic

DATA = conftest.BENCH / "tests" / "data"
CELL = "deepseek-v2-lite.longmix"
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            vocab_size=512, torch_dtype="float32")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout root with the real ``bench/`` files plus a tiny
    DeepSeek-V2 configuration, an offline mix and its cell."""
    root = tmp_path_factory.mktemp("ds") / "root"
    shutil.copytree(conftest.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = root / "bench"
    cfg = json.loads((b / "configs" / "deepseek-v2-lite.json").read_text())
    cfg["model"].update(TINY)
    cfg["serving"]["page"] = 16
    (b / "configs" / "dstiny.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tinymix.json").write_text(json.dumps({
        "kind": "offline", "slots": 3, "requests": 6,
        "prompt": {"dist": "loguniform", "min": 40, "max": 200},
        "output": {"dist": "uniform", "min": 4, "max": 10}}))
    (b / "cells" / "dstiny.tinymix.json").write_text(json.dumps({
        "mean_logit_gap": 1e-3, "sample_tokens": 30, "sample_requests": 3,
        "prompt_bucket": 256}))
    spec = json.loads((conftest.REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dstiny", "source": "test",
                            "file": "bench/configs/dstiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dstiny.tinymix", "config": "dstiny",
                              "traffic": "tinymix", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("dstiny.tinymix")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    peaks = json.loads((b / "peaks.json").read_text())
    peaks["cpu"] = dict(next(iter(peaks.values())))
    (b / "peaks.json").write_text(json.dumps(peaks))
    return root


@pytest.fixture(scope="module")
def served(root):
    """Serve the tiny mix through the engine, keeping the logits row behind
    every emitted token."""
    from repro import configs
    from repro.launch.serve import serving_policy
    from repro.models import registry
    from repro.runtime.engine import EngineConfig, Request, StemEngine

    cell = harness.Cell(root, "dstiny.tinymix")
    seed = 7
    bundle = registry.build(cell.program_config(configs))
    params = cell.family.program_params(cell.model,
                                        bundle.abstract_params()[0], seed)
    tr, rule = cell.traffic, cell.rule
    eng = StemEngine(bundle, params, serving_policy("stem", rule.page),
                     EngineConfig.for_trace(
                         max_slots=tr["slots"], max_prompt=tr["prompt"]["max"],
                         max_new_tokens=tr["output"]["max"],
                         page_size=rule.page, budget_frac=rule.budget_frac))
    items = traffic.generate(tr, seed, 1.0, cell.model["vocab_size"])
    unified, last = eng._unified, {}

    def keep(*args):
        out = unified(*args)
        dec, chunk = out[:2]
        last["dec"], last["chunk"] = np.asarray(dec), (
            None if chunk is None else np.asarray(chunk))
        return out

    eng._unified = keep
    for it in items:
        eng.submit(Request(uid=it.uid, prompt=it.prompt,
                           max_new_tokens=it.max_new))
    rows = collections.defaultdict(list)
    while eng.pending:
        held = {s: (st, len(st.tokens)) for s, st in enumerate(eng.slots)
                if st is not None}
        eng.step()
        fresh = {s: (st, 0) for s, st in enumerate(eng.slots)
                 if st is not None and id(st) not in
                 {id(v[0]) for v in held.values()}}
        for s, (st, n0) in {**fresh, **held}.items():
            if len(st.tokens) > n0:
                rows[st.req.uid].append(
                    last["chunk"][0] if n0 == 0 else last["dec"][s])
    done = {f.uid: f for f in eng.finished}
    w = jax.jit(lambda k: cell.family.canonical(cell.model, k))(
        stem_reference.jax_key(seed))
    return cell, w, eng.stats, [(it, done[it.uid].tokens,
                                 np.stack(rows[it.uid])) for it in items]


def _ref(cell, w, it, tokens, fp8=False):
    return cell.family.logits(cell.model, cell.rule, w, it.prompt, tokens,
                              kmax=cell.rule.prefill_bound(200),
                              prompt_bucket=256, fp8=fp8)


def test_reference_matches_served_logits(served):
    """float32 on both sides; absorbed against expanded attention and
    sorted expert groups against per-token tiles differ in summation order
    only."""
    cell, w, _, reqs = served
    for it, tokens, prog in reqs:
        ref = _ref(cell, w, it, tokens)
        np.testing.assert_allclose(prog[:, :ref.shape[1]], ref, atol=1e-4)
        assert harness.widest(cell.family.gaps(ref, tokens)) == 0.0


def test_fp8_control_is_far_from_the_program(served):
    cell, w, _, reqs = served
    prog_err = ctrl_err = 0.0
    for it, tokens, prog in reqs:
        ref = _ref(cell, w, it, tokens)
        low = _ref(cell, w, it, tokens, fp8=True)
        prog_err = max(prog_err, float(np.abs(prog[:, :ref.shape[1]] - ref).max()))
        ctrl_err = max(ctrl_err, float(np.abs(low - ref).max()))
    assert ctrl_err > 20 * prog_err


def test_engine_counts_experts(served):
    cell, _, stats, reqs = served
    assert stats["moe_experts_hit"] > 0
    assert stats["moe_max_expert_load"] >= stats["moe_experts_hit"] / 8


def test_deepseek_v2_logits_match_recorded(root):
    """Logits of two requests on the tiny configuration (weights from seed
    7; one with the float8 control), recorded from the reference when the
    family was added, come out the same."""
    cell = harness.Cell(root, "dstiny.tinymix")
    w = jax.jit(lambda k: cell.family.canonical(cell.model, k))(
        stem_reference.jax_key(7))
    rec = np.load(DATA / "deepseek_v2_tiny_logits.npz")
    for i in range(2):
        got = cell.family.logits(
            cell.model, cell.rule, w, rec[f"prompt{i}"], rec[f"served{i}"],
            kmax=cell.rule.prefill_bound(200), prompt_bucket=256,
            fp8=bool(rec[f"fp8{i}"]))
        np.testing.assert_allclose(got, rec[f"logits{i}"], rtol=1e-5,
                                   atol=1e-5)


def test_canonical_weights_reach_the_program_layout(root):
    from repro import configs
    from repro.models import registry

    cell = harness.Cell(root, "dstiny.tinymix")
    m, fam = cell.model, cell.family
    like = registry.build(cell.program_config(configs)).abstract_params()[0]
    p = fam.program_params(m, like, 3)
    w = jax.jit(lambda k: fam.canonical(m, k))(stem_reference.jax_key(3))
    h, r, dn = (m["num_attention_heads"], m["kv_lora_rank"],
                m["qk_nope_head_dim"])
    a0, a1 = p["segment0"]["sub0"]["attn"], p["segment1"]["sub0"]["attn"]
    np.testing.assert_array_equal(
        a0["wq"][0].reshape(m["hidden_size"], -1), w["q_proj"][0])
    np.testing.assert_array_equal(a1["w_dkv"][0], w["kv_a_proj"][1][:, :r])
    np.testing.assert_array_equal(a1["w_kr"][1], w["kv_a_proj"][2][:, r:])
    kvb = np.asarray(w["kv_b_proj"][2]).reshape(r, h, -1)
    np.testing.assert_array_equal(a1["w_uk"][1], kvb[..., :dn])
    np.testing.assert_array_equal(a1["w_uv"][1], kvb[..., dn:])
    f1 = p["segment1"]["sub0"]["ffn"]
    np.testing.assert_array_equal(f1["w_down"], w["expert_down"])
    np.testing.assert_array_equal(f1["shared"]["w_up"], w["shared_up"])
    np.testing.assert_array_equal(p["segment0"]["sub0"]["ffn"]["w_gate"],
                                  w["dense_gate"])
    np.testing.assert_array_equal(p["segment1"]["sub0"]["norm2"],
                                  w["mlp_norm"][1:])


def test_head_sharing_rule_is_the_programs():
    """The configuration's ``head_share``, the family's reference and the
    program's serving policy share a latent slot's pages the same way."""
    from repro.core import policy as policy_lib
    from repro.launch.serve import serving_policy

    cell = harness.Cell(conftest.REPO, CELL)
    pol = serving_policy(cell.config["serving"]["policy"], cell.rule.page)
    assert (cell.config["serving"]["head_share"] == cell.family.HEAD_SHARE
            == policy_lib.latent_group_reduce(pol))


def test_the_cell_and_its_metrics_are_found():
    cell = harness.Cell(conftest.REPO, CELL)
    assert cell.family.__name__.endswith("deepseek_v2")
    assert cell.chips == 1 and cell.traffic["slots"] == 8
    assert {m["name"] for m in cell.end_to_end} == {
        "prompt_tokens_per_s", "output_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {f"{g}.longmix" for g in (
        "device_idle_share", "host_ms_per_step", "mfu", "select_ms_per_step",
        "attend_ms_per_step", "dense_ms_per_step", "unscoped_ms_per_step",
        "moe_ms_per_step", "moe_roofline_share", "mla_decode_hbm_share")}
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
    spec = json.loads((conftest.REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}["deepseek-v2-lite"]
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.model["num_hidden_layers"] == 7
    assert cell.config["published"]["num_hidden_layers"] == 27


def _record(phase_s, unscoped_ops=(), **stats):
    cell = harness.Cell(conftest.REPO, CELL)
    steps = [{"t0": 0.0, "t1": 0.05, "decode": [20000, 40000, 60000],
              "chunks": [[30000, 4096, 256, False]]}] * 4
    return cell, {"model": cell.model, "family": cell.family,
                  "rule": cell.rule,
                  "peak": cell.peaks["TPU v5 lite"], "steps": steps,
                  "stats": {"step_calls": 4, **stats},
                  "phases": {"steps": 4, "phase_s": phase_s,
                             "group_s": {},
                             "unscoped_ops": list(unscoped_ops)}}


def test_roofline_shares_read_below_100_and_fall_silent_without_phases():
    cell, rec = _record({"stem.moe": 4 * 0.030, "stem.mla_decode": 4 * 0.004},
                        moe_experts_hit=4 * 6 * 64, moe_max_expert_load=0)
    moe = cell.reader("moe_roofline_share.longmix")(rec)
    mla = cell.reader("mla_decode_hbm_share.longmix")(rec)
    # 6 layers x 64 experts x 17.3 MB + routers and shared: ~7.1 GB a step
    # at 819 GB/s is ~8.7 ms of 30; 3 slots' kept latents ~0.56 GB ~0.7 ms.
    assert 20 < moe < 40 and 10 < mla < 25
    cell, rec = _record({})
    for name in ("moe_roofline_share.longmix", "mla_decode_hbm_share.longmix",
                 "moe_ms_per_step.longmix"):
        assert cell.reader(name)(rec) is None


def test_moe_time_counts_the_compilers_ragged_dot_kernels():
    """On the chip the routed experts' matmuls run as ``ragged-dot-*``
    kernels that lose their named scope, so the phase split counts them as
    unscoped (a traced run of the cell read ``stem.moe`` at 1.5 ms a step
    beside 37 ms of them).  The MoE readers add them to ``stem.moe``, and
    no other unscoped op."""
    ops = [["ragged-dot-none.3", 4 * 0.011], ["ragged-dot-none", 4 * 0.0017],
           ["dynamic-slice_bitcast_fusion.31", 4 * 0.0066]]
    cell, rec = _record({"stem.moe": 4 * 0.0015}, ops,
                        moe_experts_hit=4 * 6 * 64, moe_max_expert_load=0)
    ms = cell.reader("moe_ms_per_step.longmix")(rec)
    assert ms == pytest.approx(1.5 + 11 + 1.7)
    # ~8.7 ms of weight reads a step (as above) over 14.2 ms.
    assert 50 < cell.reader("moe_roofline_share.longmix")(rec) < 70


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_run_is_correct(monkeypatch, capsys, root, trace):
    """A whole run of the tiny cell through ``run.py``: correct, and the
    cell's metrics read (the CPU's trace has no device plane, so the phase
    metrics fall silent)."""
    monkeypatch.setattr(run, "init_jax", conftest.cpu_jax)
    rc = run.main(["--workload", "dstiny.tinymix", "--seed", str(2**31 + 9),
                   "--seconds", "2", "--trace", str(trace)], root=root,
                  platform="cpu")
    res = json.loads(capsys.readouterr()[0].strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True, res["checks"]
    names = set(res["metrics"])
    if trace:
        assert {"host_ms_per_step.longmix", "mfu.longmix"} <= names
    else:
        assert {"prompt_tokens_per_s", "output_tokens_per_s",
                "setup_s"} <= names
