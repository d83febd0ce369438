"""The plain reference against the serving program at a tiny size on the
CPU, both in float32: every logit the engine computed for a served token
matches.  The float8 control lies far from both.  The Qwen family's
reference computes, bit for bit, the logits recorded from the reference
before it was split into a family module and ``stem_reference.py``."""
from __future__ import annotations

import collections
import dataclasses

import jax
import numpy as np
import pytest

import conftest
import harness
import stem_reference
import traffic

DATA = conftest.BENCH / "tests" / "data"


@pytest.fixture(scope="module")
def served(tiny_root):
    """Serve a few requests through the engine, keeping the logits row
    behind every emitted token."""
    from repro import configs
    from repro.launch.serve import serving_policy
    from repro.models import registry
    from repro.runtime.engine import EngineConfig, Request, StemEngine

    cell = harness.Cell(tiny_root, "tiny.tinyoff")
    seed = 5
    bundle = registry.build(cell.program_config(configs))
    params = cell.family.program_params(cell.model,
                                        bundle.abstract_params()[0], seed)
    tr, rule = cell.traffic, cell.rule
    eng = StemEngine(bundle, params, serving_policy("stem", rule.page),
                     EngineConfig.for_trace(
                         max_slots=tr["slots"], max_prompt=tr["prompt"]["max"],
                         max_new_tokens=tr["output"]["max"],
                         page_size=rule.page, budget_frac=rule.budget_frac))
    items = traffic.generate(tr, seed, 1.0, cell.model["vocab_size"])[:6]
    unified, last = eng._unified, {}

    def keep(*args):
        dec, chunk, pools = unified(*args)
        last["dec"], last["chunk"] = np.asarray(dec), (
            None if chunk is None else np.asarray(chunk))
        return dec, chunk, pools

    eng._unified = keep
    for it in items:
        eng.submit(Request(uid=it.uid, prompt=it.prompt,
                           max_new_tokens=it.max_new))
    rows = collections.defaultdict(list)
    while eng.pending:
        held = {s: (st, len(st.tokens)) for s, st in enumerate(eng.slots)
                if st is not None}
        eng.step()
        now = {s: (st, 0) for s, st in enumerate(eng.slots)
               if st is not None and id(st) not in
               {id(v[0]) for v in held.values()}}
        for s, (st, n0) in {**now, **held}.items():
            if len(st.tokens) > n0:
                rows[st.req.uid].append(
                    last["chunk"][0] if n0 == 0 else last["dec"][s])
    done = {f.uid: f for f in eng.finished}
    w = jax.jit(lambda k: cell.family.canonical(cell.model, k))(
        stem_reference.jax_key(seed))
    return cell, w, [(it, done[it.uid].tokens, np.stack(rows[it.uid]))
                     for it in items]


def _ref(cell, w, it, tokens, rule=None, fp8=False):
    return cell.family.logits(cell.model, rule or cell.rule, w, it.prompt,
                              tokens, kmax=cell.rule.prefill_bound(300),
                              prompt_bucket=256, fp8=fp8)


def test_reference_matches_served_logits(served):
    cell, w, reqs = served
    for it, tokens, prog in reqs:
        ref = _ref(cell, w, it, tokens)
        np.testing.assert_allclose(prog[:, :ref.shape[1]], ref, atol=2e-5)
        assert harness.widest(cell.family.gaps(ref, tokens)) == 0.0


def test_reference_sees_the_decode_budget(served):
    """A dense decode rule computes other logits: the comparison covers
    the page selection, not only the projections."""
    cell, w, reqs = served
    dense = dataclasses.replace(cell.rule, budget_frac=1.0)
    err = max(float(np.abs(prog[:, :cell.model["vocab_size"]]
                           - _ref(cell, w, it, tokens, dense)).max())
              for it, tokens, prog in reqs)
    assert err > 1e-2


def test_fp8_control_is_far_from_the_program(served):
    cell, w, reqs = served
    prog_err = ctrl_err = 0.0
    for it, tokens, prog in reqs:
        ref = _ref(cell, w, it, tokens)
        low = _ref(cell, w, it, tokens, fp8=True)
        prog_err = max(prog_err, float(np.abs(prog[:, :ref.shape[1]] - ref).max()))
        ctrl_err = max(ctrl_err, float(np.abs(low - ref).max()))
    assert ctrl_err > 100 * prog_err


def test_qwen_dense_logits_match_recorded(tiny_root):
    """Logits of four requests on the tiny configuration (weights from seed
    5; one with the float8 control), recorded from the reference as it
    stood before the Qwen family moved into ``families/qwen_dense.py``,
    come out bit for bit the same."""
    cell = harness.Cell(tiny_root, "tiny.tinyoff")
    w = jax.jit(lambda k: cell.family.canonical(cell.model, k))(
        stem_reference.jax_key(5))
    rec = np.load(DATA / "qwen_dense_tiny_logits.npz")
    for i in range(4):
        got = cell.family.logits(
            cell.model, cell.rule, w, rec[f"prompt{i}"], rec[f"served{i}"],
            kmax=cell.rule.prefill_bound(300), prompt_bucket=256,
            fp8=bool(rec[f"fp8{i}"]))
        np.testing.assert_array_equal(got, rec[f"logits{i}"])
