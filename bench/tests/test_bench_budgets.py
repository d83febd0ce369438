"""The benchmark's copy of Stem's budget rule agrees with the program's
policy for every cell's settings, and the Qwen family's FLOP count adds
up."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import stem_rule
from conftest import BENCH

qwen_dense = harness.family(BENCH, "qwen_dense")


def _configs():
    return sorted((BENCH / "configs").glob("*.json"))


@pytest.mark.parametrize("path", _configs(), ids=lambda p: p.stem)
def test_prefill_budgets_match_policy(path):
    from repro.launch.serve import serving_policy
    cfg = json.loads(path.read_text())
    rule = stem_rule.StemRule.from_config(cfg["serving"])
    pol = serving_policy(cfg["serving"]["policy"], rule.page)
    for pages in (1, 2, 3, 17, 64, 127, 128, 129, 200, 254, 255):
        want = pol.prefill_budgets(pages * rule.page)
        np.testing.assert_array_equal(rule.prefill_budgets(pages * rule.page),
                                      want, err_msg=f"{pages} pages")


@pytest.mark.parametrize("path", _configs(), ids=lambda p: p.stem)
def test_decode_budgets_match_policy(path):
    from repro.launch.serve import serving_policy
    cfg = json.loads(path.read_text())
    rule = stem_rule.StemRule.from_config(cfg["serving"])
    pol = serving_policy(cfg["serving"]["policy"], rule.page)
    n = np.arange(1, 300)
    forced = np.minimum(n, rule.sink + rule.local)
    want = np.asarray(pol.schedule.decode_budgets(
        jnp.asarray(n), jnp.asarray(forced), rule.budget_frac))
    np.testing.assert_array_equal(rule.decode_budget(n), np.minimum(want, n))


def test_prefill_bound_matches_chunk_bound():
    from repro.core import chunked
    from repro.launch.serve import serving_policy
    rule = stem_rule.StemRule()
    pol = serving_policy("stem", rule.page)
    for max_prompt in (384, 4096, 32512):
        pages = -(-max_prompt // rule.page)
        assert rule.prefill_bound(max_prompt) == chunked.chunk_budget_bound(
            pol, pages)


def test_linear_params_match_program_tree():
    import jax
    from repro import configs
    from repro.models import registry
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    like = registry.build(configs.get_config("qwen3-0.6b")).abstract_params()[0]
    layer = like["segment0"]["sub0"]
    mats = [x for x in jax.tree.leaves({k: layer[k] for k in ("attn", "ffn")})
            if len(x.shape) > 2]
    assert qwen_dense.linear_params(cfg["model"]) == sum(
        int(np.prod(x.shape)) for x in mats)


def test_chunk_flops_count_real_tokens_and_kept_keys():
    model = {"num_hidden_layers": 1, "hidden_size": 8,
             "num_attention_heads": 2, "num_key_value_heads": 1,
             "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
             "tie_word_embeddings": True, "torch_dtype": "float32"}
    rule = stem_rule.StemRule(page=4, stride=2)
    lin = 2.0 * qwen_dense.linear_params(model)
    # A 6-token prompt, one chunk of 8: rows 0 and 1, budgets [1, 2].
    got = qwen_dense.chunk_flops(model, rule, 6, 0, 8, completes=True)
    keys = [1, 2, 3, 4] + [4 + 1, 4 + 2]
    score = (1 + 2) * rule.stride * 2.0 * 4 * 2
    want = lin * 6 + 4.0 * 2 * 4 * sum(keys) + score + 2.0 * 8 * 10
    assert got == pytest.approx(want)
    # A decode token at position 9 (3 valid pages, budget 2): page 0 and
    # its own page up to position 9.
    assert qwen_dense.decode_flops(model, rule, 9) == pytest.approx(
        lin + 2.0 * 8 * 10 + 2 * (4.0 * 4 * (4 + 2) + 2.0 * 4 * 3))
