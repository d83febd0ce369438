"""Compile-only checks against the TPU compiler, without a chip.

Interpret mode runs kernel bodies in Python and cannot see Mosaic's rules
(8x128 block tiling, lane-aligned stores, VMEM limits).  These tests
describe a v5e:2x2 topology and compile the serving main path for one of
its chips, at qwen3-0.6b attention widths (bf16, head_dim 128, block 128,
16 query / 8 KV heads), with kernels forced to ``interpret=False``.
Nothing runs; a compile that passes here is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time, and every pytest
worker imports this file.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import backend, configs
from repro.core import chunked as chunked_lib
from repro.kernels import block_sparse_attn, flash_attention, paged_attn
from repro.kernels import stem_metric
from repro.launch import steps as steps_lib
from repro.launch.serve import serving_policy
from repro.models import registry, transformer
from repro.runtime import sampling as sampling_lib
from repro.runtime.engine import EngineConfig

HQ, HK, D, BS, STRIDE = 16, 8, 128, 128, 4
SLOTS, MAXP = 4, 17                 # 4 slots x (2048 prompt + 32 decode)
PAGES = 1 + SLOTS * MAXP
LAYERS = 2                          # depth of the stacked pools
CHUNK = 2 * BS
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A chip compile written to the persistent cache cannot be read back
    # without a chip; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _kernels_named_under_phases(hlo: str):
    """Each Mosaic kernel of the compiled step keeps its stable name and
    the device phase that called it in its op_name, which is what a
    profiler trace of the chip reports for it."""
    calls = [l for l in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert calls
    for line in calls:
        op = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(r"/stem\.(score|attend)/stem_paged_\w+/", op), op


@pytest.mark.parametrize("lane", ["decode", "chunk"])
def test_score_pages_compiles(spec, lane):
    kg = spec((LAYERS, HK, PAGES, STRIDE, D), F32)
    if lane == "decode":
        fn = lambda q, kg, layer, pt: paged_attn.decode_page_scores(
            q, kg, layer, pt, group=HQ // HK, interpret=False)
        q, pt = spec((SLOTS, HQ, 1, D), BF16), spec((SLOTS, MAXP), I32)
    else:
        fn = lambda q, kg, layer, pt: paged_attn.chunk_page_scores(
            q, kg, layer, pt, block_size=BS, pooling="antidiag",
            group=HQ // HK, interpret=False)
        q, pt = spec((1, HQ, CHUNK, D), BF16), spec((1, MAXP), I32)
    _has_kernel(_compile(fn, q, kg, spec((), I32), pt))


@pytest.mark.parametrize("lane", ["decode", "chunk"])
def test_attend_pages_compiles(spec, lane):
    b, nc, rows, k_max = ((SLOTS, 1, 1, MAXP) if lane == "decode"
                          else (1, CHUNK // BS, BS, 2))
    pool = spec((LAYERS, HK, PAGES, BS, D), BF16)

    def fn(q, kp, vp, layer, gp, idx, cnt, pos):
        return paged_attn._attend_pages(
            q, kp, vp, layer, gp, idx, cnt, pos, block_size=BS,
            causal=lane == "chunk", interpret=False, name=f"attend_{lane}")
    _has_kernel(_compile(
        fn, spec((b, HQ, nc, rows, D), BF16), pool, pool, spec((), I32),
        spec((b, HQ, nc, k_max), I32), spec((b, HQ, nc, k_max), I32),
        spec((b, HQ, nc), I32), spec((b,), I32)))


@pytest.mark.parametrize("group_dedup", [False, True])
def test_block_sparse_attention_compiles(spec, group_dedup):
    n, k_max = 8192, 16
    h_sel = HK if group_dedup else HQ
    fn = lambda q, k, v, idx, mask: block_sparse_attn.block_sparse_attention(
        q, k, v, idx, mask, block_size=BS, group_dedup=group_dedup,
        interpret=False)
    _has_kernel(_compile(
        fn, spec((1, HQ, n, D), BF16), spec((1, HK, n, D), BF16),
        spec((1, HK, n, D), BF16), spec((1, h_sel, n // BS, k_max), I32),
        spec((1, h_sel, n // BS, k_max), jnp.bool_)))


@pytest.mark.parametrize("kernel", ["value_magnitude", "antidiag_pool",
                                    "flash_attention"])
def test_dense_kernels_compile(spec, kernel):
    x = spec((1, HK, 2048, D), BF16)
    if kernel == "value_magnitude":
        fn = lambda v: stem_metric.value_magnitude(v, block_size=BS,
                                                   interpret=False)
        args = (x,)
    elif kernel == "antidiag_pool":
        fn = lambda v: stem_metric.antidiag_pool(v, block_size=BS,
                                                 stride=STRIDE,
                                                 interpret=False)
        args = (x,)
    else:
        fn = lambda q, k, v: flash_attention.flash_attention(
            q, k, v, interpret=False)
        args = (spec((1, HQ, 2048, D), BF16), x, x)
    _has_kernel(_compile(fn, *args))


UNIFIED_CASES = [("xla", "sync"), ("pallas", "sync"), ("pallas", "async")]


def _unified_step(spec, monkeypatch, executor, loop, *, layers, slots,
                  max_prompt, max_new_tokens, donate=False,
                  arch="qwen3-0.6b"):
    """The engine's unified step at ``arch``'s width with the depth cut to
    ``layers``, jitted for the described chip, and its abstract arguments
    for both signatures: ``(step, args of the mixed step, args of the
    decode-only step, the abstract pools)``.  ``donate`` donates the pools
    (and the async loop's token buffer) as the engine does.  This
    process's backend is the CPU, so the pallas case steers the kernels
    to Mosaic here."""
    if executor == "pallas":
        monkeypatch.setattr(backend, "interpret_kernels", lambda: False)
    cfg = configs.get_config(arch).replace(num_layers=layers)
    bundle = registry.build(cfg)
    pol = serving_policy("stem", BS)
    ecfg = EngineConfig.for_trace(max_slots=slots, max_prompt=max_prompt,
                                  max_new_tokens=max_new_tokens, page_size=BS)
    P = ecfg.max_pages_per_slot

    def shaped(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)
    params = shaped(jax.eval_shape(bundle.init_params,
                                   jax.random.PRNGKey(0)))
    pools = shaped(jax.eval_shape(
        lambda: transformer.init_page_pools(cfg, ecfg.num_pages, pol)))
    chunk = {"tokens": spec((1, CHUNK), I32), "page_table": spec((1, P), I32),
             "start": spec((1,), I32), "true_len": spec((1,), I32),
             "budgets": spec((1, CHUNK // BS), I32), "last": spec((1,), I32)}
    sampler = None
    if loop == "async":
        sampler = sampling_lib.get_sampler("greedy")
        chunk.update(slot=spec((1,), I32), emit=spec((1,), jnp.bool_))
        lead = (spec((slots,), I32), spec((slots,), jnp.bool_))
    else:
        lead = (spec((slots, 1), I32),)
    step = jax.jit(steps_lib.make_unified_step(
        bundle, stem_cfg=pol, budget_frac=0.5,
        chunk_k_max=chunked_lib.chunk_budget_bound(pol, P),
        executor=executor, sampler=sampler),
        donate_argnums=(((1, 2) if loop == "async" else (1,)) if donate
                        else ()))
    args = (params, pools) + lead + (spec((slots, P), I32),
                                     spec((slots,), I32))
    return step, args + (chunk,), args + (None,), pools


@pytest.mark.parametrize("executor,loop", UNIFIED_CASES)
def test_unified_step_compiles(spec, monkeypatch, executor, loop):
    """The engine's unified step, both signatures (mixed and decode-only),
    at qwen3-0.6b width with the depth cut to 2 layers, for the serving
    geometry chip_smoke.py runs (4 slots, 2048 + 32 tokens, 256-token
    chunks)."""
    step, mixed, decode, pools = _unified_step(
        spec, monkeypatch, executor, loop, layers=LAYERS, slots=SLOTS,
        max_prompt=2048, max_new_tokens=32)
    assert jax.tree.leaves(pools)[0].shape[2:4] == (PAGES, BS)
    assert mixed[-3].shape == (SLOTS, MAXP)
    for args in (mixed, decode):
        compiled = step.lower(*args).compile()
        if executor == "pallas":
            _has_kernel(compiled)
            _kernels_named_under_phases(compiled.as_text())
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# Result shape and opcode of one HLO instruction:
# ``%name = f32[4,8,292]{2,1,0:T(8,128)} opcode(``.
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _pool_plumbing(hlo: str, leaf_shapes) -> list:
    """Instructions of ``hlo`` that copy, slice or restack a whole pool
    leaf: a ``copy``, ``dynamic-slice`` or ``dynamic-update-slice``, or a
    fusion XLA named after one (``copy*``, ``*dynamic*slice*``), whose
    result has a leaf's shape.  In-place scatters into the stack are
    fusions of another name; the asynchronous memory-space moves
    (``copy-start``/``copy-done``) are other opcodes and not counted."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        shape = tuple(int(x) for x in dims.split(",")) if dims else ()
        plumbing = (op in ("copy", "dynamic-slice", "dynamic-update-slice")
                    or (op == "fusion"
                        and re.match(r"copy|.*dynamic.*slice", name)))
        if plumbing and shape in leaf_shapes:
            found.append(f"{op} {name} {shape}")
    return found


@pytest.mark.parametrize("executor,loop", UNIFIED_CASES)
def test_unified_step_updates_pools_in_place(spec, monkeypatch, executor,
                                             loop):
    """The unified step, compiled with its pools donated as the engine
    donates them, writes and reads each layer in the carried stack: no op
    copies, slices or restacks a pool leaf (k, v, kg, vm; stacked or one
    layer's), and the step's scratch memory stays below the pools' bytes.
    Pools of 632 MB (4 layers, 3 slots x 97 pages) are too large for the
    compiler to stage them in on-chip memory, as a deployment's are."""
    step, mixed, decode, pools = _unified_step(
        spec, monkeypatch, executor, loop, layers=4, slots=3,
        max_prompt=12288, max_new_tokens=128, donate=True)
    leaves = jax.tree.leaves(pools)
    shapes = {l.shape for l in leaves} | {l.shape[1:] for l in leaves}
    pool_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    for args in (mixed, decode):
        compiled = step.lower(*args).compile()
        assert not _pool_plumbing(compiled.as_text(), shapes)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < pool_bytes
        assert mem.alias_size_in_bytes >= pool_bytes


def test_latent_step_updates_pools_in_place(spec, monkeypatch):
    """deepseek-v2-lite's unified step (MLA over latent pages, dropless
    experts) at its published widths with the depth cut to 3 (the dense
    layer and two MoE layers), pools donated: no op copies, slices or
    restacks a latent pool leaf (kv, kg, vm; stacked or one layer's), the
    step's scratch stays below the pools' bytes, and the mixed step's ops
    carry the ``stem.mla_decode``, ``stem.mla_chunk`` and ``stem.moe``
    phases.  Pools of 2.2 GB in the benchmark cell's geometry (8 slots x
    516 pages of 64k-token requests); at 4 slots the compiler copies the
    stack around the decode write instead.  The ``vm`` leaf (one float a
    page) is left out: XLA relays out its unit head axis with a copy of
    that size."""
    step, mixed, decode, pools = _unified_step(
        spec, monkeypatch, "xla", "sync", layers=3, slots=8,
        max_prompt=65536, max_new_tokens=512, donate=True,
        arch="deepseek-v2-lite")
    leaves = jax.tree.leaves(pools)
    assert leaves[0].shape[-1] == 640          # 512 c_kv + 64 k_rope, padded
    big = [l for l in leaves if l.ndim > 3]    # kv and kg, not vm
    shapes = {l.shape for l in big} | {l.shape[1:] for l in big}
    pool_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
    for args in (mixed, decode):
        compiled = step.lower(*args).compile()
        hlo = compiled.as_text()
        assert not _pool_plumbing(hlo, shapes)
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes < pool_bytes
        assert mem.alias_size_in_bytes >= pool_bytes
        phases = ("stem.mla_decode", "stem.moe") + (
            ("stem.mla_chunk",) if args is mixed else ())
        assert all(p in hlo for p in phases)
