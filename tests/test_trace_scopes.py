"""Named device-phase scopes and engine host spans (the program's own
trace instrumentation).

The unified step carries ``jax.named_scope`` phases so every op of the
compiled step is owned by one phase in a profiler trace, and every Pallas
kernel keeps a stable name; the engine writes ``engine.*`` host spans onto
the profiler's clock.  These tests lower both step signatures under both
paged executors and read the scopes back from the HLO metadata, then run
the engine under a CPU profiler trace and read the spans back from it.
"""
import collections
import dataclasses
import glob
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import chunked as chunked_lib
from repro.core import policy as policy_lib
from repro.core.config import StemConfig
from repro.launch import steps as steps_lib
from repro.models import registry, transformer
from repro.runtime import sampling as sampling_lib
from repro.runtime.engine import EngineConfig, Request, StemEngine

TINY = ArchConfig(
    name="scopes-tiny", family="dense", num_layers=2, d_model=32,
    num_heads=4, num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
    qk_norm=True, dtype="float32",
)
STEM = StemConfig(block_size=8, sink_blocks=1, local_blocks=1,
                  min_budget_blocks=2, stride=4)
SLOTS, MAXP, CHUNK = 2, 4, 16
I32 = jnp.int32

PHASES = {"stem.embed", "stem.qkv", "stem.kv_write", "stem.score",
          "stem.select", "stem.attend", "stem.o_proj", "stem.mlp",
          "stem.head", "stem.sample"}
# Every pl.pallas_call the paged executors make, by its stable name.
KERNELS = {"xla": set(),
           "pallas": {"stem_paged_decode_score", "stem_paged_decode_attend",
                      "stem_paged_chunk_score", "stem_paged_chunk_attend"}}
ENGINE_SPANS = {"engine.admit", "engine.schedule", "engine.inputs",
                "engine.dispatch", "engine.wait", "engine.emit"}


def _step_args(sampled: bool, mixed: bool):
    sd = jax.ShapeDtypeStruct
    chunk = None
    if mixed:
        chunk = {"tokens": sd((1, CHUNK), I32), "page_table": sd((1, MAXP), I32),
                 "start": sd((1,), I32), "true_len": sd((1,), I32),
                 "budgets": sd((1, CHUNK // STEM.block_size), I32),
                 "last": sd((1,), I32)}
        if sampled:
            chunk.update(slot=sd((1,), I32), emit=sd((1,), jnp.bool_))
    lead = ((sd((SLOTS,), I32), sd((SLOTS,), jnp.bool_)) if sampled
            else (sd((SLOTS, 1), I32),))
    return lead + (sd((SLOTS, MAXP), I32), sd((SLOTS,), I32), chunk)


@pytest.fixture(scope="module")
def lowered():
    """{(executor, sampled, mixed): (jitted step, its abstract args)}."""
    bundle = registry.build(TINY)
    pol = policy_lib.as_policy(STEM)
    params = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    pools = jax.eval_shape(
        lambda: transformer.init_page_pools(TINY, 1 + SLOTS * MAXP, pol))
    out = {}
    for executor in ("xla", "pallas"):
        for sampled in (False, True):
            step = jax.jit(steps_lib.make_unified_step(
                bundle, stem_cfg=pol, budget_frac=0.5,
                chunk_k_max=chunked_lib.chunk_budget_bound(pol, MAXP),
                executor=executor,
                sampler=sampling_lib.get_sampler("greedy") if sampled
                else None))
            for mixed in (False, True):
                out[executor, sampled, mixed] = (
                    step, (params, pools) + _step_args(sampled, mixed))
    return out


CASES = [(e, s, m) for e in ("xla", "pallas") for s in (False, True)
         for m in (False, True)]


def _case_id(case):
    e, s, m = case
    return f"{e}-{'sampled' if s else 'logits'}-{'mixed' if m else 'decode'}"


def _ops_with_scope(hlo: str):
    """[(opcode, op_name)] of every instruction in HLO text."""
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= \S+ ([\w-]+)\(", line)
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), on.group(1) if on else ""))
    return out


def _phase(op_name: str):
    parts = [p for p in op_name.split("/") if p in PHASES]
    return parts[-1] if parts else None


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_every_matmul_lies_under_a_phase(lowered, case):
    """Every phase the signature runs appears in the HLO metadata, and no
    dot, convolution or custom call is left outside a phase."""
    executor, sampled, mixed = case
    step, args = lowered[case]
    hlo = step.lower(*args).as_text(dialect="hlo", debug_info=True)
    ops = _ops_with_scope(hlo)
    names = "\n".join(n for _, n in ops)
    want = PHASES - ({"stem.sample"} if not sampled else set())
    if executor == "pallas":
        # the fused path's metric is computed in-kernel: its score phase
        # holds no matmul but still owns the kernel's ops
        assert "stem.score/stem_paged_decode_score" in names
    missing = {p for p in want if f"/{p}/" not in names}
    assert not missing, f"phases absent from the step's HLO: {missing}"
    assert "stem.decode_lane/" in names
    assert ("stem.chunk_lane/" in names) == mixed
    heavy = collections.Counter(
        op for op, n in ops
        if op in ("dot", "convolution", "custom-call") and _phase(n) is None)
    assert not heavy, f"matmuls or custom calls outside every phase: {heavy}"


@pytest.mark.parametrize("executor", ["xla", "pallas"])
def test_pallas_calls_carry_stable_names(lowered, executor):
    """Each pl.pallas_call in the step keeps its own stable ``name``, so a
    kernel is found by name in a trace after a refactor."""
    step, args = lowered[executor, False, True]
    jaxpr = jax.make_jaxpr(step)(*args)

    def names(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                yield getattr(name, "name", name)
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr
                    if hasattr(sub, "eqns"):
                        yield from names(sub)
    assert set(names(jaxpr.jaxpr)) == KERNELS[executor]


# -- engine host spans ------------------------------------------------------

def _host_spans(trace_dir: str):
    """[(name, start_ns, end_ns)] of every ``engine.*`` host event."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _requests(arrival_step=0):
    rng = np.random.RandomState(3)
    return [Request(uid=u, prompt=rng.randint(0, TINY.vocab_size, size=(n,))
                    .astype(np.int32), max_new_tokens=3,
                    arrival_step=arrival_step)
            for u, n in enumerate((5, 13, 9))]


@pytest.mark.parametrize("async_depth", [0, 1])
def test_engine_spans_nest_in_step(tmp_path, async_depth):
    """Every engine span of a profiled run lies inside an ``engine.step``
    span; the async loop wraps each wait and emit in ``engine.reconcile``."""
    bundle = registry.build(TINY)
    params = bundle.init_params(jax.random.PRNGKey(0))
    eng = StemEngine(bundle, params, STEM, EngineConfig(
        max_slots=2, num_pages=1 + 2 * 3, max_pages_per_slot=3,
        budget_frac=0.5, async_depth=async_depth))
    eng.run(_requests())                        # compile outside the trace
    eng.reset_metrics()
    for r in _requests():
        eng.submit(dataclasses.replace(r, uid=r.uid + 10))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    steps = [(s, e) for n, s, e in spans if n == "engine.step"]
    assert len(steps) == 4
    seen = {n for n, _, _ in spans}
    want = ENGINE_SPANS | ({"engine.reconcile"} if async_depth else set())
    assert want <= seen, f"spans never written: {want - seen}"
    for n, s, e in spans:
        assert any(s0 <= s and e <= e0 for s0, e0 in steps), (
            f"{n} [{s}, {e}] lies outside every engine.step")
    if async_depth:
        rec = [(s, e) for n, s, e in spans if n == "engine.reconcile"]
        for n, s, e in spans:
            if n in ("engine.wait", "engine.emit"):
                assert any(s0 <= s and e <= e0 for s0, e0 in rec), (
                    f"async {n} outside every engine.reconcile")


def test_admission_counters():
    """``admissions`` counts new admissions and ``queue_wait_s`` sums their
    waits from submission (a request schedulable at once) or from the
    first step it could be scheduled (a later arrival step)."""
    bundle = registry.build(TINY)
    params = bundle.init_params(jax.random.PRNGKey(0))
    eng = StemEngine(bundle, params, STEM, EngineConfig(
        max_slots=1, num_pages=1 + 3, max_pages_per_slot=3))
    reqs = _requests()
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(r)
    late = dataclasses.replace(_requests(arrival_step=2)[0], uid=99)
    eng.submit(late)
    fin = eng.run(max_steps=200)
    t1 = time.perf_counter()
    assert eng.stats["admissions"] == len(reqs) + 1
    queued = [f.queue_s for f in fin]
    assert eng.stats["queue_wait_s"] == pytest.approx(sum(queued), rel=1e-9)
    # one slot: the first request waits for nothing but admission, the
    # others queue behind it, and no wait predates the submissions
    assert all(0.0 <= q <= t1 - t0 for q in queued)
    assert sorted(queued)[-1] > 0.0
    eng.reset_metrics()
    assert eng.stats["admissions"] == 0 and eng.stats["queue_wait_s"] == 0.0
