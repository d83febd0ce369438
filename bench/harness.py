"""One run of one cell: build the serving engine from the cell's files,
drive it on a wall-clock schedule, measure, then check what it served
against the plain reference.

Everything cell-specific is found by name under ``bench/``:
``configs/<config>.json`` (model as published, its family, serving rule,
program arch), ``families/<family>.py`` (the model's weights, plain
reference and operation count; see ``families/qwen_dense.py``),
``traffic/<mix>.json`` (generator parameters and slots),
``cells/<cell>.json`` (limits of the correctness check) and
``metrics/<metric>.py`` (one reader per metric, end-to-end and per-layer
alike).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

import devtrace
import measure
import phases
import stem_reference
import stem_rule
import traffic as traffic_lib

TRACE_SECONDS = 2.0
WARMUP_PAGES = 3      # warm-up prompt: two chunk steps, then a decode-only one


def load_module(path: pathlib.Path, name: str):
    """The Python file at ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(bench: pathlib.Path, name: str):
    """The model family module ``families/<name>.py`` under ``bench``."""
    path = pathlib.Path(bench) / "families" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model family file {path}")
    return load_module(path, "family_" + name)


class Cell:
    """A workload of ``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: pathlib.Path, workload: str):
        root = pathlib.Path(root)
        self.bench = root / "bench"
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        self.name = workload
        w = cells[workload]
        self.chips = int(w["chips"])
        entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
        self.config = json.loads((root / entry["file"]).read_text())
        if "family" not in self.config:
            raise ValueError(f"{root / entry['file']} names no family (a "
                             f"file of {self.bench / 'families'})")
        self.family = family(self.bench, self.config["family"])
        self.model = self.config["model"]
        self.rule = stem_rule.StemRule.from_config(self.config["serving"])
        self.traffic = json.loads(
            (self.bench / "traffic" / f"{w['traffic']}.json").read_text())
        self.check = json.loads(
            (self.bench / "cells" / f"{workload}.json").read_text())
        self.peaks = json.loads((self.bench / "peaks.json").read_text())

        def mine(m):
            return workload in m.get("workloads", [workload])
        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]

    def reader(self, metric: str):
        return load_module(self.bench / "metrics" / f"{metric}.py",
                           "metric_" + metric).read

    def program_config(self, configs):
        return self.family.program_config(
            self.model, configs.get_config(self.config["program"]["arch"]))


def _span(jax, name, on):
    """A host span in the profiler's trace while tracing is on."""
    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


class Driver:
    """Submits requests when due and steps the engine; records each step's
    span and what it carried, and when every token came out."""

    def __init__(self, jax, engine, Request, items, t_zero):
        self.jax, self.engine, self.Request = jax, engine, Request
        self.queue = collections.deque(sorted(items, key=lambda i: i.due_s))
        self.t_zero = t_zero
        self.items = {i.uid: i for i in items}
        self.times = collections.defaultdict(list)   # uid -> emission times
        self.submitted = []
        self.lag = []
        self.steps = []
        self.traced = False

    def _submit(self, now):
        while self.queue and self.t_zero + self.queue[0].due_s <= now:
            it = self.queue.popleft()
            self.engine.submit(self.Request(uid=it.uid, prompt=it.prompt,
                                            max_new_tokens=it.max_new))
            self.submitted.append(it.uid)
            self.lag.append(now - (self.t_zero + it.due_s))

    def step(self, record: bool):
        eng = self.engine
        before = {id(st): (st.phase, st.prefill_pos, len(st.tokens),
                           int(eng.cache_lens[s]))
                  for s, st in enumerate(eng.slots) if st is not None}
        held = [st for st in eng.slots if st is not None]
        t0 = time.perf_counter()
        with _span(self.jax, "bench.step", self.traced):
            eng.step()
        t1 = time.perf_counter()
        fresh = [st for st in eng.slots
                 if st is not None and id(st) not in before]
        dec, chunks = [], []
        for st in held + fresh:
            phase, pos, ntok, clen = before.get(
                id(st), ("prefill", 0, 0, 0))
            new = len(st.tokens) - ntok
            if phase == "decode" and new > 0:
                dec.append(clen)
            if phase == "prefill" and st.prefill_pos > pos:
                chunks.append([st.true_len, pos, st.prefill_pos - pos,
                               st.phase == "decode"])
            if new > 0:
                self.times[st.req.uid].extend([t1] * new)
        if record:
            self.steps.append({"t0": t0, "t1": t1, "decode": dec,
                               "chunks": chunks})

    def run(self, until: float, record: bool, tracer=None):
        while True:
            now = time.perf_counter()
            if tracer is not None:
                tracer.poll(now)
            if now >= until:
                return now
            with _span(self.jax, "bench.submit", self.traced):
                self._submit(now)
            if self.engine.pending == 0:
                nxt = (self.t_zero + self.queue[0].due_s) if self.queue else until
                with _span(self.jax, "bench.idle", self.traced):
                    time.sleep(max(0.0, min(nxt, until) - now))
                continue
            self.step(record)


class Tracer:
    """Profiles ``TRACE_SECONDS`` in the middle of the window, and keeps the
    argument signatures of the engine's step, so that the trace's ops can
    be named from the compiled step's HLO once the window has closed."""

    def __init__(self, jax, driver, start, stop, keep=None):
        self.jax, self.driver = jax, driver
        self.start, self.stop = start, stop
        self.keep = keep
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "before"
        self.span = None
        eng = driver.engine
        self.step, self.signatures = eng._unified, {}

        def recording(*a):
            if (a[-1] is None) not in self.signatures:
                self.signatures[a[-1] is None] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype, sharding=x.sharding), a)
            return self.step(*a)
        eng._unified = recording

    def poll(self, now):
        if self.state == "before" and now >= self.start:
            self.jax.profiler.start_trace(self.dir)
            self.span = self.jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
            self.span.__enter__()
            self.driver.traced = True
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.span.__exit__(None, None, None)
            self.driver.traced = False
            self.jax.profiler.stop_trace()
            self.state = "done"

    def result(self):
        """(``devtrace.reduce``, ``phases.reduce``) of the traced window;
        each None where there is nothing to read.  With ``keep`` the loaded
        trace is written there as JSON."""
        if self.state == "on":
            self.poll(float("inf"))
        self.driver.engine._unified = self.step
        try:
            files = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
            trace = devtrace.load(str(files[0])) if files else {"planes": []}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        if any(p["name"].startswith("/device:") for p in trace["planes"]):
            phases.name_ops(trace, [
                phases.hlo_op_names(self.step.lower(*a).compile().as_text())
                for a in self.signatures.values()])
        if self.keep:
            pathlib.Path(self.keep).write_text(json.dumps(trace))
        return devtrace.reduce(trace), phases.reduce(trace)


@dataclasses.dataclass
class Served:
    """What one request was served: all its tokens (``done``), or, for a
    request still decoding when the window closed, the tokens so far."""
    uid: int
    tokens: list
    error: str | None
    done: bool


def run_window(jax, cell: Cell, seed: int, seconds: float, trace: bool,
               t_start: float, out: dict, keep_trace: str | None = None):
    """Build, warm up, drive the window.  Fills ``out`` and returns
    ``[(Served, traffic.Item)]`` for every finished request and every one
    still decoding at the close, once the program's state is freed.  With
    ``keep_trace`` the traced window's loaded trace is written there."""
    from repro import configs
    from repro.launch.serve import serving_policy
    from repro.models import registry
    from repro.runtime.engine import EngineConfig, Request, StemEngine

    clock = measure.CompileClock(jax)
    pcfg = cell.program_config(configs)
    bundle = registry.build(pcfg)
    like = bundle.abstract_params()[0]
    params = cell.family.program_params(cell.model, like, seed)
    rule = cell.rule
    policy = serving_policy(cell.config["serving"]["policy"], rule.page)
    stated = dict(stride=policy.stride, sink=policy.sink_blocks,
                  local=policy.local_blocks, beta=policy.metric.beta,
                  mu=policy.schedule.mu,
                  min_budget=policy.schedule.min_budget_blocks)
    for k, v in stated.items():
        if getattr(rule, k) != v:
            raise ValueError(f"the program's {k} is {v}; the configuration "
                             f"states {getattr(rule, k)}")
    tr = cell.traffic
    ecfg = EngineConfig.for_trace(
        max_slots=tr["slots"], max_prompt=tr["prompt"]["max"],
        max_new_tokens=tr["output"]["max"], page_size=rule.page,
        budget_frac=rule.budget_frac)
    engine = StemEngine(bundle, params, policy, ecfg)
    vocab = cell.family.vocab(cell.model)
    items = traffic_lib.generate(tr, seed, seconds, vocab)

    warm = np.random.default_rng(int(seed) + 1).integers(
        0, vocab, min(WARMUP_PAGES * rule.page, tr["prompt"]["max"]),
        dtype=np.int32)
    engine.run([Request(uid=-1, prompt=warm, max_new_tokens=2)])
    engine.reset_metrics()

    drv = Driver(jax, engine, Request, items, t_zero=0.0)
    if tr["kind"] == "offline" and tr.get("fill_slots"):
        first = items[:tr["slots"]]
        drv.queue = collections.deque(items[tr["slots"]:])
        for it in first:
            engine.submit(Request(uid=it.uid, prompt=it.prompt,
                                  max_new_tokens=it.max_new))
            drv.submitted.append(it.uid)
        while engine.waiting or any(st is not None and st.phase == "prefill"
                                    for st in engine.slots):
            drv.step(record=False)
    now = time.perf_counter()
    preroll = tr.get("preroll_s", 0.0) if tr["kind"] == "open_loop" else 0.0
    drv.t_zero = now + preroll
    drv.lag.clear()
    if preroll:
        drv.run(until=drv.t_zero, record=False)
    opened = drv.t_zero
    out["setup_s"] = opened - t_start
    stats0 = dict(engine.stats)
    comp0 = clock.snapshot()
    tracer = None
    if trace:
        mid = opened + max(0.0, (seconds - TRACE_SECONDS) / 2)
        tracer = Tracer(jax, drv, mid, mid + min(TRACE_SECONDS, seconds),
                        keep_trace)
    closed = drv.run(until=opened + seconds, record=True, tracer=tracer)
    comp1 = clock.snapshot()
    stats = {k: engine.stats[k] - stats0.get(k, 0) for k in engine.stats
             if isinstance(engine.stats[k], (int, float))}
    out["trace"], out["phases"] = tracer.result() if tracer else (None, None)
    out["compiles_in_window"] = comp1["compiles"] - comp0["compiles"]
    out["compile_s_in_window"] = comp1["compile_s"] - comp0["compile_s"]
    out["record"] = {
        "model": cell.model, "family": cell.family, "rule": rule,
        "peak": cell.peaks[jax.devices()[0].device_kind],
        "open": opened, "close": closed, "setup_s": out["setup_s"],
        "steps": drv.steps, "stats": stats, "trace": out["trace"],
        "phases": out["phases"],
        "requests": {u: {"due": opened + drv.items[u].due_s,
                         "times": drv.times.get(u, [])}
                     for u in drv.submitted},
    }
    out["attempted"] = sum(1 for u in drv.submitted
                           if opened + drv.items[u].due_s <= closed)
    out["generator_lag_s"] = max(drv.lag) if drv.lag else 0.0
    out["waiting_at_close"] = len(engine.waiting) + sum(
        1 for st in engine.slots if st is not None and not st.tokens)
    devs = jax.devices()[:cell.chips]
    out["memory_peak_bytes"] = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    served = [(Served(f.uid, f.tokens, f.error, True), drv.items[f.uid])
              for f in engine.finished]
    served += [(Served(st.req.uid, list(st.tokens), None, False),
                drv.items[st.req.uid])
               for st in engine.slots if st is not None and st.tokens]
    del engine, params, drv, tracer
    gc.collect()
    jax.clear_caches()
    return served


def pick_sample(served, seed: int, tokens: int, max_requests: int):
    """The longest served request (prompt and tokens), then others drawn
    from the seed until ``tokens`` served tokens or ``max_requests``
    requests.  Requests cut off by the close count with what they got."""
    ok = [(f, it) for f, it in served if f.error is None]
    if not ok:
        return []
    ok.sort(key=lambda p: (-(len(p[1].prompt) + len(p[0].tokens)), p[0].uid))
    rest = ok[1:]
    order = np.random.default_rng(int(seed) + 2).permutation(len(rest))
    sample = [ok[0]]
    for i in order:
        if (sum(len(f.tokens) for f, _ in sample) >= tokens
                or len(sample) >= max_requests):
            break
        sample.append(rest[i])
    return sample


def compare(jax, cell: Cell, seed: int, sample, fp8_control: bool = False):
    """Reference logits at every served position of the sample.  Returns
    {"gaps": per served token, how far its reference logit lies below the
    reference's best; "control_gaps": the same for the tokens the float8
    reference puts first (only with ``fp8_control``)}."""
    fam = cell.family
    w = jax.jit(lambda k: fam.canonical(cell.model, k))(
        stem_reference.jax_key(seed))
    kw = dict(kmax=cell.rule.prefill_bound(cell.traffic["prompt"]["max"]),
              prompt_bucket=cell.check["prompt_bucket"])
    gaps, ctrl = [], []
    for f, it in sample:
        ref = fam.logits(cell.model, cell.rule, w, it.prompt, f.tokens, **kw)
        gaps.append(fam.gaps(ref, f.tokens))
        if fp8_control:
            low = fam.logits(cell.model, cell.rule, w, it.prompt, f.tokens,
                             fp8=True, **kw)
            ctrl.append(fam.gaps(ref, low.argmax(-1)))
    del w
    gc.collect()
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros((0,))
    return {"gaps": cat(gaps), "control_gaps": cat(ctrl)}


def widest(gaps) -> float:
    return float(np.max(gaps)) if len(gaps) else float("inf")


def served_counts_wrong(served, vocab: int) -> int:
    """Requests with a token id outside the vocabulary, or finished with
    another number of tokens than they asked for."""
    bad = 0
    for f, it in served:
        if f.error is None and (
                any(not 0 <= t < vocab for t in f.tokens)
                or (f.done and len(f.tokens) != it.max_new)):
            bad += 1
    return bad
