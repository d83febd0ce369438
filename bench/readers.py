"""The arithmetic of the metric readers in ``metrics/``, end-to-end and
per-layer.

A reader gets the run's record (``out["record"]`` of
``harness.run_window``): the window's open and close, every engine step's
span and what it carried, each request's due time and token times, the
engine's counters over the window, the reduced trace and its phases, and
the model with its family module (``families/<family>.py``), which counts
a step's operations and bytes.  It returns a number, or None when the run
has nothing to read (no step, no trace)."""
from __future__ import annotations

import measure


def _span_s(rec):
    return sum(s["t1"] - s["t0"] for s in rec["steps"])


def host_ms_per_step(rec):
    """Host time per engine step that is not spent waiting for the device's
    results: (sum of step spans - engine sync_wait_s) / engine step_calls."""
    calls = rec["stats"].get("step_calls", 0)
    if not calls:
        return None
    return (_span_s(rec) - rec["stats"]["sync_wait_s"]) / calls * 1e3


def device_idle_share(rec):
    """Share of the traced window with no operation on the device, %."""
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(rec):
    """FLOPs the window's steps need over (step time x bf16 peak), %."""
    span = _span_s(rec)
    if span <= 0:
        return None
    need = sum(rec["family"].step_flops(rec["model"], rec["rule"], s)
               for s in rec["steps"])
    return 100.0 * need / (span * rec["peak"]["bf16_flops_per_s"])


def hbm_share(rec):
    """Bytes the window's decode steps need over (step time x HBM peak), %.
    Steps that carry a prefill chunk are left out."""
    steps = [s for s in rec["steps"] if s["decode"] and not s["chunks"]]
    span = _span_s({"steps": steps})
    if span <= 0:
        return None
    need = sum(rec["family"].step_bytes(rec["model"], rec["rule"], s)
               for s in steps)
    return 100.0 * need / (span * rec["peak"]["hbm_bytes_per_s"])


# -- end-to-end ------------------------------------------------------------

def _window(rec):
    return rec["close"] - rec["open"]


def setup_s(rec):
    """Process start to window open."""
    return rec["setup_s"]


def prompt_tokens_per_s(rec):
    """Prompt tokens prefilled in the window over the window's seconds."""
    n = sum(max(0, min(width, plen - start))
            for s in rec["steps"] for plen, start, width, _ in s["chunks"])
    return n / _window(rec)


def output_tokens_per_s(rec):
    """Output tokens emitted in the window over the window's seconds."""
    n = sum(len(s["decode"]) + sum(1 for c in s["chunks"] if c[3])
            for s in rec["steps"])
    return n / _window(rec)


def ttfts_s(rec):
    """Every request due in the window, from its due time to its first
    token; one still without a token at the close counts at its age
    then."""
    o, c = rec["open"], rec["close"]
    waits = []
    for r in rec["requests"].values():
        if o <= r["due"] <= c:
            first = r["times"][0] if r["times"] and r["times"][0] <= c else c
            waits.append(first - r["due"])
    return waits


def ttft_ms(rec, q: float):
    """The ``q``th percentile of ``ttfts_s``."""
    waits = ttfts_s(rec)
    return measure.percentile(waits, q) * 1e3 if waits else None


def ttft_mean_ms(rec):
    """Mean of ``ttfts_s``: a step of the engine is long next to a
    prefill, so each time to first token is quantized to steps and a
    percentile of a few dozen of them jumps by a step from run to run."""
    waits = ttfts_s(rec)
    return sum(waits) / len(waits) * 1e3 if waits else None


def itl_p95_ms(rec):
    """95th percentile of every gap between consecutive output tokens of a
    request, both inside the window."""
    o, c = rec["open"], rec["close"]
    gaps = [b - a for r in rec["requests"].values()
            for a, b in zip(r["times"], r["times"][1:]) if o <= a and b <= c]
    return measure.percentile(gaps, 95) * 1e3 if gaps else None
