"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit.  The same numbers close
standard error.  Without a TPU, or with fewer chips than the cell needs,
it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))


def _fail(msg: str) -> None:
    print(f"bench: {msg}; no result", file=sys.stderr)


def init_jax(root: pathlib.Path, cell, platform: str):
    """Import JAX with the compile cache at a fixed path in the checkout.
    Returns None, having said why, when the chips the cell needs are not
    there or the device has no entry in ``peaks.json``."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != platform:
        _fail(f"needs a {platform.upper()}, but JAX's backend is "
              f"{devices[0].platform!r}")
        return None
    if len(devices) < cell.chips:
        _fail(f"{cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}")
        return None
    kind = devices[0].device_kind
    print(f"device: {devices[0].platform} {kind} x {len(devices)}", flush=True)
    if kind not in cell.peaks:
        _fail(f"no peaks for device kind {kind!r} in bench/peaks.json")
        return None
    sys.path.insert(0, str(root / "src"))
    return jax


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(jax, cell, args, keep_trace: str | None = None):
    """One run of the cell: (the result line's object, the run's record)."""
    import harness
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}

    out: dict = {}
    served = harness.run_window(jax, cell, args.seed, args.seconds,
                                bool(args.trace), T_START, out, keep_trace)
    finished = [p for p in served if p[0].done]
    print(f"window: compiles {out['compiles_in_window']} "
          f"({out['compile_s_in_window']:.3f} s), steps "
          f"{len(out['record']['steps'])}, attempted {out['attempted']}, "
          f"finished {len(finished)}, generator lag max "
          f"{out['generator_lag_s'] * 1e3:.3f} ms", flush=True)

    chk = cell.check
    sample = harness.pick_sample(served, args.seed, chk["sample_tokens"],
                                 chk["sample_requests"])
    wrong = harness.served_counts_wrong(served, cell.family.vocab(cell.model))
    failed = sum(1 for f, _ in finished if f.error is not None)
    gaps = harness.compare(jax, cell, args.seed, sample)["gaps"]
    gap = float(gaps.mean()) if len(gaps) else float("inf")
    checks = {
        "mean_logit_gap": {"value": gap, "limit": chk["mean_logit_gap"]},
        "wrong_outputs": {"value": wrong, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": out["compiles_in_window"], "limit": 0},
        "requests_compared": {"value": len(sample), "limit": 1},
    }
    correct = (gap <= chk["mean_logit_gap"] and wrong == 0 and failed == 0
               and out["compiles_in_window"] == 0 and len(sample) >= 1)

    rec = out["record"]
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": failed + wrong, "metrics": metrics, "device": device}
    if args.trace:
        tr = out["trace"] or {"busy_s": 0.0, "window_s": 0.0,
                              "device_ops": [], "idle_gaps": []}
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    return result, rec


def emit(result: dict) -> None:
    """The compared numbers beside their limits, closing standard error,
    then the result line, closing standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, root: pathlib.Path = ROOT, platform: str = "tpu") -> int:
    args = parse(argv)
    import harness
    cell = harness.Cell(root, args.workload)
    jax = init_jax(root, cell, platform)
    if jax is None:
        return 3
    result, _ = measure(jax, cell, args)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
