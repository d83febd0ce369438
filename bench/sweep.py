"""Find the highest arrival rate an open-loop cell sustains: run its
traffic at each given rate in one process and print, per rate, the
requests due and finished in the window, the requests still without a
first token at the close, and the latency tails.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,5 --seconds 20

A rate is sustained while the requests left waiting at the close stay
about as few as at low load; above it the backlog grows all through the
window.  The cell's traffic file then fixes a rate below it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402  (sets up sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import harness
    import readers
    cell = harness.Cell(run.ROOT, args.workload)
    jax = run.init_jax(run.ROOT, cell, "tpu")
    if jax is None:
        return 3
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        out: dict = {}
        finished = harness.run_window(jax, cell, args.seed, args.seconds,
                                      False, time.perf_counter(), out)
        rec = out["record"]
        done = sum(1 for f, _ in finished if f.done
                   and f.error is None and rec["requests"][f.uid]["times"]
                   and rec["requests"][f.uid]["times"][-1] <= rec["close"])
        print(json.dumps({
            "rate_per_s": rate, "due_in_window": out["attempted"],
            "finished": done, "waiting_at_close": out["waiting_at_close"],
            "ttft_mean_ms": readers.ttft_mean_ms(rec),
            "ttft_p50_ms": readers.ttft_ms(rec, 50),
            "ttft_p95_ms": readers.ttft_ms(rec, 95),
            "itl_p95_ms": readers.itl_p95_ms(rec),
            "output_tokens_per_s": readers.output_tokens_per_s(rec),
            "steps": len(rec["steps"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
