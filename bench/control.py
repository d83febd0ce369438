"""Readings that set a cell's correctness limit, several seeds in one
process on the chip the cell needs.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it runs the cell's window as ``run.py`` does, then prints
one JSON line.  ``mean_gap`` is the number ``run.py`` compares: over the
sampled served tokens, the mean amount by which a token's reference logit
lies below the reference's best (the program's reading).
``control_mean_gap`` is the same for the tokens the reference itself puts
first when computed in float8 e4m3, one precision step below the bf16
the model is served in (the control's reading).  The limit goes between
the largest ``mean_gap`` and the smallest ``control_mean_gap``.  The
widest gaps and the shares of positions whose token is not the
reference's first pick are printed beside them.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import harness
    cell = harness.Cell(run.ROOT, args.workload)
    jax = run.init_jax(run.ROOT, cell, "tpu")
    if jax is None:
        return 3
    chk = cell.check
    for seed in (int(s) for s in args.seeds.split(",")):
        out: dict = {}
        t0 = time.perf_counter()
        served = harness.run_window(jax, cell, seed, args.seconds, False,
                                    t0, out)
        sample = harness.pick_sample(served, seed, chk["sample_tokens"],
                                     chk["sample_requests"])
        t1 = time.perf_counter()
        r = harness.compare(jax, cell, seed, sample, fp8_control=True)
        g, c = r["gaps"], r["control_gaps"]
        print(json.dumps({
            "seed": seed, "gap": harness.widest(g),
            "control_gap": harness.widest(c),
            "mean_gap": float(g.mean()), "control_mean_gap": float(c.mean()),
            "mismatch": float((g > 0).mean()),
            "control_mismatch": float((c > 0).mean()),
            "requests": len(sample),
            "tokens": sum(len(f.tokens) for f, _ in sample),
            "longest_prompt": max((len(it.prompt) for _, it in sample),
                                  default=0),
            "compiles_in_window": out["compiles_in_window"],
            "setup_s": out["setup_s"],
            "check_s": time.perf_counter() - t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
