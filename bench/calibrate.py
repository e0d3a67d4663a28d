#!/usr/bin/env python3
"""Readings that the limits in ``checks.LIMITS`` are set from.

    python bench/calibrate.py --workload bcnn.offline --seeds 12 --seconds 4

In one process, for each seed: one window of the cell's traffic at its
own load through the same server, and the compared numbers of

* the program (what the window served) against the float32 reference
  at ``highest`` precision: the lower reading;
* the controls, the reference itself put in the program's place at a
  lower precision: float32 at ``high`` (three bf16 passes), and
  bfloat16 throughout: the upper reading.

The benchmark's own runs never run this.  Prints one JSON line per
seed and a summary; needs the chip like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 101)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    import checks
    import loadgen

    bench, cell, cfg, mix = run.load_cell(args.workload)
    ready = run.setup(cell, cfg, mix)
    srv, refmod, params = ready.srv, ready.refmod, ready.params
    shape = tuple(cfg["input_shape"])
    rows = []
    for s in range(args.seeds):
        seed = args.first_seed + 7919 * s
        traffic = loadgen.make(mix, args.seconds, seed, shape)
        kw = {"seed": seed} if traffic.arrivals == "closed_batch" else {}
        w = run.DRIVERS[traffic.arrivals](srv, traffic, mix, args.seconds,
                                          **kw)
        x = w.sample_x
        ref = checks.reference_logits(refmod, cfg, params, x)
        high = checks.reference_logits(refmod, cfg, params, x,
                                       precision="high")
        bf16 = checks.reference_logits(refmod, cfg, params, x,
                                       dtype=jnp.bfloat16,
                                       precision="default")
        row = {"seed": seed, "rows": len(x), "failed": w.failed,
               "program": checks.logit_gap(w.sample_y, ref),
               "control_high": checks.logit_gap(high, ref),
               "control_bf16": checks.logit_gap(bf16, ref),
               "argmax_flips_bf16": int((bf16.argmax(-1) !=
                                         ref.argmax(-1)).sum())}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {k: {"min": float(np.min([r[k] for r in rows])),
                   "max": float(np.max([r[k] for r in rows]))}
               for k in ("program", "control_high", "control_bf16")}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
