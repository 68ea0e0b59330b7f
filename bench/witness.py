"""A second witness beside a cell's correctness readings: what rounding
alone does to the reference (not part of a benchmark run).

    python3 bench/witness.py --workload <name> --seeds 1,2,3 \\
        [--seconds 0.1] [--out FILE]

For each seed it runs the cell through its driver, as
``bench/calibrate.py`` does, and reads at the same prompts and tokens,
against the float32 reference: the program's served tokens
(``program``, the numbers a run compares) and the first choices of the
same reference with every matrix product's operands rounded to
bfloat16, the precision the program serves in (``bf16``).  Where the
two read alike, the program's distance from the reference is what
bfloat16 costs; where the program reads far above, something besides
rounding moves its tokens.  One JSON object per line, on standard
output and in ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402

from bench import calibrate, run as harness, spec  # noqa: E402
from bench.reference import moe as ref_lib  # noqa: E402


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def read(run, cell) -> dict:
    """The bfloat16 reference's readings at a driven run's compared
    tokens: the control's operand rounding (fp8 under a scale) made
    bfloat16's."""
    ref_lib._quant = _bf16
    return calibrate._control(run, cell)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = harness.load(args.workload)
    devices = harness._device_check(cell.chips)
    harness._compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell, seed, args.seconds, False, devices)
        t0 = time.perf_counter()
        result = spec.driver(cell.traffic["kind"]).drive(run)
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "program": result["checks"],
                           "bf16": read(run, cell),
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
