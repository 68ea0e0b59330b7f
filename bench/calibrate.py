"""Readings that correctness limits are set from, many seeds in one
process (not part of a benchmark run).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--witness] [--seconds 0.1] [--out FILE]

For each seed it runs the cell through its driver and prints the
compared numbers as the program reads them.  For each control seed it
also reads the control, the configuration's reference computed with fp8
operands in the program's place, at the same prompts and tokens, and
judges it against the cell's limits as a run would
(``control_correct``).  With ``--witness`` it reads, for every seed,
the same reference with bfloat16 operands (``bf16``): what rounding
alone does at the precision the program serves in.  Where the witness
and the program read alike, the program's distance from the reference
is what bfloat16 costs; where the program reads far above, something
besides rounding moves its tokens.  One JSON object per line, on
standard output and in ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import modelcell, run as harness, spec  # noqa: E402


def _control(run, cell, precision="fp8"):
    """The reference at ``precision`` in the program's place, read at a
    driven run's compared tokens."""
    model, _ = modelcell.build(cell.config, run.devices)
    return modelcell.compare(run.facts["compared"], cell.config,
                             modelcell.served_shapes(model), run.seed,
                             model.mesh.shape["model"], run.devices[0],
                             control=precision)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}

    cell = harness.load(args.workload)
    devices = harness._device_check(cell.chips)
    harness._compile_cache()
    out = open(args.out, "a") if args.out else None
    for seed in sorted(set(seeds) | ctrl):
        run = harness.Run(cell, seed, args.seconds, False, devices)
        t0 = time.perf_counter()
        result = spec.driver(cell.traffic["kind"]).drive(run)
        line = {"workload": args.workload, "seed": seed,
                "program": result["checks"],
                "seconds": time.perf_counter() - t0}
        if seed in ctrl:
            line["control"] = _control(run, cell)
            line["control_correct"] = harness.judge(line["control"],
                                                    cell.limits)[0]
        if args.witness:
            line["bf16"] = _control(run, cell, "bf16")
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()


if __name__ == "__main__":
    main()
