#!/usr/bin/env python3
"""The readings that a cell's limits are set from, many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 3 [--control]

For each seed it runs the cell as ``bench/run.py`` does (set-up, a short
window at the cell's own load, the comparison with the plain reference)
and prints the program's compared numbers. With ``--control`` the same
sampled frames are also run through the control, the reference computed in
the next precision down (bfloat16 for float32), and compared with the
float32 reference in the same way: the control has to come out not
correct. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.run import RunError, _device, one, start  # noqa: E402

CONTROL_DTYPE = "bfloat16"


class WithControl:
    """A reference that also runs the control on every frame it is given,
    comparing the control with the reference into ``checks``."""

    def __init__(self, reference, checks, dtype=CONTROL_DTYPE):
        self.reference, self.checks, self.dtype = reference, checks, dtype

    def outputs(self, frames, *, edges):
        want = self.reference.outputs(frames, edges=edges)
        got = self.reference.outputs(frames, edges=edges, dtype=self.dtype)
        self.checks.frames_pair(got, want)
        return want


def readings(workload, seeds, seconds, *, control=False, require_tpu=True,
             overrides=None):
    """[(seed, program rows, program correct, control rows, control correct)]"""
    start()
    from bench import compare, spec

    cell = spec.Cell(workload, overrides=overrides)
    _device(cell.chips, require_tpu)
    out = []
    for seed in seeds:
        ctrl = compare.Checks()
        ref = cell.reference()
        r = one(cell, seed, seconds,
                reference=WithControl(ref, ctrl) if control else ref)
        ok, rows = r["correct"], r["rows"]
        row = {"seed": seed, "correct": ok, "program": compare.report(rows)}
        c_ok = c_rows = None
        if control:
            c_ok, c_rows = ctrl.verdict(cell.limits)
            row["control_correct"] = c_ok
            row["control"] = compare.report(c_rows)
        print("readings " + json.dumps(row), flush=True)
        out.append((seed, rows, ok, c_rows, c_ok))
        del r
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    try:
        readings(args.workload, seeds, args.seconds, control=args.control)
    except RunError as err:
        print(f"readings: {err}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
