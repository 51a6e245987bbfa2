"""Readings that the limits of ``correct`` are set from.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11 12 ...

For each seed, one run of the cell (``run.run_cell``, untraced), whose
checked requests are compared with the float32 reference twice: the
served tokens' widest logit gap (the program's reading, which sets the
lower end of a limit), and the widest gap of the tokens that the
reference computed in float8 would put first at the same positions (the
control's reading, the upper end).  One JSON line per seed, with
``correct`` as the cell's limits judge the program and the control put
in its place, then one with the largest program reading and the
smallest control reading.
Runs on a TPU only; the benchmark's own runs do not run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    r = run.resolve(args.workload)
    prog, ctl = [], []
    for seed in args.seeds:
        rec = run.run_cell(r, seed, args.seconds, False, control=True)
        prog.append(rec["gap_max"])
        ctl.append(rec["control_gap_max"])
        # the control in the program's place, judged as a run is judged
        as_program = dict(rec, gap_max=rec["control_gap_max"])
        print(json.dumps({"seed": seed, "program": rec["gap_max"],
                          "control": rec["control_gap_max"],
                          "program_correct": run.is_correct(
                              run.checks(rec, r["limits"])),
                          "control_correct": run.is_correct(
                              run.checks(as_program, r["limits"])),
                          "tokens": rec["checked_tokens"],
                          "requests": rec["checked_requests"],
                          "reference_s": rec["reference_s"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(prog),
                      "upper": min(ctl), "seeds": len(prog)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
