"""Readings that the limits of `correct` are set from.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Runs the cell once per seed in this one process (so set-up compiles once),
each run exactly as `run.py` would but with a short window, and reads two
sets of numbers on the same checked sample: the program's (every number
its driver reads, `readings`, or else its `checks`) and the control's,
which is the plain reference computed one precision below the one the
configuration states, put in the program's place. The control's
readings go through the same check as the program's, against the same
limits: `control_correct` has to come out false. A limit sits above the
largest program reading and below the smallest control reading.
Prints one JSON line per seed and a summary line last. Needs the chip, as
`run.py` does; the benchmark's own runs never read the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run
import session


def control_checks(out: dict) -> dict:
    """The control's readings that the program's checks compare, each
    beside the program's own limit."""
    return {k: session.check(v, out["checks"][k]["limit"])
            for k, v in out["record"]["control"].items() if k in out["checks"]}


def control_correct(out: dict) -> bool:
    """`correct` as the harness decides it, of the control put in the
    program's place."""
    return all(c["value"] <= c["limit"]
               for c in control_checks(out).values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    program, control = {}, {}
    for seed in seeds:
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(), control=True)
        rec = out["record"]
        line = {"seed": seed, "correct": out["correct"],
                "compiles_in_window": out["compiles_in_window"],
                "program": rec.get("readings") or {
                    k: c["value"] for k, c in out["checks"].items()},
                "control": rec["control"],
                "control_correct": control_correct(out)}
        print(json.dumps(line), flush=True)
        for k, v in line["program"].items():
            program.setdefault(k, []).append(v)
        for k, v in rec["control"].items():
            control.setdefault(k, []).append(v)
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "program": program, "control": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
