"""Readings that set a cell's limit: the program's widest logit gap over
many seeds, and the float8 control's at the same positions.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13,... --control-seeds 11,12,13

Each seed is one whole run of the cell (bench/run.py: weights, engine,
warm-up, the cell's own traffic for ``--seconds``, the reference check),
all in one process so that set-up is paid once per seed and compiles are
read back from the cache. On a control seed the float8 control takes the
program's place in the comparison that decides ``correct``: the reference
with every matrix product in float8 e4m3, over the same prompts and served
tokens, puts a token first at each position, and the widest gap of those
tokens is held against the cell's limit. The control has to come out not
correct on every control seed. The benchmark's own runs never run it.

Prints one line per seed and, last, one JSON object with every reading
and verdict: the limit lies above the largest program reading and below
the smallest control reading (bench/limits/<cell>.json says how it was
set).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    readings = []
    for seed in seeds:
        out = bench_run.run(["--workload", args.workload, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace", "0"],
                            control=seed in ctrl)
        gap = out["compared"]["widest_logit_gap"]["value"]
        is_ctrl = seed in ctrl
        row = {"seed": seed, "control": is_ctrl, "correct": out["correct"],
               "program": out["program_widest_logit_gap"] if is_ctrl else gap,
               "control_gap": gap if is_ctrl else None,
               "limit": out["compared"]["widest_logit_gap"]["limit"],
               "tokens": out["compared"]["tokens_checked"]["value"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        readings.append(row)
        print(f"[control] {json.dumps(row)}", flush=True)
    prog = [r["program"] for r in readings]
    low = [r["control_gap"] for r in readings if r["control"]]
    print(json.dumps({
        "workload": args.workload, "readings": readings,
        "program_max": max(prog),
        "program_all_correct": all(r["correct"] for r in readings
                                   if not r["control"]),
        "control_min": min(low) if low else None,
        "control_all_not_correct": all(not r["correct"] for r in readings
                                       if r["control"])}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
