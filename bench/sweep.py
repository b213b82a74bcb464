"""Offered-rate sweep of an open-loop cell, to find its knee once.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates 2,3,4,5,6,8

Runs the cell's traffic at each Poisson rate in turn, in one process
(bench/run.py without the reference check), and prints per rate the
requests due and served in the window, how many waited for admission
when the window opened and when it closed, and the end-to-end metrics.
The knee is the highest rate at which the waiting queue does not grow
across the window; the cell's traffic file then fixes its rate at about
0.8 x the knee. The benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        r = bench_run.resolve(args.workload)
        traffic = copy.deepcopy(r["traffic"])
        traffic["arrival"]["rate_rps"] = rate
        out = bench_run.run(["--workload", args.workload, "--seed",
                             str(args.seed), "--seconds", str(args.seconds),
                             "--trace", "0"], override={"traffic": traffic},
                            check=False)
        row = {"rate_rps": rate, **out["load"],
               **{k: v["value"] for k, v in out["metrics"].items()}}
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    print(json.dumps({"workload": args.workload, "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
