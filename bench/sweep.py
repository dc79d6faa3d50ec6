#!/usr/bin/env python3
"""Sweep the arrival rate of an open-loop cell to find its knee, in one
process on the chip.

    python3 bench/sweep.py --workload dit-xl.img-steady \\
        --rates 0.30,0.36,0.42,0.48 --seconds 45 --seed 7

Each rate is one run of the cell (``bench/run.py``) with only the traffic
file's ``rate_per_s`` changed. For each it prints the window's request
count, the due-to-latent p50 and p90, and the mean latency of the window's
last third of requests over its first third: a queue that grows through
the window shows as a ratio well over 1. The knee is the highest rate
whose queue does not grow; the cell's rate is about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=os.path.join(bench_run.ROOT,
                                                  "bench_out", "sweep"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        def edit(cell, rate=rate):
            tr = dict(cell["traffic"])
            tr["arrivals"] = dict(tr["arrivals"], rate_per_s=rate)
            return dict(cell, traffic=tr)

        seed = args.seed + i
        out = os.path.join(args.out, f"rate{rate}")
        bench_run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0",
                        "--out", out]
                       + (["--rehearse"] if args.rehearse else []),
                       edit_cell=edit)
        with open(os.path.join(out, f"{args.workload}.{seed}.trace0.json")) \
                as f:
            run = json.load(f)["run"]
        lat = run["latencies_s"]
        third = max(1, len(lat) // 3)
        row = {"rate_per_s": rate, "requests": run["attempted"],
               "unfinished": run["unfinished"],
               "p50_s": float(np.percentile(lat, 50)) if lat else None,
               "p90_s": float(np.percentile(lat, 90)) if lat else None,
               "growth": (float(np.mean(lat[-third:]) / np.mean(lat[:third]))
                          if lat else None),
               "rounds_per_sample": float(np.mean(run["rounds_used"]))
               if run["rounds_used"] else None}
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    with open(os.path.join(args.out, f"{args.workload}.sweep.json"), "w") \
            as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
