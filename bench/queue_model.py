#!/usr/bin/env python3
"""How an open-loop cell's latency percentiles move with the round time.

    python3 bench/queue_model.py <run record .json> [--span 0.05]

Replays the arrival schedule of a run record (``bench/run.py --out``)
through a model of the engine's lockstep grid: S slots, FIFO admission at
the start of a step, every request ``rounds_used`` rounds, steps back to
back at the run's measured step period, and an idle grid waiting for the
next due request. It then scales the period by 1 - span .. 1 + span in
steps of 0.1% and prints the window's p50, p90 and mean latency at each
scale, and the largest change of each between neighbouring scales: a
percentile that jumps there is decided by a single queueing event.
"""
from __future__ import annotations

import argparse
import json
import statistics

import numpy as np


def finish_times(dues, period, slots, rounds):
    """Finish time of each request (``dues`` sorted)."""
    fin = [0.0] * len(dues)
    queue, live = [], []                    # live: [index, rounds left]
    t, i = dues[0], 0
    while i < len(dues) or queue or live:
        while i < len(dues) and dues[i] <= t:
            queue.append(i)
            i += 1
        if not queue and not live:
            t = dues[i]
            continue
        while queue and len(live) < slots:
            live.append([queue.pop(0), rounds])
        t += period
        for lane in live:
            lane[1] -= 1
            if lane[1] == 0:
                fin[lane[0]] = t
        live = [lane for lane in live if lane[1]]
    return fin


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("record")
    ap.add_argument("--span", type=float, default=0.05)
    args = ap.parse_args(argv)
    with open(args.record) as f:
        rec = json.load(f)
    run, steps = rec["run"], rec["steps"]
    slots = run["traffic"]["num_slots"]
    # the schedule's requests (warm-up serves rids 0 .. S)
    reqs = sorted((r for r in rec["requests"] if r["rid"] > slots),
                  key=lambda r: r["due"])
    rounds = int(statistics.median(r["rounds_used"] for r in reqs
                                   if "finished" in r))
    period = statistics.median(
        (b[0] - a[0]) / a[2] for a, b in zip(steps, steps[1:])
        if b[0] - a[1] < 0.01)
    dues = [r["due"] for r in reqs]
    window = [r["in_window"] for r in reqs]
    measured = [r["finished"] - r["due"] for r in reqs if r["in_window"]]
    print(f"step period {period:.6f} s, {rounds} rounds a request, "
          f"{sum(window)} window requests; measured p50 "
          f"{np.percentile(measured, 50):.4f} s, p90 "
          f"{np.percentile(measured, 90):.4f} s, mean "
          f"{np.mean(measured):.4f} s")
    n = int(round(args.span * 1000))
    rows = []
    for j in range(-n, n + 1):
        scale = 1.0 + j / 1000.0
        fin = finish_times(dues, period * scale, slots, rounds)
        lat = [f - d for f, d, w in zip(fin, dues, window) if w]
        rows.append((scale, np.percentile(lat, 50), np.percentile(lat, 90),
                     float(np.mean(lat))))
    base = rows[n]
    for scale, p50, p90, mean in rows:
        if abs(scale * 100 - round(scale * 100)) < 1e-6:   # whole percents
            print(f"period x{scale:.3f}: p50 {p50:.4f} "
                  f"({100 * (p50 / base[1] - 1):+.2f}%), p90 {p90:.4f} "
                  f"({100 * (p90 / base[2] - 1):+.2f}%), mean {mean:.4f} "
                  f"({100 * (mean / base[3] - 1):+.2f}%)")
    for k, name in ((1, "p50"), (2, "p90"), (3, "mean")):
        jump = max(abs(b[k] / a[k] - 1) for a, b in zip(rows, rows[1:]))
        print(f"largest change of {name} per 0.1% of period: "
              f"{100 * jump:.2f}%")


if __name__ == "__main__":
    main()
