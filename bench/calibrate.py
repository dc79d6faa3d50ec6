#!/usr/bin/env python3
"""Readings that set a cell's ``latent_gap`` limit: the program's and the
control's, in one process on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 101-112 \\
        --control-seeds 101-103 --seconds 8

For every seed it makes one short run of the cell (``bench/run.py``: the
timed path at the timed sizes, at the cell's load, with its own reference
check) and records the program's gap on each checked request. For each
control seed it then puts the control in the program's place: the plain
reference computed with float8 (e4m3) matmul operands, the precision step
below the configuration's bfloat16, serves the same checked requests to
its own accept decisions, and each answer is judged against the float32
reference by the same comparison. The control has to fail: its gaps are
the upper readings. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out.extend(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def control_readings(cell, seed, record, rehearse):
    """Gaps of the control's answers to the run's checked requests."""
    import jax
    import numpy as np

    import check
    import reference
    import weights
    from repro.diffusion import init_wrapper

    tr = record["run"]["traffic"]
    latent_shape = tuple(tr["latent_shape"])
    cfg, model = bench_run.program_config(cell, rehearse)
    rng = np.random.default_rng(seed)
    wseed, kseed = (int(x) for x in rng.integers(0, 2 ** 31 - 1, 2))
    structure = jax.eval_shape(
        lambda k: init_wrapper(cfg, latent_shape[-1], k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    params = weights.draw(structure, wseed, model["family"])
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(kseed),
                                       bench_run.N_KEYS))
    i_seq = record["run"]["i_seq"]
    f32 = reference.make_drift(params, model)
    fp8 = reference.make_drift(params, model, quant="fp8")
    out = []
    for res in record["checks"]:
        rid = res["rid"]
        t0 = time.perf_counter()
        x0 = np.asarray(jax.random.normal(jax.numpy.asarray(keys[rid]),
                                          latent_shape), np.float32)
        em_c = reference.chords(fp8, x0, i_seq, tr["n_steps"], tr["rtol"])
        j = check.reference_choice(em_c, tr["rtol"])
        ans = em_c[j]
        em = reference.chords(f32, x0, i_seq, tr["n_steps"], tr["rtol"],
                              min_rounds=ans.round)
        got = check.judge(ans.out, ans.round, ans.core, em, tr["rtol"])
        got.update(rid=rid, seconds=time.perf_counter() - t0)
        out.append(got)
        print(f"[calibrate] control seed {seed} request {rid}: gap "
              f"{got['gap']:.6g}, control {got['program']}, reference "
              f"{got['reference']}", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default=os.path.join(bench_run.ROOT,
                                                  "bench_out", "cal"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    extra = ["--rehearse"] if args.rehearse else []
    cell = bench_run.load_cell(args.workload)
    table = {"program": {}, "control": {}}
    control = set(seeds(args.control_seeds))
    for seed in seeds(args.seeds):
        bench_run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0",
                        "--out", args.out] + extra)
        path = os.path.join(args.out, f"{args.workload}.{seed}.trace0.json")
        with open(path) as f:
            record = json.load(f)
        table["program"][seed] = [(r["rid"], r["gap"], r["program"],
                                   r["reference"]) for r in record["checks"]]
        if seed in control:
            table["control"][seed] = [
                (r["rid"], r["gap"], r["program"], r["reference"])
                for r in control_readings(cell, seed, record, args.rehearse)]
    prog = [g for rows in table["program"].values() for _, g, _, _ in rows]
    ctrl = [g for rows in table["control"].values() for _, g, _, _ in rows]
    table["lower"] = max(prog) if prog else None
    table["upper"] = min(ctrl) if ctrl else None
    with open(os.path.join(args.out, f"{args.workload}.calibration.json"),
              "w") as f:
        json.dump(table, f, indent=1)
    print(f"[calibrate] {args.workload}: program gaps max {table['lower']} "
          f"over {len(prog)} answers; control gaps min {table['upper']} "
          f"over {len(ctrl)} answers", flush=True)


if __name__ == "__main__":
    main()
