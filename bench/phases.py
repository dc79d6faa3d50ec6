#!/usr/bin/env python3
"""The engine's phases and the backbone's scopes, read from a traced run.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1 --keep-trace`` does (the same
result line comes first), keeps the engine's own tracer and the compiled
round program's HLO text (it wraps ``run.py``'s ``serve``, which is handed
the engine), and then reads what ``bench/devtrace.py`` does not keep:

* every host span the engine annotates (``serve/``, ``dispatch/``,
  ``verify/``), with its arguments: ``t_ns`` (the tracer's clock at entry)
  and, on round dispatches, ``rounds``;
* each device op's scope in the backbone's vocabulary
  (``repro.obs.scopes``), from the compiled round program's HLO text keyed
  by op name: on the v5e an "XLA Ops" event carries the op's HLO text
  without metadata, and no stats but ``device_duration_ps``,
  ``device_offset_ps`` and ``Time Scale Multiplier``. An op whose name or
  result type is not the round program's belongs to another program
  (admission, the drain's gather).

From them: ``scope_s`` (leaf-op device seconds by scope, clipped to the
window), ``mixed_s`` (the part of it in fusions that hold ops of more than
one scope, each charged whole to its root's), ``rounds_in_window``, ``engine_idle_s`` (idle seconds whose
innermost host span is ``serve/step`` or one of its children), the clock
offset fitted from ``t_ns`` with the spread of its residuals, the window
requests' ``request/queued`` seconds from the ring, the longest window
step's phase split, and the readings ``queue_wait_p90_s``,
``engine_idle_pct`` and ``ms_per_round`` of a scope. The ring is written as
a Chrome trace on the profiler's clock next to the run record. The last
line of standard output is a JSON summary.

``--no-profile`` runs the cell untraced with the engine's tracer on (it
wraps ``run.py``'s ``build_engine`` to hand it one) and
reads the ring alone: queue waits and step times without the profiler's
cost, and without its stop at the window's close, which holds the engine
for seconds while queued requests wait.

:func:`reduce` returns every key ``devtrace.reduce`` returns, unchanged, and
adds its own.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import devtrace  # noqa: E402

ENGINE_PREFIXES = ("serve/", "dispatch/", "verify/")
ROUND_DISPATCHES = ("dispatch/round", "dispatch/multi", "dispatch/roll",
                    "dispatch/round_keep")
OTHER = "other programs"


# -- the record ---------------------------------------------------------------

def extract(xspace_path: str) -> dict:
    """``devtrace.extract``'s record, with the engine's host spans too, plus
    ``phases``: [name, start, end, args] of every host span with a ``t_ns``
    argument (``repro.obs.profiler_phases``)."""
    from repro.obs import profiler_phases

    record = devtrace.extract(xspace_path)
    phase_spans = [list(p) for p in profiler_phases(xspace_path)
                   if p[0].startswith(ENGINE_PREFIXES)]
    # devtrace keeps dispatch/ spans already
    record["host"] += [p[:3] for p in phase_spans
                       if p[0].startswith(("serve/", "verify/"))]
    record["phases"] = phase_spans
    return record


def round_op(text: str, scopes: dict):
    """The :class:`repro.obs.scopes.OpScope` of a device op's text, or None
    for an op of another program: one whose name or result type is not the
    round program's. ``scopes`` is ``hlo_op_scopes``' map of it."""
    name, _, rest = text.partition(" = ")
    hit = scopes.get(name.lstrip("%"))
    return hit if hit is not None and rest.startswith(hit.type) else None


def reduce(record: dict, kernels=None, scopes=None) -> dict:
    """``devtrace.reduce`` plus ``scope_s``, ``mixed_s`` (the seconds of
    fusions that hold ops of two unnested scopes, charged whole to one),
    ``rounds_in_window`` and ``engine_idle_s``. ``scopes`` is the round
    program's ``hlo_op_scopes`` map."""
    red = devtrace.reduce(record, kernels)
    w0, w1 = devtrace.window_of(record)
    scope_s: Dict[str, float] = {}
    mixed_s = 0.0
    for ops in record["devices"].values():
        inside = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
        for text, s, d in devtrace._leaves(inside):
            hit = round_op(text, scopes or {})
            scope = OTHER if hit is None else str(hit.scope)
            dur = (min(s + d, w1) - max(s, w0)) * 1e-9
            scope_s[scope] = scope_s.get(scope, 0.0) + dur
            if hit is not None and hit.mixed:
                mixed_s += dur
    red["scope_s"] = scope_s
    red["mixed_s"] = mixed_s
    red["rounds_in_window"] = sum(
        int(a.get("rounds", 1)) for name, s, _, a in record.get("phases", [])
        if name in ROUND_DISPATCHES and w0 <= s < w1)
    red["engine_idle_s"] = sum(v for k, v in red["idle_by_label_s"].items()
                               if k.startswith(ENGINE_PREFIXES))
    return red


# -- readings -------------------------------------------------------------------

def queue_wait_p90_s(waits, dropped: int) -> Optional[float]:
    import numpy as np

    if dropped or not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 90))


def engine_idle_pct(red: dict, dropped: int) -> Optional[float]:
    if dropped or not red["window_s"]:
        return None
    return 100.0 * red["engine_idle_s"] / red["window_s"]


def ms_per_round(red: dict, scope: str, dropped: int) -> Optional[float]:
    seconds = red["scope_s"].get(scope)
    if dropped or not seconds or not red["rounds_in_window"]:
        return None
    return 1e3 * seconds / red["rounds_in_window"]


def window_clock_fit(record: dict):
    from repro.obs import fit_offset

    w0, w1 = devtrace.window_of(record)
    return fit_offset([(s, a["t_ns"]) for _, s, _, a in record["phases"]
                       if w0 <= s < w1])


def longest_step(record: dict) -> dict:
    """The longest window ``serve/step``: its seconds and its children's."""
    w0, w1 = devtrace.window_of(record)
    steps = [p for p in record["phases"]
             if p[0] == "serve/step" and w0 <= p[1] < w1]
    if not steps:
        return {}
    _, s, e, _ = max(steps, key=lambda p: p[2] - p[1])
    split: Dict[str, float] = {}
    for name, a, b, _ in record["phases"]:
        if name != "serve/step" and s <= a and b <= e:
            split[name] = split.get(name, 0.0) + (b - a) * 1e-9
    return {"seconds": (e - s) * 1e-9, "phases": split}


def queue_waits(tracer, rids) -> List[float]:
    """Seconds each request of ``rids`` spent queued (summed over
    re-queues), from the ring's ``request/queued`` spans."""
    by_rid: Dict[int, float] = {}
    for ev in tracer.named("request/queued"):
        rid = ev.args.get("rid")
        by_rid[rid] = by_rid.get(rid, 0.0) + ev.dur
    return [by_rid[r] for r in rids if r in by_rid]


# -- the run ------------------------------------------------------------------

def _window_step_median(run_rec):
    """Median seconds of the steps that started between the first and the
    last window request's due time (host clock of the run record)."""
    due = [r["due"] for r in run_rec["requests"] if r.get("in_window")]
    steps = [t1 - t0 for t0, t1, ran, _ in run_rec["steps"]
             if ran and due and min(due) <= t0 <= max(due)]
    return statistics.median(steps) if steps else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out",
                                                  "phases"))
    ap.add_argument("--no-profile", action="store_true",
                    help="run untraced (--trace 0) with the engine's tracer "
                         "on: the ring's readings alone, without the "
                         "profiler's cost or its stop at the window's close")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import run as bench_run
    from repro.obs import Tracer, write_chrome_trace
    from repro.obs.scopes import hlo_op_scopes

    kept = {}
    serve, build = bench_run.serve, bench_run.build_engine

    def serve_and_keep(cell, run_args, engine, *rest):
        out = serve(cell, run_args, engine, *rest)
        kept["tracer"] = engine.tracer
        if not args.no_profile:
            round_prog = engine.executor.grid(engine.spec).round
            kept["hlo"] = round_prog.lower(engine.state).compile().as_text()
        return out

    bench_run.serve = serve_and_keep
    if args.no_profile:  # the tracer is the last argument
        bench_run.build_engine = lambda *a: build(*a[:-1], Tracer())
    trace = 0 if args.no_profile else 1
    run_argv = ["--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", str(trace),
                "--out", args.out]
    run_argv += [] if args.no_profile else ["--keep-trace"]
    rc = bench_run.main(run_argv + (["--rehearse"] if args.rehearse else []))
    if rc or "tracer" not in kept:
        return rc or 1

    stem = os.path.join(args.out, f"{args.workload}.{args.seed}")
    with open(stem + f".trace{trace}.json") as f:
        run_rec = json.load(f)
    tracer = kept["tracer"]
    dropped = tracer.dropped
    rids = [r["rid"] for r in run_rec["requests"] if r.get("in_window")]
    waits = queue_waits(tracer, rids)
    summary = {"cell": args.workload, "seed": args.seed, "trace": trace,
               "dropped": dropped, "queue_waits_s": waits,
               "window_step_median_s": _window_step_median(run_rec),
               "readings": {"queue_wait_p90_s": queue_wait_p90_s(waits,
                                                                 dropped)}}
    if args.no_profile:
        write_chrome_trace(stem + ".ring.json", tracer)
        print(json.dumps(summary), flush=True)
        return 0

    cell = bench_run.load_cell(args.workload)
    kernels = {}
    for m in cell["per_layer"]:
        kernels.update(getattr(bench_run.load_reader(m["name"]), "KERNELS",
                               {}))
    log_dir = os.path.join(args.out, "trace", f"{args.workload}.{args.seed}")
    record = extract(devtrace.find_xspace(log_dir))
    scopes = hlo_op_scopes(kept["hlo"])
    red = reduce(record, kernels, scopes)
    fit = window_clock_fit(record)
    write_chrome_trace(stem + ".ring.json", tracer,
                       clock_offset_ns=fit.offset_ns)
    with open(stem + ".round.hlo.txt", "w") as f:
        f.write(kept["hlo"])
    with open(stem + ".phases.json", "w") as f:
        json.dump({"phases": record["phases"],
                   "scopes": {k: list(v) for k, v in scopes.items()}}, f)

    busy_ops = sum(red["scope_s"].values())
    scope_split = sorted(red["scope_s"].items(), key=lambda kv: -kv[1])
    summary.update({
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "rounds_in_window": red["rounds_in_window"],
        "engine_idle_s": red["engine_idle_s"],
        "idle_by_label_s": red["idle_by_label_s"],
        "scope_s": dict(scope_split),
        "scope_share": {k: v / busy_ops for k, v in scope_split}
        if busy_ops else {},
        "mixed_s": red["mixed_s"],
        "mixed_share": red["mixed_s"] / busy_ops if busy_ops else None,
        "clock_offset_ns": fit.offset_ns,
        "clock_residual_spread_ns": fit.spread_ns,
        "clock_spans": fit.n,
        "longest_step": longest_step(record),
    })
    summary["readings"].update({
        "engine_idle_pct": engine_idle_pct(red, dropped),
        "mlp_ms_per_round": ms_per_round(red, "mlp", dropped),
        "ssd_scan_ms_per_round": ms_per_round(red, "mamba2.scan", dropped),
    })
    for k, v in scope_split:
        print(f"[phases] scope {k}: {v:.6f} s", flush=True)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
