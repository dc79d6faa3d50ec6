#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process owns.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is ``BENCHMARK.json``'s workload of that name; its configuration
(``bench/configs/<config>.json``), traffic mix (``bench/traffic/<traffic>.json``)
and per-layer metric readers (``bench/metrics/<metric>.py``) are found by
name. The run draws the weights and every request from ``--seed``, builds
``ContinuousEngine`` from what those files define (latent shape, N steps,
``uniform_tgrid(N)``, K cores, S slots, ``rtol``, kernel flags; every other
knob at the program's default), warms up, drives the traffic for
``--seconds``, lets the window's requests finish, reads the device's peak
memory, frees the engine, and checks a sample of the window's answers
against the plain reference (``bench/reference.py``, ``bench/check.py``).
The reference runs the init sequence the traffic file states, and a run
whose program uses another is not correct; a configuration file whose model
numbers are not the program's stops the run before anything is timed.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window is traced by the profiler and the line
carries the per-layer metrics, the device's busy and window seconds and a
breakdown. The last line of standard output is that JSON object; the
numbers compared are the last lines of standard error. A per-run record
goes to ``--out`` (default ``bench_out/``).

It exits non-zero with no result line when JAX's first device is not a TPU
listed in ``bench/peaks.json``, or sees fewer chips than the cell asks for.
``--rehearse`` runs the whole path on the CPU at micro size (reduced
configuration, 128-token latents, a short window) and prints no chip
result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
REHEARSAL_LATENT = (1, 128, 16)
N_KEYS = 8192


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at micro size; no chip result")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the raw profiler trace and the extracted "
                         "event record in --out")
    return ap.parse_args(argv)


# -- the cell, from the files ------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if not wl:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    conf = [c for c in bench["configs"] if c["name"] == wl["config"]][0]

    def for_cell(metrics, reported=None):
        out = []
        for m in metrics:
            cells = m.get("workloads")
            if cells is None:
                cells = [name] if (reported is None
                                   or m["moves"] in reported) else []
            if name in cells:
                out.append(m)
        return out

    e2e = for_cell(bench["end_to_end"])
    return {
        "name": name, "workload": wl,
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "traffic": load_json(os.path.join(BENCH, "traffic",
                                          wl["traffic"] + ".json")),
        "end_to_end": e2e,
        "per_layer": for_cell(bench["per_layer"], {m["name"] for m in e2e}),
    }


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- device -------------------------------------------------------------------

def check_device(chips: int, rehearse: bool):
    """(device, peaks): a TPU listed in peaks.json with enough chips, or
    SystemExit. The rehearsal takes whatever JAX has and has no peaks."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if rehearse:
        return dev, None
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if dev.device_kind not in peaks:
        raise SystemExit(f"bench: device kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return dev, peaks[dev.device_kind]


# -- the program under test --------------------------------------------------

def program_config(cell: dict, rehearse: bool):
    """(program cfg, model numbers for the reference). The model's family
    must have its file, ``bench/backbones/<family>.py``."""
    import backbones
    from repro.configs import get_config

    conf = cell["config"]
    backbones.load(conf["model"]["family"])
    kern = conf["kernels"]
    if rehearse:
        cfg = get_config(conf["arch"], reduced=True).replace(
            use_kernels=kern["backbone"])
        model = dict(conf["model"])
        for key in model:
            if key == "head_dim":
                model[key] = cfg.resolved_head_dim
            elif hasattr(cfg, key):
                model[key] = getattr(cfg, key)
        return cfg, model
    cfg = get_config(conf["arch"]).replace(use_kernels=kern["backbone"],
                                           **conf["changes"])
    model = conf["model"]
    differ = []
    for key, want in model.items():
        have = cfg.resolved_head_dim if key == "head_dim" else getattr(
            cfg, key, None)
        if have != want:
            differ.append(f"{key} is {have!r} in the program, {want!r} in "
                          f"the file")
    if differ:
        # the reference would model another network than the one timed
        raise SystemExit(f"bench: configuration differs from "
                         f"{conf['arch']}: " + "; ".join(differ))
    return cfg, model


def build_engine(cfg, traffic: dict, params, latent_shape, round_kernel,
                 tracer):
    from repro.core.ode import uniform_tgrid
    from repro.diffusion import make_drift
    from repro.serve import ContinuousEngine

    n = traffic["n_steps"]
    return ContinuousEngine(
        make_drift(params, cfg), latent_shape=latent_shape, n_steps=n,
        num_cores=traffic["num_cores"], tgrid=uniform_tgrid(n),
        num_slots=traffic["num_slots"], rtol=traffic["rtol"],
        use_kernel=round_kernel, tracer=tracer)


# -- the run ------------------------------------------------------------------

def serve(cell, args, engine, keys, compiles, t_start):
    """Set-up warm-up, the window, the drain. Returns the run record."""
    import jax

    import traffic as traffic_mod
    from engine_loop import Annotation, EngineLoop, overlap
    from repro.serve import Request

    tr = cell["traffic"]
    s_slots = tr["num_slots"]
    drain_limit = tr["drain_limit_s"]
    d = EngineLoop(engine, lambda rid: Request(rid=rid, key=keys[rid]),
               annotate=bool(args.trace))
    # warm-up: a full grid admitted together, then one request alone, each
    # served to its end, so every program and transfer shape the window
    # uses (admit and drain of one and of S lanes) is compiled or loaded
    first = list(range(s_slots))
    for rid in first:
        d.submit(rid)
    warm_ok = d.run_until_done(first, drain_limit)
    if warm_ok:
        d.submit(s_slots)
        warm_ok = d.run_until_done([s_slots], drain_limit)
    if not warm_ok:
        log(f"warm-up requests not done in {drain_limit} s")
        t = d.now()
        return d, {"setup_s": time.perf_counter() - t_start,
                   "window": (t, t), "window_rids": list(d.order),
                   "counters": {"start": (0, 0), "end": (0, 0),
                                "t_open": t, "t_close": t},
                   "log_dir": None}
    warm_end = time.perf_counter()
    setup_s = warm_end - t_start
    next_rid = s_slots + 1
    log(f"set-up {setup_s:.3f} s (to the end of warm-up); compiles so far: "
        f"{len(compiles.events)}, "
        f"{sum(e[2] for e in compiles.events):.2f} s")

    arr = tr["arrivals"]
    profiling = False
    log_dir = os.path.join(args.out, "trace", f"{cell['name']}.{args.seed}")

    def start_profile():
        nonlocal profiling
        if args.trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            profiling = True

    def stop_profile():
        nonlocal profiling
        if profiling:
            jax.profiler.stop_trace()
            profiling = False

    win_ann = Annotation("bench/window", bool(args.trace))
    counters = {}

    def read_counters():
        m = engine.metrics
        return (m["serve.occupancy.live_rounds"].value,
                m["serve.occupancy.slot_rounds"].value)

    if arr["process"] == "poisson":
        start_profile()
        t_sched = d.now()
        schedule = [a._replace(due=t_sched + a.due) for a in
                    traffic_mod.poisson(arr, args.seconds, drain_limit)]
        w0, w1 = (t_sched + w for w in
                  traffic_mod.window_bounds(arr, args.seconds))
        window_rids, i = [], 0
        opened = closed = False
        while True:
            t = d.now()
            while i < len(schedule) and schedule[i].due <= t:
                rid = next_rid
                next_rid += 1
                d.submit(rid, due=schedule[i].due,
                         in_window=schedule[i].in_window)
                if schedule[i].in_window:
                    window_rids.append(rid)
                i += 1
            if not opened and t >= w0:
                opened = True
                win_ann.__enter__()
                counters["start"] = read_counters()
                counters["t_open"] = t
            if opened and not closed and t >= w1:
                closed = True
                win_ann.__exit__(None, None, None)
                counters["end"] = read_counters()
                counters["t_close"] = t
                stop_profile()
            if closed and all("finished" in d.req[r] for r in window_rids):
                break
            if t > w1 + drain_limit:
                break
            if d.outstanding:
                d.step()
            elif i < len(schedule):
                d.wait_until(schedule[i].due)
            else:
                break
    elif arr["process"] == "backlog":
        depth = int(arr["depth_per_slot"]) * s_slots
        start_profile()
        for _ in range(depth):
            d.submit(next_rid)
            next_rid += 1
        d.step()
        w0 = d.now()
        w1 = w0 + args.seconds
        win_ann.__enter__()
        counters["start"] = read_counters()
        counters["t_open"] = w0
        in_flight_at_close = None
        while True:
            done = d.step()
            t = d.now()
            if in_flight_at_close is None:
                for _ in done:
                    d.submit(next_rid)
                    next_rid += 1
                if t >= w1:
                    win_ann.__exit__(None, None, None)
                    counters["end"] = read_counters()
                    counters["t_close"] = t
                    stop_profile()
                    waiting = [r for r in d.order
                               if "finished" not in d.req[r]]
                    # FIFO admission: the queue holds the newest submissions
                    in_flight_at_close = waiting[:len(waiting)
                                                 - len(engine.queue)]
            if in_flight_at_close is not None and all(
                    "finished" in d.req[r] for r in in_flight_at_close):
                break
            if t > w1 + drain_limit:
                break
        rounds = d.round_intervals()
        for rid in d.order:
            if "finished" not in d.req[rid]:
                continue
            inside = sum(overlap(*rounds[g], w0, w1) / (rounds[g][1]
                                                        - rounds[g][0])
                         for g in d.rounds_of(rid))
            d.req[rid]["window_rounds"] = inside
            d.req[rid]["in_window"] = inside > 0
        window_rids = [r for r in d.order if d.req[r].get("in_window")]
        window_rids += [r for r in (in_flight_at_close or [])
                        if r not in window_rids]
    else:
        raise SystemExit(f"bench: unknown arrival process {arr['process']!r}")
    stop_profile()
    if not counters.get("end"):
        counters["end"] = read_counters()
        counters["t_close"] = d.now()
    return d, {"setup_s": setup_s, "window": (w0, w1),
               "window_rids": window_rids, "counters": counters,
               "log_dir": log_dir}


def useful_evaluations(i_seq, n: int, rounds_used: int):
    """Per round r = 1..rounds_used, the cores still short of t=1 (core k
    emits at round ``n - i_seq[k] + k``); finished cores are not useful."""
    emit = [n - i + k for k, i in enumerate(i_seq)]
    return [sum(1 for e in emit if e >= r) for r in range(1, rounds_used + 1)]


def accounting(d, info, cell, i_seq, flops_fwd):
    """Window quantities every reader may use."""
    from engine_loop import overlap

    w0, w1 = info["window"]
    n = cell["traffic"]["n_steps"]
    rounds = d.round_intervals()
    useful = busy = 0.0
    for rid in d.order:
        r = d.req[rid]
        if "finished" not in r:
            continue
        per_round = useful_evaluations(i_seq, n, r["rounds_used"])
        for g, ev in zip(d.rounds_of(rid), per_round):
            a, b = rounds[g]
            useful += ev * flops_fwd * overlap(a, b, w0, w1) / (b - a)
    for a, b in rounds.values():
        busy += overlap(a, b, w0, w1)
    wr = [d.req[r] for r in info["window_rids"]]
    done = [r for r in wr if "finished" in r]
    c0, c1 = info["counters"]["start"], info["counters"]["end"]
    return {
        "window_s": w1 - w0,
        "attempted": len(wr),
        "unfinished": len(wr) - len(done),
        "latencies_s": [r["finished"] - r["due"] for r in done],
        "samples_in_window": sum(r.get("window_rounds", 0.0)
                                 / r["rounds_used"] for r in done),
        "rounds_used": [r["rounds_used"] for r in done],
        "live_rounds": c1[0] - c0[0],
        "slot_rounds": c1[1] - c0[1],
        "useful_flops": useful,
        "busy_wall_s": busy,
        "generator_lag_s": [r["submitted"] - r["due"] for r in wr],
    }


def check_answers(d, info, cell, params, model, i_seq, latent_shape, keys,
                  seed):
    """Reference check of a sample of the window's answers."""
    import jax
    import numpy as np

    import check
    import reference

    tr = cell["traffic"]
    limit = cell["config"]["check"]["latent_gap"]
    done = [r for r in info["window_rids"] if "finished" in d.req[r]]
    if not done:
        return [], limit
    rng = np.random.default_rng(seed + 2)
    longest = max(done, key=lambda r: d.req[r]["rounds_used"])
    rest = [r for r in done if r != longest]
    k = min(len(rest), max(0, tr["check_sample"] - 1))
    sample = [longest] + [int(x) for x in rng.choice(rest, k, replace=False)]
    f = reference.make_drift(params, model)
    results = []
    for rid in sample:
        r = d.req[rid]
        t0 = time.perf_counter()
        x0 = np.asarray(jax.random.normal(jax.numpy.asarray(keys[rid]),
                                          latent_shape), np.float32)
        em = reference.chords(f, x0, i_seq, tr["n_steps"], tr["rtol"],
                              min_rounds=r["rounds_used"])
        res = check.judge(r["latent"], r["rounds_used"], r["core"], em,
                          tr["rtol"])
        res["rid"] = rid
        res["seconds"] = time.perf_counter() - t0
        results.append(res)
        log(f"check request {rid}: gap {res['gap']:.6g}, program "
            f"round/core {res['program']}, reference {res['reference']}, "
            f"ratios {res['ratios']}, {res['seconds']:.2f} s")
    return results, limit


def main(argv=None, edit_cell=None) -> int:
    """One run. ``edit_cell`` (used by ``bench/sweep.py``) may change the
    loaded cell, such as its arrival rate, before the run."""
    t_start = time.perf_counter()
    args = parse_args(argv)
    cell = load_cell(args.workload)
    if edit_cell is not None:
        cell = edit_cell(cell)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    cold = not (os.path.isdir(CACHE_DIR) and os.listdir(CACHE_DIR))
    os.makedirs(args.out, exist_ok=True)
    if not args.rehearse:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        # the TPU runtime logs under /tmp/tpu_logs unless told otherwise
        os.environ.setdefault("TPU_LOG_DIR", os.path.join(args.out,
                                                          "tpu_logs"))
    tr = cell["traffic"]
    if args.rehearse:
        tr = dict(tr, latent_shape=list(REHEARSAL_LATENT))
        cell = dict(cell, traffic=tr)
    latent_shape = tuple(tr["latent_shape"])
    cfg, model = program_config(cell, args.rehearse)

    import jax
    import numpy as np

    import flops
    import devtrace as trace_mod
    import weights
    from engine_loop import CompileLog, GcLog

    dev, peaks = check_device(cell["workload"]["chips"], args.rehearse)
    if not args.rehearse:
        from repro.utils.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        enable_compile_cache()
    compiles = CompileLog()
    gc_log = GcLog()
    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {'empty' if cold else 'not empty'}")

    from repro.diffusion import init_wrapper
    from repro.obs import Tracer

    rng = np.random.default_rng(args.seed)
    wseed, kseed = (int(x) for x in rng.integers(0, 2 ** 31 - 1, 2))
    structure = jax.eval_shape(
        lambda k: init_wrapper(cfg, latent_shape[-1], k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    params = jax.block_until_ready(weights.draw(structure, wseed,
                                                model["family"]))
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(kseed), N_KEYS))
    engine = build_engine(cfg, tr, params, latent_shape,
                          cell["config"]["kernels"]["round"],
                          Tracer() if args.trace else None)
    # the reference and the accounting take the init sequence the traffic
    # file states; the program's own must be the same
    i_seq = [int(i) for i in tr["init_sequence"]]
    prog_seq = [int(i) for i in engine.cost.seq_for_level(0)]
    log(f"model {cfg.name}: {cfg.num_layers} layers, latents "
        f"{latent_shape}, N={tr['n_steps']} K={tr['num_cores']} "
        f"S={tr['num_slots']} rtol={tr['rtol']}, init sequence {i_seq} "
        f"(the program's {prog_seq}), kernel path "
        f"{engine.executor.kernel_path}")

    d, info = serve(cell, args, engine, keys, compiles, t_start)
    log(f"setup_s {info['setup_s']:.3f} (the compile cache was "
        + ("empty: this run compiled" if cold else
           "not empty: what earlier runs in this checkout compiled loads")
        + ")")
    flops_fwd = flops.drift_forward(model, latent_shape[-2],
                                    latent_shape[-1])
    acc = accounting(d, info, cell, i_seq, flops_fwd)
    w0_abs = d.origin + info["counters"]["t_open"]
    w1_abs = d.origin + info["counters"]["t_close"]
    in_window = compiles.between(w0_abs, w1_abs)
    stats = {}
    for dv in jax.local_devices():
        ms = dv.memory_stats() or {}
        stats[dv.id] = ms.get("peak_bytes_in_use", 0)
    peak_bytes = max(stats.values()) if stats else 0
    lag = acc["generator_lag_s"]
    pauses = gc_log.between(w0_abs, w1_abs)
    log(f"garbage collections inside the window: {len(pauses)}, longest "
        f"{max((p[1] for p in pauses), default=0.0):.4f} s")
    log(f"compiles inside the window: {len(in_window)} "
        f"({', '.join(e[1] for e in in_window) or 'none'})")
    log(f"generator lag s: mean {statistics.fmean(lag) if lag else 0:.4f}, "
        f"max {max(lag) if lag else 0:.4f} over {len(lag)} requests")
    log(f"peak_bytes_in_use {peak_bytes}")
    log(f"window {acc['window_s']:.3f} s: {acc['attempted']} requests, "
        f"{acc['unfinished']} unfinished, {len(d.steps)} steps, "
        f"rounds used {acc['rounds_used']}")

    # free the program's state before the reference runs
    del engine
    d.engine = None
    trace_red = None
    if args.trace and info["log_dir"] is not None:
        kernels = {}
        for m in cell["per_layer"]:
            kernels.update(getattr(load_reader(m["name"]), "KERNELS", {}))
        record = trace_mod.extract(trace_mod.find_xspace(info["log_dir"]))
        trace_red = trace_mod.reduce(record, kernels)
        if args.keep_trace:
            with open(info["log_dir"] + ".events.json", "w") as f:
                json.dump(record, f)
        else:
            shutil.rmtree(info["log_dir"], ignore_errors=True)
        log(f"trace: window {trace_red['window_s']:.4f} s, busy "
            f"{trace_red['busy_s']:.4f} s, kernels "
            f"{trace_red['kernel_s']} calls {trace_red['kernel_calls']}")

    results, limit = check_answers(d, info, cell, params, model, i_seq,
                                   latent_shape, keys, args.seed)
    gap = max((r["gap"] for r in results), default=math.inf)
    seq_differs = int(prog_seq != i_seq)
    failed = acc["unfinished"] + sum(1 for r in results
                                     if not r["gap"] <= limit)
    correct = bool(acc["attempted"] > 0 and failed == 0 and results
                   and not seq_differs)

    run = {"cell": cell["name"], "seed": args.seed, "trace": args.trace,
           "setup_s": info["setup_s"], "peaks": peaks, "model": model,
           "traffic": tr, "i_seq": i_seq, "flops_per_forward": flops_fwd,
           "reduced_trace": trace_red, "compiles_in_window": len(in_window),
           "memory_peak_bytes": peak_bytes, **acc}
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        value = load_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": acc["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        result["breakdown"] = trace_mod.breakdown(trace_red)
    result["checks"] = {"latent_gap": {"value": gap if math.isfinite(gap)
                                       else None, "limit": limit},
                        "unfinished": {"value": acc["unfinished"],
                                       "limit": 0},
                        "init_sequence_differs": {"value": seq_differs,
                                                  "limit": 0}}

    out_path = os.path.join(args.out, f"{cell['name']}.{args.seed}."
                                      f"trace{args.trace}.json")
    with open(out_path, "w") as f:
        reqs = [{k: v for k, v in d.req[r].items() if k != "latent"}
                for r in d.order]
        json.dump({"run": run, "result": result, "checks": results,
                   "requests": reqs, "gc_pauses": gc_log.events,
                   "compiles": compiles.events,
                   "steps": d.steps}, f, default=str)
    log(f"run record: {out_path}")
    sys.stderr.flush()
    print(f"check latent_gap {gap!r} limit {limit!r}", file=sys.stderr)
    print(f"check unfinished {acc['unfinished']} limit 0", file=sys.stderr)
    print(f"check init_sequence_differs {seq_differs} limit 0",
          file=sys.stderr, flush=True)
    line = json.dumps(result)
    if args.rehearse:
        print(f"[rehearsal, not a chip result] {line}", flush=True)
    else:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
