"""Time the dropless MoE layer's two grouped matmuls alone on one TPU, at a
round's rows, under a list of ragged-dot tilings, beside the time
``repro.models.moe._ragged_time`` models for each and the tiling
``_ragged_tiles`` picks.

    python benchmarks/ragged_tiles.py --out bench_out/ragged_tiles.json

The shapes are those of ``nemotron3-nano.img-backlog``: 196,608 rows (8
latents of 4,096 tokens, 6 experts a token) over 128 experts, up
``[rows, 2688] x [128, 2688, 1856]`` and down ``[rows, 1856] x
[128, 1856, 2688]``, bf16 in and out. Group sizes are drawn log-normal
(sigma 0.8, seeded) to the cell's expert load: mean 1,536 rows, the largest
3-5x that, the smallest a few percent, no idle expert.

Each call's device time is read from a profiler trace: the summed duration
of the ops named ``ragged-dot-none`` (what ``bench/metrics/expert_roofline.py``
reads), call by call in the order they ran. The host time of a call (the
up call's also copies ``w`` to the kernel's layout) is printed beside it.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from repro.models import moe

ROWS, EXPERTS, D, F = 196608, 128, 2688, 1856
CALLS = {"up": (D, F), "down": (F, D)}
TILINGS = {  # beside each call's pick and XLA's default
    "up": [(512, 512, 512), (512, 1024, 1024), (512, 384, 1920), (512, 896, 640),
           (512, 1408, 640), (512, 896, 1024), (256, 384, 1920), (256, 768, 1920),
           (128, 1024, 1024), (1024, 512, 1024), (1024, 896, 640), (256, 896, 640)],
    "down": [(512, 512, 512), (512, 1024, 1024), (256, 384, 2688), (512, 640, 1408),
             (512, 1024, 896), (256, 512, 2688), (512, 384, 1408), (512, 512, 896),
             (128, 1024, 1024), (1024, 512, 1024), (1024, 640, 896), (256, 640, 896)],
}


def group_sizes(seed=2717):
    w = np.exp(0.8 * np.random.default_rng(seed).standard_normal(EXPERTS))
    sizes = np.floor(w / w.sum() * ROWS).astype(np.int32)
    sizes[np.argmax(sizes)] += ROWS - sizes.sum()
    return sizes


def _call(tiles):
    def f(x, w, g):
        if tiles is None:
            return jax.lax.ragged_dot(x, w, g)
        with set_xla_metadata(ragged_dot_tiling=",".join(map(str, tiles))):
            return jax.lax.ragged_dot(x, w, g)
    return jax.jit(f)


def ragged_dot_ns(log_dir):
    """Durations of the ``ragged-dot-none`` ops on the first TPU, in the
    order they started."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    planes = sorted((p for p in ProfileData.from_file(path).planes
                     if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    ops = [(e.start_ns, e.duration_ns) for plane in planes[:1] for line in plane.lines
           if line.name == "XLA Ops" for e in line.events
           if e.name.startswith("%ragged-dot-none")]  # the op's HLO text
    return [d for _, d in sorted(ops)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    sizes = jnp.asarray(group_sizes())
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    runs = []
    for (name, (k, n)), kx, kw in zip(CALLS.items(), keys[::2], keys[1::2]):
        x = jax.random.normal(kx, (ROWS, k), jnp.bfloat16)
        w = (jax.random.normal(kw, (EXPERTS, k, n), jnp.float32)
             / np.sqrt(k)).astype(jnp.bfloat16)
        pick = moe._ragged_tiles(ROWS, k, n, EXPERTS, 2)
        for tiles in [None, pick] + [t for t in TILINGS[name] if t != pick]:
            f = _call(tiles)
            t0 = time.perf_counter()
            f(x, w, sizes).block_until_ready()
            compile_s = time.perf_counter() - t0
            f(x, w, sizes).block_until_ready()
            runs.append({"call": name, "tiles": tiles, "pick": tiles == pick,
                         "modelled_ms": 1e3 * moe._ragged_time(
                             ROWS, k, n, EXPERTS, 2, tiles or (512, 128, 128)),
                         "compile_s": compile_s, "f": f, "args": (x, w, sizes)})
    log_dir = tempfile.mkdtemp(prefix="ragged_tiles_")
    jax.profiler.start_trace(log_dir)
    for r in runs:
        t0 = time.perf_counter()
        for _ in range(args.iters):
            y = r["f"](*r["args"])
        y.block_until_ready()
        r["host_ms"] = 1e3 * (time.perf_counter() - t0) / args.iters
    jax.profiler.stop_trace()
    ns = ragged_dot_ns(log_dir)
    whole = len(ns) == args.iters * len(runs)
    out = []
    for i, r in enumerate(runs):
        dev_ms = (1e-6 * float(np.median(ns[i * args.iters:(i + 1) * args.iters]))
                  if whole else None)
        row = {k: r[k] for k in ("call", "tiles", "pick", "modelled_ms",
                                 "host_ms", "compile_s")}
        row["device_ms"] = dev_ms
        out.append(row)
        print(json.dumps(row), flush=True)
    summary = {"device": dev.device_kind, "rows": ROWS, "iters": args.iters,
               "ops_traced": len(ns), "ops_ns": ns, "group_sizes": np.asarray(sizes).tolist(),
               "runs": out}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(summary, fh, indent=1)
    print(json.dumps({k: summary[k] for k in ("device", "rows", "ops_traced")}))


if __name__ == "__main__":
    main()
