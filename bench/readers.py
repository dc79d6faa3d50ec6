"""Arithmetic the metric readers in ``bench/metrics/`` share.

Every function takes the run record ``bench/run.py`` builds and returns a
number, or ``None`` where the run has nothing to read (no trace, no chip
peaks, no kernel calls): a share of a roofline or a peak is never 0 for
want of data.
"""
from __future__ import annotations

import math

import numpy as np

import backbones
import flops


def latency_percentile(run, q):
    lat = run["latencies_s"]
    if not lat:
        return None
    # an unfinished window request counts as missing every limit
    lat = list(lat) + [math.inf] * run["unfinished"]
    value = float(np.percentile(np.asarray(lat, np.float64), q))
    return value if math.isfinite(value) else None


def mean_rounds(run):
    r = run["rounds_used"]
    return float(np.mean(r)) if r else None


def step_mfu(run):
    if run["peaks"] is None or not run["busy_wall_s"]:
        return None
    return (100.0 * run["useful_flops"] / run["busy_wall_s"]
            / run["peaks"]["bf16_flops_per_s"])


def device_idle(run):
    red = run["reduced_trace"]
    if red is None or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def _kernel_share(run, label, work):
    red = run["reduced_trace"]
    if red is None or run["peaks"] is None:
        return None
    calls = red["kernel_calls"].get(label, 0)
    seconds = red["kernel_s"].get(label, 0.0)
    if not calls or seconds <= 0:
        return None
    f, b = work
    least = max(f / run["peaks"]["bf16_flops_per_s"],
                b / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds


def attention_roofline(run, label):
    """One kernel call per layer per round, over all S x K lanes at once."""
    m, tr = run["model"], run["traffic"]
    lanes = tr["num_slots"] * tr["num_cores"] * tr["latent_shape"][0]
    seq = tr["latent_shape"][-2]
    causal = backbones.load(m["family"]).ATTENTION_CAUSAL
    work = flops.flash_attention_call(lanes, m["num_heads"], seq, seq,
                                      m["head_dim"], 2, causal=causal)
    return _kernel_share(run, label, work)
