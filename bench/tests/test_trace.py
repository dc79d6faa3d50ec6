"""Self-check of the trace reduction (``bench/devtrace.py``).

The recorded trace (``bench/testdata/trace_small.json``) is the extracted
event record of a few rounds of a traced ``dit-xl.img-steady`` run on one
v5e, trimmed to a short window, with each op's full HLO text. Its busy
time, per-kernel time and idle gaps are recomputed here independently, on a
1 ns grid with numpy, and must equal what ``reduce`` gives. In that text an
op that consumes a kernel's result names the kernel among its operands
(``%fusion.138 = ... fusion(..., %flash_attention.6, ...)``); such an op is
no call of the kernel.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402

SMALL = os.path.join(BENCH, "testdata", "trace_small.json")


def _brute(record):
    w0, w1 = devtrace.window_of(record)
    base, n = int(w0), int(w1 - w0)
    busy = []
    for ops in record["devices"].values():
        grid = np.zeros(n, bool)
        for name, s, d in ops:
            a, b = max(int(s) - base, 0), min(int(s + d) - base, n)
            if a < b:
                grid[a:b] = True
        busy.append(grid.sum())
    return (w1 - w0) * 1e-9, float(np.mean(busy)) * 1e-9


def _synthetic():
    # window 0..1000 ns; a while op (100..600) holds two ops; one kernel
    ops = [["while.1", 100.0, 500.0],
           ["fusion.1", 100.0, 200.0],
           ["%flash_attention.2", 350.0, 250.0],
           ["fusion.3", 800.0, 300.0]]
    host = [["bench/window", 0.0, 1000.0],
            ["bench/step", 0.0, 1000.0],
            ["dispatch/round", 620.0, 700.0]]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_synthetic_trace():
    red = devtrace.reduce(_synthetic(), {"flash": "%flash_attention"})
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((500 + 200) * 1e-9)
    assert red["kernel_s"] == {"flash": pytest.approx(250e-9)}
    assert red["kernel_calls"] == {"flash": 1}
    assert "while.1" not in red["op_s"]                 # holds other ops
    assert red["op_s"]["fusion.1"] == pytest.approx(200e-9)
    assert red["op_s"]["fusion.3"] == pytest.approx(200e-9)  # clipped
    gaps = {label: d for label, d in red["longest_gaps"]}
    assert gaps["dispatch/round"] == pytest.approx(200e-9)   # 600..800
    assert red["idle_by_label_s"]["bench/step"] == pytest.approx(100e-9)


def test_consumer_of_a_kernel_is_not_a_call():
    flash = ("%flash_attention.6 = bf16[8,24,4096,128]{3,2,1,0} custom-call("
             "bf16[8,24,4096,128]{3,2,1,0} %q, bf16[8,24,4096,128]{3,2,1,0} "
             "%k), custom_call_target=\"tpu_custom_call\"")
    consumer = ("%fusion.138 = bf16[8,4096,3072]{2,1,0} fusion(bf16[8,24,"
                "4096,128]{3,2,1,0} %flash_attention.6, bf16[24,128,3072]"
                "{2,1,0} %wo), kind=kOutput")
    record = {"devices": {"/device:TPU:0": [[flash, 0.0, 400.0],
                                            [consumer, 400.0, 100.0]]},
              "host": [["bench/window", 0.0, 1000.0]]}
    red = devtrace.reduce(record, {"flash": "%flash_attention"})
    assert red["kernel_calls"] == {"flash": 1}
    assert red["kernel_s"] == {"flash": pytest.approx(400e-9)}


def _calls_by_name(record, window, prefix):
    """(calls, seconds) of the ops whose own name starts with ``prefix``,
    clipped to the window."""
    w0, w1 = window
    n, s = 0, 0.0
    for ops in record["devices"].values():
        for text, start, dur in ops:
            if text.split(" ", 1)[0].startswith(prefix) \
                    and start < w1 and start + dur > w0:
                n += 1
                s += (min(start + dur, w1) - max(start, w0)) * 1e-9
    return n, s


def test_recorded_trace():
    with open(SMALL) as f:
        record = json.load(f)
    kernels = {"flash_attention": "%flash_attention",
               "rectify": "fused_step_rectify_accept"}
    red = devtrace.reduce(record, kernels)
    window, busy = _brute(record)
    assert red["window_s"] == pytest.approx(window, rel=1e-9)
    assert red["busy_s"] == pytest.approx(busy, rel=1e-6)
    assert 0 < red["busy_s"] <= red["window_s"]
    w = devtrace.window_of(record)
    for label, prefix in (("flash_attention", "%flash_attention."),
                          ("rectify", "%vmap_jit_fused_step_rectify_accept")):
        calls, seconds = _calls_by_name(record, w, prefix)
        assert calls > 0
        assert red["kernel_calls"][label] == calls
        assert red["kernel_s"][label] == pytest.approx(seconds, rel=1e-9)
    # a flash call at these shapes takes some 75 ms on the v5e
    per_call = (red["kernel_s"]["flash_attention"]
                / red["kernel_calls"]["flash_attention"])
    assert per_call > 0.05
    idle = sum(red["idle_by_label_s"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
