"""Self-check of the phase and scope reduction (``bench/phases.py``) and of
the ``engine_idle.lat`` reader.

* on the recorded trace (``bench/testdata/trace_small.json``) every key
  ``devtrace.reduce`` returns comes back from ``phases.reduce`` with the same
  value: the phase reduction only adds keys;
* on a synthetic record with scoped ops and ``serve/*`` spans, the added
  keys and the readings built on them are what the record says, and a ring
  that dropped events gives no reading;
* an op takes its scope from the round program's HLO text, matched on its
  name and result type; an op of another program is counted apart, and a
  fusion that holds ops of two unnested scopes is counted in ``mixed_s``
  too.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import devtrace  # noqa: E402
import phases  # noqa: E402
from repro.obs.scopes import hlo_op_scopes  # noqa: E402

SMALL = os.path.join(BENCH, "testdata", "trace_small.json")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_recorded_trace_keeps_every_devtrace_key():
    with open(SMALL) as f:
        record = json.load(f)
    kernels = {"flash_attention": "%flash_attention",
               "rectify": "fused_step_rectify_accept"}
    before = devtrace.reduce(record, kernels)
    after = phases.reduce(record, kernels)
    for key, value in before.items():
        assert after[key] == value, key
    assert set(after) - set(before) == {"scope_s", "mixed_s",
                                        "rounds_in_window", "engine_idle_s"}
    # the recorded trace has no engine phase spans and no round program
    # text: the in-step idle is labelled bench/step, not serve/*, and no op
    # is matched to the round program
    assert after["engine_idle_s"] == 0.0
    assert set(after["scope_s"]) == {phases.OTHER}
    assert after["mixed_s"] == 0.0


HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %p = bf16[8]{0} parameter(0)
  %n = bf16[8]{0} negate(%p), metadata={op_name="jit(round_fn)/while/body/norm/neg"}
  ROOT %m = bf16[8]{0} multiply(%n, %n), metadata={op_name="jit(round_fn)/while/body/mlp/mul"}
}

%fused_computation.2 (q: bf16[8]) -> bf16[8] {
  %q = bf16[8]{0} parameter(0)
  %w = bf16[8]{0} slice(%q), metadata={op_name="jit(round_fn)/drift/while/body/dynamic_slice"}
  ROOT %r = bf16[8]{0} negate(%w), metadata={op_name="jit(round_fn)/drift/while/body/attn/neg"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %x = bf16[8]{0} parameter(0)
  %fusion.1 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.4 = bf16[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %dot.2 = bf16[8]{0} dot(%x, %x), metadata={op_name="jit(round_fn)/attn/dot_general"}
  ROOT %add.3 = bf16[8]{0} add(%x, %x), metadata={op_name="jit(round_fn)/add"}
}
"""


def _synthetic():
    # window 0..1000 ns; three leaf ops of the round program, one of
    # another whose name the round program also uses
    ops = [["%fusion.1 = bf16[8]{0} fusion(%x)", 0.0, 300.0],
           ["%dot.2 = bf16[8]{0} dot(%x, %x)", 300.0, 200.0],
           ["%add.3 = bf16[8]{0} add(%x, %x)", 500.0, 100.0],
           ["%fusion.1 = f32[2]{0} fusion(%y)", 900.0, 50.0]]
    phase = [["serve/step", 0.0, 800.0, {"t_ns": 10.0}],
             ["dispatch/round", 50.0, 100.0, {"t_ns": 60.0, "rounds": 1}],
             ["serve/drain", 700.0, 790.0, {"t_ns": 710.0, "lanes": 1}],
             ["serve/step", 800.0, 1000.0, {"t_ns": 810.0}],
             ["dispatch/multi", 820.0, 850.0, {"t_ns": 830.0, "rounds": 3}],
             ["dispatch/round", 1100.0, 1150.0, {"t_ns": 1110.0,
                                                  "rounds": 1}]]
    host = [["bench/window", 0.0, 1000.0]] + [p[:3] for p in phase]
    return {"devices": {"/device:TPU:0": ops}, "host": host,
            "phases": phase}


def test_synthetic_record():
    record = _synthetic()
    red = phases.reduce(record, scopes=hlo_op_scopes(HLO))
    assert red["scope_s"] == {"mlp": pytest.approx(300e-9),
                              "attn": pytest.approx(200e-9),
                              "None": pytest.approx(100e-9),
                              phases.OTHER: pytest.approx(50e-9)}
    assert red["mixed_s"] == pytest.approx(300e-9)    # fusion.1: norm, mlp
    assert red["rounds_in_window"] == 1 + 3       # the 1100 ns one is out
    # idle 600..900 is labelled by the span over its middle, serve/drain;
    # 950..1000 by serve/step
    assert red["engine_idle_s"] == pytest.approx(350e-9)
    assert red["idle_by_label_s"] == {"serve/drain": pytest.approx(300e-9),
                                      "serve/step": pytest.approx(50e-9)}
    assert phases.engine_idle_pct(red, 0) == pytest.approx(35.0)
    assert phases.ms_per_round(red, "mlp", 0) == pytest.approx(
        1e3 * 300e-9 / 4)
    assert phases.ms_per_round(red, "mamba2.scan", 0) is None
    fit = phases.window_clock_fit(record)
    assert fit.offset_ns == -10.0 and fit.n == 5
    step = phases.longest_step(record)
    assert step["seconds"] == pytest.approx(800e-9)
    assert step["phases"] == {"dispatch/round": pytest.approx(50e-9),
                              "serve/drain": pytest.approx(90e-9)}
    # a ring that dropped events gives no reading
    assert phases.engine_idle_pct(red, 1) is None
    assert phases.ms_per_round(red, "mlp", 1) is None
    assert phases.queue_wait_p90_s([1.0, 2.0], 1) is None
    assert phases.queue_wait_p90_s([], 0) is None
    assert phases.queue_wait_p90_s(list(range(11)), 0) == pytest.approx(9.0)


def test_engine_idle_reader_counts_in_step_labels_only():
    red = {"window_s": 10.0,
           "idle_by_label_s": {"bench/step": 0.05, "dispatch/round": 0.02,
                               "serve/decide": 0.01, "verify/readback": 0.01,
                               "bench/wait": 5.0, "no host span": 0.3}}
    read = _reader("engine_idle.lat").read
    assert read({"reduced_trace": red}) == pytest.approx(0.9)
    assert read({"reduced_trace": None}) is None


def test_op_scope_matches_name_and_result_type():
    smap = hlo_op_scopes(HLO)
    assert smap["dot.2"] == ("attn", "bf16[8]{0}", False)
    # a fusion takes its root's scope, and is mixed if its ops lie in two
    # scopes neither of which holds the other: norm and mlp are, but an
    # attn op and a slice in the drift around it are not
    assert smap["fusion.1"] == ("mlp", "bf16[8]{0}", True)
    assert smap["fusion.4"] == ("attn", "bf16[8]{0}", False)
    assert phases.round_op("%dot.2 = bf16[8]{0} dot(%x, %x)",
                           smap).scope == "attn"
    assert phases.round_op("%add.3 = bf16[8]{0} add(%x, %x)",
                           smap).scope is None
    assert phases.round_op("%dot.2 = f32[9]{0} dot(%a, %b)", smap) is None
    assert phases.round_op("%copy.9 = bf16[8]{0} copy(%x)", smap) is None
