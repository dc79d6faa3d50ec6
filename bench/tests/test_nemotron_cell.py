"""The ``nemotron3-nano.img-backlog`` cell's checks, on the CPU at micro
size, as ``test_harness.py`` and ``test_backbones.py`` make them for the
other cells:

* the plain reference (``bench/backbones/nemotron_h.py``) agrees with the
  program's drift on the same weights, and the float8 control is far off;
* the CPU rehearsal of the cell is ``correct``, and the control fails the
  cell's comparison;
* the family's drawn weights and FLOP counts are the recorded numbers, the
  expert stacks and the router drawn at fan-in ``d_model``;
* ``expert_roofline`` counts each grouped matmul's work from the shapes.
"""
from __future__ import annotations

import json
import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402

CELL = "nemotron3-nano.img-backlog"
# weights.draw(structure, 3) at the rehearsal size; FLOPs of one drift
# evaluation of one latent at the rehearsal size and at 4096 x 64
RECORDED = {
    "weights_sumsq": 6051.595417410528,
    "flops_rehearse": 33742848.0,
    "flops_full": 3279793782784.0,
}
# relative, for another CPU's vector width; on one machine they are equal
RTOL = 1e-6


def _params(seed):
    import jax

    import weights
    from repro.diffusion import init_wrapper

    cell = bench_run.load_cell(CELL)
    cfg, model = bench_run.program_config(cell, rehearse=True)
    latent = bench_run.REHEARSAL_LATENT
    structure = jax.eval_shape(
        lambda k: init_wrapper(cfg, latent[-1], k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    return cell, cfg, model, weights.draw(structure, seed, model["family"]), latent


def test_reference_matches_program_drift():
    import jax
    import jax.numpy as jnp

    import reference
    from repro.diffusion import make_drift

    _, cfg, model, params, latent = _params(3)
    x = jax.random.normal(jax.random.PRNGKey(1), (4,) + latent[1:])
    t = jnp.asarray([0.0, 0.25, 0.5, 0.95], jnp.float32)
    prog = make_drift(params, cfg)
    want = jnp.stack([prog(x[i][None], t[i])[0] for i in range(4)])
    got = reference.make_drift(params, model)(x[:, None], t)[:, 0]
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 1e-4, err
    ctrl = reference.make_drift(params, model, quant="fp8")(x[:, None], t)
    cerr = float(jnp.linalg.norm(ctrl[:, 0] - want) / jnp.linalg.norm(want))
    assert cerr > 100 * err, (cerr, err)


def _rehearse(tmp_path, seed):
    out = str(tmp_path)
    bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "4", "--trace", "0", "--out", out, "--rehearse"])
    with open(os.path.join(out, f"{CELL}.{seed}.trace0.json")) as f:
        return json.load(f)


def test_rehearsal_is_correct(tmp_path):
    record = _rehearse(tmp_path, 2716000011)
    res = record["result"]
    assert res["correct"] is True and res["failed"] == 0, res
    assert res["attempted"] > 0 and record["run"]["unfinished"] == 0
    assert set(res["metrics"]) == {"samples_per_s", "setup_s"}, res


def test_control_fails_the_comparison(tmp_path):
    import calibrate

    cell = bench_run.load_cell(CELL)
    record = _rehearse(tmp_path, 21)
    assert record["result"]["correct"], record["result"]
    limit = cell["config"]["check"]["latent_gap"]
    gaps = [r["gap"] for r in calibrate.control_readings(cell, 21, record,
                                                         True)]
    assert gaps and min(gaps) > limit, (gaps, limit)


def test_family_gives_the_recorded_numbers():
    import jax

    import flops

    cell, cfg, model, params, latent = _params(3)
    sumsq = sum(float(np.sum(np.asarray(a, np.float64) ** 2))
                for a in jax.tree_util.tree_leaves(params))
    assert sumsq == pytest.approx(RECORDED["weights_sumsq"], rel=RTOL)
    assert flops.drift_forward(model, latent[-2], latent[-1]) \
        == RECORDED["flops_rehearse"]
    assert flops.drift_forward(cell["config"]["model"], 4096, 64) \
        == RECORDED["flops_full"]
    # STACKED = {"experts": 1}: [E, d, f] and the router [d, E] at fan-in d
    moe = params["backbone"]["blocks"][1]["moe"]
    d = cfg.d_model
    for w in (moe["experts"]["w_up"], moe["router"]):
        std = float(np.asarray(w, np.float64).std()) * math.sqrt(d)
        assert abs(std - 1.0) < 0.1, std
    assert "e_score_correction_bias" not in moe


def test_expert_roofline_counts_the_grouped_matmuls():
    reader = bench_run.load_reader("expert_roofline")
    m = bench_run.load_cell(CELL)["config"]["model"]
    tr = {"num_slots": 2, "num_cores": 4, "latent_shape": [1, 4096, 64]}
    rows = 2 * 4 * 4096 * 6
    flops_call = 2 * rows * 2688 * 1856  # 2 (S K 4096 6) d_model d_ff
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds = 6 * flops_call / 197e12 / 0.5  # six calls at half the peak
    run = {"model": m, "traffic": tr, "peaks": peaks,
           "reduced_trace": {"kernel_calls": {"expert_matmul": 6},
                             "kernel_s": {"expert_matmul": seconds}}}
    assert reader.read(run) == pytest.approx(50.0)
    assert reader.KERNELS == {"expert_matmul": "%ragged-dot-none"}
    # nothing to read: no trace, or no call of the kernel in it
    assert reader.read(dict(run, reduced_trace=None)) is None
    run["reduced_trace"] = {"kernel_calls": {}, "kernel_s": {}}
    assert reader.read(run) is None
