"""The benchmark's own checks, on the CPU at micro size.

    python -m pytest bench/tests

* the plain reference agrees with the program's drift on the same weights
  (both float32 at micro size), for both configurations;
* the control (the reference with float8 matmul operands) fails the
  cell's comparison;
* a run whose timed path is broken underneath comes out ``correct: false``,
  for each fault a one-chip serving cell can have: a round that returns
  its state unchanged, and an answer altered where the engine produces it.
  (Leaving out the rectification between cores is no such fault: core 0,
  the sequential solve, is then always the one accepted, and its latent
  agrees with the reference's answer; it shows as more rounds per sample.)
* a program whose default init sequence is not the one the traffic file
  states comes out ``correct: false``: the reference never takes the
  program's sequence.

These drive ``bench/run.py`` in its CPU rehearsal, which skips the look for
a chip and runs everything else.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402

CELLS = ["dit-xl.img-steady", "zamba2.img-backlog"]


def _params(cell, seed):
    import jax

    import weights
    from repro.diffusion import init_wrapper

    cfg, model = bench_run.program_config(cell, rehearse=True)
    latent = bench_run.REHEARSAL_LATENT
    structure = jax.eval_shape(
        lambda k: init_wrapper(cfg, latent[-1], k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    return cfg, model, weights.draw(structure, seed, model["family"]), latent


@pytest.mark.parametrize("name", CELLS)
def test_reference_matches_program_drift(name):
    import jax
    import jax.numpy as jnp

    import reference
    from repro.diffusion import make_drift

    cell = bench_run.load_cell(name)
    cfg, model, params, latent = _params(cell, 3)
    x = jax.random.normal(jax.random.PRNGKey(1), (4,) + latent[1:])
    t = jnp.asarray([0.0, 0.25, 0.5, 0.95], jnp.float32)
    prog = make_drift(params, cfg)
    want = jnp.stack([prog(x[i][None], t[i])[0] for i in range(4)])
    got = reference.make_drift(params, model)(x[:, None], t)[:, 0]
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert err < 1e-4, err
    # the control is far from both
    ctrl = reference.make_drift(params, model, quant="fp8")(x[:, None], t)
    cerr = float(jnp.linalg.norm(ctrl[:, 0] - want) / jnp.linalg.norm(want))
    assert cerr > 100 * err, (cerr, err)


def _rehearse(tmp_path, name, seed, edit=None):
    out = str(tmp_path)
    bench_run.main(["--workload", name, "--seed", str(seed), "--seconds",
                    "4", "--trace", "0", "--out", out, "--rehearse"],
                   edit_cell=edit)
    with open(os.path.join(out, f"{name}.{seed}.trace0.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_comparison(tmp_path, name):
    import calibrate

    cell = bench_run.load_cell(name)
    record = _rehearse(tmp_path, name, 21)
    assert record["result"]["correct"], record["result"]
    limit = cell["config"]["check"]["latent_gap"]
    gaps = [r["gap"] for r in calibrate.control_readings(cell, 21, record,
                                                         True)]
    assert gaps and min(gaps) > limit, (gaps, limit)


def _short_drain(cell):
    return dict(cell, traffic=dict(cell["traffic"], drain_limit_s=3.0))


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    from repro.serve import executor

    real = executor._grid_fns

    def frozen(*a, **kw):
        fns = dict(real(*a, **kw))
        fns["round"] = lambda params, st: st
        return fns

    load = bench_run.load_cell
    monkeypatch.setattr(executor, "_grid_fns", frozen)
    monkeypatch.setattr(bench_run, "load_cell",
                        lambda name: _short_drain(load(name)))
    res = _rehearse(tmp_path, "dit-xl.img-steady", 22)["result"]
    assert res["correct"] is False and res["failed"] > 0, res


def test_other_init_sequence_is_not_correct(tmp_path, monkeypatch):
    # the reference runs the traffic file's sequence, never the program's
    from repro.serve.sched import cost

    monkeypatch.setattr(cost, "make_sequence", lambda k, n: [0, 2, 6, 13])
    res = _rehearse(tmp_path, "dit-xl.img-steady", 24)["result"]
    assert res["correct"] is False, res
    assert res["checks"]["init_sequence_differs"]["value"] == 1, res


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.serve import engine

    real = engine.ContinuousEngine._finish_lane

    def altered(self, item, i_seq, ru, chosen_k, sample, *a, **kw):
        sample = np.asarray(sample) * 1.1
        return real(self, item, i_seq, ru, chosen_k, sample, *a, **kw)

    monkeypatch.setattr(engine.ContinuousEngine, "_finish_lane", altered)
    res = _rehearse(tmp_path, "dit-xl.img-steady", 23)["result"]
    assert res["correct"] is False, res
