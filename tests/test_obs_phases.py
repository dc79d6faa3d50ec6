"""Engine phases on the profiler clock, and the backbone's named scopes.

* **phases** — the synchronous and the overlap engine record every phase
  (``serve/step`` holding ``serve/decide``, ``dispatch/admit``, a round
  dispatch, ``verify/readback`` and ``serve/drain``) under the same names,
  each nested in its ``serve/step``;
* **no cost off** — with ``NULL_TRACER`` the engine builds no profiler
  annotation at all, and the samples stay bitwise equal;
* **clock fit** — under a CPU ``jax.profiler`` capture, the offset fitted
  from the phases' ``t_ns`` arguments maps each ring ``dispatch/round``
  onto its annotation in the ``.xplane.pb``;
* **scopes** — the compiled DiT and Zamba2 drifts (reduced size) and the
  round program carry every scope of the vocabulary in their ``op_name``
  metadata, and ``hlo_op_scopes`` reads them back per op.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import uniform_tgrid
from repro.obs import Tracer, fit_offset, profiler_phases
from repro.obs.scopes import SCOPES, hlo_op_scopes, scope_of
from repro.serve import ContinuousEngine, Request

N, K = 16, 4
TG = uniform_tgrid(N, 0.98)
LAM = jnp.linspace(0.1, 1.5, 4)
PHASES = {"serve/step", "serve/decide", "dispatch/admit", "verify/readback",
          "serve/drain"}


def _drift(x, t):
    return -x * LAM


def _serve(tracer=None, overlap=False, n_req=3):
    eng = ContinuousEngine(_drift, (4,), N, K, TG, num_slots=2, rtol=0.05,
                           overlap=overlap, tracer=tracer)
    for i in range(n_req):
        eng.submit(Request(rid=i, key=jax.random.PRNGKey(i)))
    return eng, dict(eng.run_until_drained())


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_records_every_phase_inside_its_step(overlap):
    eng, out = _serve(Tracer(), overlap=overlap)
    assert len(out) == 3
    host = [e for e in eng.tracer.events if e.ph == "X"
            and e.track == ("host", 0)]
    names = {e.name for e in host}
    assert PHASES <= names, PHASES - names
    rounds = {"dispatch/round", "dispatch/round_keep"} & names
    assert rounds, names
    steps = [(e.ts, e.ts + e.dur) for e in host if e.name == "serve/step"]
    for e in host:
        if e.name == "serve/step":
            continue
        assert any(a <= e.ts and e.ts + e.dur <= b for a, b in steps), e
    # the steps themselves never overlap
    steps.sort()
    assert all(b0 <= a1 for (_, b0), (a1, _) in zip(steps, steps[1:]))


def test_null_tracer_builds_no_annotation(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(*args, **kwargs):
        made.append(args[0])
        return real(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    for overlap in (False, True):
        _, out_off = _serve(None, overlap=overlap)
        assert made == []
        eng_on, out_on = _serve(Tracer(), overlap=overlap)
        spans = [e for e in eng_on.tracer.events
                 if e.ph == "X" and e.track == ("host", 0)]
        assert len(made) == len(spans) > 0
        made.clear()
        for rid in out_off:
            assert np.array_equal(np.asarray(out_off[rid].sample),
                                  np.asarray(out_on[rid].sample)), rid


def test_fitted_offset_maps_ring_onto_profiler(tmp_path):
    tracer = Tracer()
    eng = ContinuousEngine(_drift, (4,), N, K, TG, num_slots=2, rtol=0.05,
                           tracer=tracer)
    for i in range(2):  # compile outside the capture
        eng.submit(Request(rid=i, key=jax.random.PRNGKey(i)))
    eng.run_until_drained()
    tracer.events.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(2, 5):
            eng.submit(Request(rid=i, key=jax.random.PRNGKey(i)))
        eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    xspace = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    phases = profiler_phases(xspace[0])
    ring = [e for e in tracer.events if e.name == "dispatch/round"]
    prof = sorted(p for p in phases if p[0] == "dispatch/round")
    assert len(ring) == len(prof) > 0
    fit = fit_offset([(p[1], p[3]["t_ns"]) for p in phases])
    assert fit.n == len(phases)
    for ev, (_, start_ns, end_ns, _) in zip(ring, prof):
        assert abs(ev.ts * 1e9 + fit.offset_ns - start_ns) < 1e6
        assert abs((ev.ts + ev.dur) * 1e9 + fit.offset_ns - end_ns) < 1e6
    # retrospective spans land on the profiler clock too: each request's
    # queued span ends at an admission, inside a dispatch/admit annotation
    admits = [p for p in phases if p[0] == "dispatch/admit"]
    for ev in tracer.named("request/queued"):
        end = (ev.ts + ev.dur) * 1e9 + fit.offset_ns
        assert any(a - 1e6 <= end <= b + 1e6 for _, a, b, _ in admits)


def test_fit_offset_median_and_spread():
    fit = fit_offset([(110.0, 10.0), (125.0, 20.0), (130.0, 30.0)])
    assert fit.offset_ns == 100.0
    assert fit.spread_ns == 5.0 and fit.n == 3
    with pytest.raises(ValueError):
        fit_offset([])


def test_scope_of_takes_the_innermost_vocabulary_scope():
    assert scope_of("jit(round)/while/body/shared_block/attn/dot") == "attn"
    assert scope_of("jit(f)/vmap(mlp)/dot_general") == "mlp"
    assert scope_of("jit(round)/mamba2.scan/while/body/exp") == "mamba2.scan"
    assert scope_of("jit(f)/serve.grid_s2k4.round/add") is None
    assert scope_of("jit(round)/vmap(vmap(drift))/while/body/squeeze") \
        == "drift"


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _op_name_scopes(text):
    return {scope_of(m) for m in re.findall(r'op_name="([^"]*)"', text)}


@pytest.mark.parametrize("arch,want", [
    ("chords-dit-xl", {"norm", "attn", "mlp", "wrapper.in", "wrapper.out"}),
    ("zamba2-2.7b", {"norm", "attn", "mlp", "mamba2.in_proj", "mamba2.conv",
                     "mamba2.scan", "mamba2.out", "shared_block",
                     "wrapper.in", "wrapper.out"}),
])
def test_compiled_drift_carries_the_scopes(arch, want):
    from repro.configs import get_config
    from repro.diffusion import init_wrapper
    from repro.diffusion.wrapper import denoise

    cfg = get_config(arch, reduced=True)
    params = jax.eval_shape(lambda k: init_wrapper(cfg, 8, k),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 16, 8), jnp.float32)
    text = _compiled_text(lambda p, x: denoise(p, cfg, x, 0.35), params, x)
    assert want <= _op_name_scopes(text), want - _op_name_scopes(text)
    per_op = {o.scope for o in hlo_op_scopes(text).values()}
    assert want <= per_op
    assert per_op <= set(SCOPES) | {None}


def test_round_program_carries_the_step_scope():
    eng = ContinuousEngine(_drift, (4,), N, K, TG, num_slots=2)
    round_prog = eng.executor.grid(eng.spec).round
    text = round_prog.lower(eng.state).compile().as_text()
    want = {"chords.step", "drift"}
    assert want <= _op_name_scopes(text)
    assert want <= {o.scope for o in hlo_op_scopes(text).values()}
