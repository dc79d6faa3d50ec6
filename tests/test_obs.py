"""Observability substrate (``repro.obs``): the contracts the tooling keys on.

* **trace validity** — a real overlap/elastic engine run under burst
  pressure produces a structurally valid Chrome trace (required fields,
  spans nest-or-disjoint per track) that contains the full request
  lifecycle, at least one speculation rollback, and at least one resize —
  i.e. the exact artifact ``python -m repro.obs check`` verifies in CI;
* **disabled parity** — instrumented code paths are bitwise-neutral: the
  same workload served with and without a tracer yields identical samples,
  and the disabled tracer records nothing;
* **bounded buffers** — the event ring drops (and counts) overflow instead
  of growing, and histograms keep exact count/sum/min/max with reservoir
  percentiles once past capacity (the fix for the previously unbounded
  ``_latencies``/``_speedups`` lists);
* **anti-drift rendering** — every ``stats()`` key appears exactly once in
  ``format_stats`` output and belongs to a named group, so the launcher
  cannot silently drop or duplicate a metric;
* **CLI semantics** — ``check`` exit codes, ``diff`` regression thresholds
  (including the 0 -> N zero-baseline case), and the jaxpr lint's
  ``host-sync-obs`` downgrade for tracer-planted callbacks.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import uniform_tgrid
from repro.obs import (METRICS_SCHEMA, MetricsRegistry, NULL_TRACER, Tracer,
                       chrome_trace, load_snapshot, mark_instrumentation,
                       metric_scalar, write_chrome_trace)
from repro.obs.check import check, diff, summarize, validate_structure
from repro.obs.render import GROUPS, format_stats
from repro.serve import ContinuousEngine, Request
from repro.serve.sched.workload import bursty_trace, drive

N, K = 16, 4
TG = uniform_tgrid(N, 0.98)
LAM = jnp.linspace(0.1, 1.5, 4)


def _drift(x, t):
    return -x * LAM


def _serve(tracer=None, n_req=3, rtol=0.0, **kw):
    eng = ContinuousEngine(_drift, (4,), N, K, TG, rtol=rtol,
                           tracer=tracer, **kw)
    for i in range(n_req):
        eng.submit(Request(rid=i, key=jax.random.PRNGKey(i)))
    return eng, dict(eng.run_until_drained())


@pytest.fixture(scope="module")
def rollback_run(tmp_path_factory):
    """The CI trace artifact's configuration at test scale: overlap engine,
    elastic 2..4 slots, burst pressure, rtol small enough that the cost
    model's cold-start prediction is wrong — forcing real speculation
    rollbacks — but accepts still land on the deterministic final round."""
    tracer = Tracer()
    eng = ContinuousEngine(_drift, (4,), N, K, TG, rtol=1e-5, min_slots=2,
                           max_slots=4, resize_hysteresis=8, overlap=True,
                           tracer=tracer)
    reqs, arrivals = bursty_trace(N, rtol=1e-5)
    out = drive(eng, reqs, arrivals)
    path = tmp_path_factory.mktemp("obs") / "trace.json"
    doc = eng.write_trace(str(path), meta={"run": "test"})
    return eng, out, doc, str(path)


# -- tentpole: the trace artifact ---------------------------------------------

def test_trace_is_structurally_valid(rollback_run):
    _, _, doc, _ = rollback_run
    assert validate_structure(doc) == []
    assert doc["otherData"]["schema"] == "repro.obs.trace"
    assert doc["otherData"]["dropped"] == 0
    # round-trips through JSON (no numpy scalars etc. leaked into args)
    json.loads(json.dumps(doc))


def test_trace_contains_request_lifecycle(rollback_run):
    eng, out, doc, _ = rollback_run
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request/submit", "request/queued", "request/compute",
            "verify/readback"} <= names
    assert any(n.startswith("dispatch/") for n in names)
    # every served request's compute span(s) carry its rid
    rids = {e["args"].get("rid") for e in doc["traceEvents"]
            if e["name"] == "request/compute"}
    assert set(out) <= rids


def test_trace_has_rollback_and_resize(rollback_run):
    eng, _, doc, _ = rollback_run
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("spec/rollback") >= 1
    assert names.count("resize/grow") >= 1
    st = eng.stats()
    assert st["speculation_rollbacks"] >= 1
    assert st["grows"] >= 1
    # rollbacks emitted exactly once per counted rollback (no phantom
    # events from speculative decisions that were undone)
    assert names.count("spec/rollback") == st["speculation_rollbacks"]
    assert names.count("spec/confirm") == st["speculation_confirms"]


def test_spans_nest_despite_rollbacks(rollback_run):
    """Commit-point emission: even with speculative admissions rolled back
    mid-flight and lanes migrated across a grow, every per-slot track's
    spans are well-nested (Perfetto renders them correctly)."""
    _, _, doc, _ = rollback_run
    slot_spans = [e for e in doc["traceEvents"]
                  if e.get("ph") == "X" and e["pid"] == 2]
    assert slot_spans, "no per-slot compute spans in trace"
    assert validate_structure({"traceEvents": slot_spans}) == []


def test_check_passes_on_real_trace(rollback_run):
    _, _, doc, _ = rollback_run
    ok, lines = check(doc)
    assert ok, lines
    # all four contracts actually ran (none skipped for missing data)
    assert sum(1 for l in lines if l.lstrip().startswith("PASS")) >= 4


def test_check_rollback_cap_fails(rollback_run):
    _, _, doc, _ = rollback_run
    ok, lines = check(doc, max_rollbacks=0)
    assert not ok
    assert any("rollback-cap" in l and "FAIL" in l for l in lines)


def test_summarize_reports_phases(rollback_run):
    _, _, doc, _ = rollback_run
    text = "\n".join(summarize(doc))
    assert "request/compute" in text
    assert "spec/rollback=1" in text or "rollback offenders" in text


def test_cli_on_artifact(rollback_run, tmp_path, capsys):
    from repro.obs.__main__ import main
    _, _, _, path = rollback_run
    assert main(["check", path]) == 0
    assert main(["summarize", path]) == 0
    assert main(["diff", path, path]) == 0
    assert main(["check", path, "--max-rollbacks", "0"]) == 1
    capsys.readouterr()


# -- disabled parity ----------------------------------------------------------

def test_disabled_tracer_is_bitwise_neutral():
    eng_off, out_off = _serve(tracer=None)
    eng_on, out_on = _serve(tracer=Tracer())
    assert sorted(out_off) == sorted(out_on)
    for rid in out_off:
        assert np.array_equal(np.asarray(out_off[rid].sample),
                              np.asarray(out_on[rid].sample)), rid
        assert out_off[rid].rounds_used == out_on[rid].rounds_used
    assert eng_off.tracer is NULL_TRACER
    assert len(eng_off.tracer.events) == 0
    assert len(eng_on.tracer.events) > 0


def test_null_tracer_records_nothing():
    t = Tracer(enabled=False)
    assert t.now() == 0.0
    t.instant("spec/rollback", round_idx=3)
    t.span("request/compute", 0.0, round_idx=1)
    t.counter("occupancy", 1.0)
    with t.phase("dispatch/round", round_idx=0):
        pass
    t.label_track(("slots", 0), "slot 0")
    assert len(t) == 0 and t.dropped == 0 and t.track_labels == {}
    # and the same context-manager singleton is reused (zero allocation)
    assert t.phase("dispatch/round") is t.phase("dispatch/admit")


# -- bounded buffers ----------------------------------------------------------

def test_ring_buffer_counts_drops():
    t = Tracer(capacity=4)
    for i in range(10):
        t.instant("retrace", round_idx=i)
    assert len(t) == 4 and t.dropped == 6
    doc = chrome_trace(t)
    assert doc["otherData"]["dropped"] == 6
    assert doc["otherData"]["events"] == 4
    # the buffered prefix is the OLDEST events (span integrity preserved)
    rounds = [e["args"]["round"] for e in doc["traceEvents"]
              if e["name"] == "retrace"]
    assert rounds == [0, 1, 2, 3]


def test_histogram_reservoir_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("serve.latency_rounds", capacity=8)
    for v in range(8):
        h.observe(v)
    # exact while count <= capacity
    assert h.percentile(50) == pytest.approx(np.percentile(range(8), 50))
    assert h.percentile(95) == pytest.approx(np.percentile(range(8), 95))
    assert h.snapshot()["exact"] is True
    for v in range(8, 108):
        h.observe(v)
    s = h.snapshot()
    # count/sum/min/max stay exact forever; reservoir stays bounded
    assert s["count"] == 108 and s["sum"] == sum(range(108))
    assert s["min"] == 0 and s["max"] == 107
    assert s["reservoir_size"] == 8 and s["exact"] is False
    assert 0 <= s["p50"] <= 107
    # per-name seeded RNG: identical streams -> identical reservoirs
    h2 = MetricsRegistry().histogram("serve.latency_rounds", capacity=8)
    for v in range(108):
        h2.observe(v)
    assert h2.snapshot() == s


def test_engine_latency_state_is_bounded(rollback_run):
    eng, _, _, _ = rollback_run
    h = eng.metrics["serve.latency_rounds"]
    assert len(h._values) <= h.capacity
    assert h.count == eng.stats()["served"]


def test_counter_negative_inc_and_kind_collision():
    reg = MetricsRegistry()
    c = reg.counter("serve.preempt.count")
    c.inc()
    c.inc(-1)  # speculative-undo bookkeeping
    assert c.value == 0
    assert reg.counter("serve.preempt.count") is c
    with pytest.raises(TypeError):
        reg.gauge("serve.preempt.count")


# -- snapshots + diff ---------------------------------------------------------

def test_snapshot_roundtrip_bare_and_embedded(tmp_path):
    reg = MetricsRegistry()
    reg.counter("serve.host_syncs").inc(5)
    reg.gauge("serve.overlap").set(1.0)
    bare = tmp_path / "metrics.json"
    reg.write_snapshot(str(bare))
    snap = load_snapshot(str(bare))
    assert snap["schema"] == METRICS_SCHEMA
    assert metric_scalar(snap, "serve.host_syncs") == 5
    assert metric_scalar(snap, "serve.missing") is None
    trace = tmp_path / "trace.json"
    write_chrome_trace(str(trace), Tracer(), metrics=reg)
    assert load_snapshot(str(trace)) == snap
    with pytest.raises(ValueError):
        other = tmp_path / "other.json"
        other.write_text("{}")
        load_snapshot(str(other))


def _snap(**scalars):
    return {"schema": METRICS_SCHEMA, "version": 1,
            "metrics": {k: {"type": "counter", "value": v}
                        for k, v in scalars.items()}}


def test_diff_threshold_semantics():
    a = _snap(**{"serve.spec.rollbacks": 0, "serve.host_syncs": 100,
                 "serve.served": 10})
    b = _snap(**{"serve.spec.rollbacks": 3, "serve.host_syncs": 110,
                 "serve.served": 20})
    _, regressions = diff(a, b, threshold=0.25)
    # 0 -> 3 rollbacks IS a regression (relative to max(|A|, 1))
    assert "serve.spec.rollbacks" in regressions
    # +10% host_syncs is under the 25% threshold
    assert "serve.host_syncs" not in regressions
    # served doubling is higher-is-better: never a regression
    assert "serve.served" not in regressions
    _, tight = diff(a, b, threshold=0.05)
    assert "serve.host_syncs" in tight
    # improvements never regress regardless of threshold
    _, back = diff(b, a, threshold=0.0)
    assert back == []


# -- structural validator -----------------------------------------------------

def test_validate_structure_catches_malformed():
    good = {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0,
            "dur": 10.0}
    overlap = dict(good, name="b", ts=5.0, dur=10.0)  # partial overlap
    nested = dict(good, name="c", ts=2.0, dur=3.0)    # fully contained: ok
    missing = {"name": "d", "ph": "i", "pid": 1, "tid": 0}
    meta = {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": "host"}}  # metadata needs no ts
    assert validate_structure({"traceEvents": [good, nested, meta]}) == []
    probs = validate_structure({"traceEvents": [good, overlap, missing]})
    assert any("partially overlaps" in p for p in probs)
    assert any("missing" in p and "'d'" in p for p in probs)
    assert validate_structure(
        {"traceEvents": [dict(good, dur=-1.0)]}) != []


# -- anti-drift rendering -----------------------------------------------------

def test_render_covers_every_stat_key(rollback_run):
    eng, _, _, _ = rollback_run
    st = eng.stats()
    lines = format_stats(st)
    text = " ".join(lines)
    for key in st:
        assert text.count(f" {key}=") == 1, key
    # every key belongs to a NAMED group (the elided accept table is the
    # one deliberate exception): a new stats() key must be added to
    # repro.obs.render.GROUPS or it fails here instead of silently
    # landing in "other"
    grouped = {k for _, keys in GROUPS for k in keys}
    assert set(st) - grouped <= {"accept_rounds_observed"}, \
        sorted(set(st) - grouped)


# -- static-analysis exemption ------------------------------------------------

def test_lint_downgrades_obs_callbacks():
    from repro.analysis.jaxpr_lint import lint_jaxpr

    @mark_instrumentation
    def obs_hook(x):
        return np.asarray(x)

    def plain_hook(x):
        return np.asarray(x)

    def build(hook):
        def fn(x):
            sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
            return jax.pure_callback(hook, sds, x) + 1
        return jax.make_jaxpr(fn)(jnp.ones(4))

    marked = lint_jaxpr("p", build(obs_hook))
    assert [(f.code, f.severity) for f in marked
            if "host-sync" in f.code] == [("host-sync-obs", "info")]
    plain = lint_jaxpr("p", build(plain_hook))
    assert [(f.code, f.severity) for f in plain
            if "host-sync" in f.code] == [("host-sync", "error")]
