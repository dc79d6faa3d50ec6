"""Backbone families as files of their own (``bench/backbones/``).

* the dense and hybrid families' reference outputs, FLOP counts and drawn
  weights are the numbers recorded before the families moved into their
  files (float32 weights at the rehearsal size, and the same weights
  stored in bfloat16);
* a family file that no existing file names, put on the loader's path, is
  what ``reference.drift``, ``flops.drift_forward``, ``weights.draw`` and
  ``readers.attention_roofline`` use, with fan-in taken after its
  ``STACKED`` axes;
* an unknown family stops ``run.program_config`` and names the missing file;
* ``reference.make_drift`` hands the weights to its jitted function as
  stored and casts no whole stacked leaf to float32.
"""
from __future__ import annotations

import math
import os
import sys
import textwrap

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import backbones  # noqa: E402
import run as bench_run  # noqa: E402

# recorded from the tree before this layout: reference.make_drift on
# weights.draw(structure, 3) at the rehearsal size, x from PRNGKey(1),
# t = [0, 0.25, 0.5, 0.95]; "bf16" stores the same weights in bfloat16
RECORDED = {
    "dit-xl.img-steady": {
        "weights_sumsq": 8374.61913403387,
        "ref_norm": 93.82383948952771,
        "ref_first": [0.0469355583190918, 0.06243446469306946,
                      -1.6077666282653809, 0.6955859661102295],
        "ctrl_norm": 93.83124881207817,
        "bf16_ref_norm": 93.85842178365759,
        "bf16_ref_first": [0.04997241497039795, 0.054613858461380005,
                           -1.6097042560577393, 0.6964181661605835],
        "bf16_ctrl_norm": 93.59627130410921,
        "flops_rehearse": 303136768,
        "flops_full": 2889459695616,
    },
    "zamba2.img-backlog": {
        "weights_sumsq": 5187.164315087337,
        "ref_norm": 90.22127512761433,
        "ref_first": [0.03347392752766609, -1.2503430843353271,
                      0.0048640817403793335, 1.0490050315856934],
        "ctrl_norm": 90.21771022934905,
        "bf16_ref_norm": 90.19808834693535,
        "bf16_ref_first": [0.033147916197776794, -1.2521257400512695,
                           0.01809680461883545, 1.0438352823257446],
        "bf16_ctrl_norm": 90.3688911183367,
        "flops_rehearse": 64692224.0,
        "flops_full": 3100825616384.0,
    },
}
# relative, for another CPU's vector width; on one machine they are equal
RTOL = 1e-6


def _rehearsal(name):
    import jax

    import weights
    from repro.diffusion import init_wrapper

    cell = bench_run.load_cell(name)
    cfg, model = bench_run.program_config(cell, rehearse=True)
    latent = bench_run.REHEARSAL_LATENT
    structure = jax.eval_shape(
        lambda k: init_wrapper(cfg, latent[-1], k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    return cell, model, latent, weights.draw(structure, 3, model["family"])


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_families_give_the_recorded_numbers(name):
    import jax
    import jax.numpy as jnp

    import flops
    import reference

    want = RECORDED[name]
    cell, model, latent, params = _rehearsal(name)
    sumsq = sum(float(np.sum(np.asarray(a, np.float64) ** 2))
                for a in jax.tree_util.tree_leaves(params))
    assert sumsq == pytest.approx(want["weights_sumsq"], rel=RTOL)
    assert flops.drift_forward(model, latent[-2], latent[-1]) \
        == want["flops_rehearse"]
    assert flops.drift_forward(cell["config"]["model"], 4096, 64) \
        == want["flops_full"]

    x = jax.random.normal(jax.random.PRNGKey(1), (4,) + latent[1:])
    t = jnp.asarray([0.0, 0.25, 0.5, 0.95], jnp.float32)
    bf16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    for tag, p in (("", params), ("bf16_", bf16)):
        ref = np.asarray(reference.make_drift(p, model)(x[:, None], t),
                         np.float64)
        ctrl = np.asarray(reference.make_drift(p, model, quant="fp8")(
            x[:, None], t), np.float64)
        assert np.linalg.norm(ref) == pytest.approx(want[tag + "ref_norm"],
                                                    rel=RTOL)
        np.testing.assert_allclose(ref.ravel()[:4], want[tag + "ref_first"],
                                   rtol=RTOL, atol=1e-7)
        assert np.linalg.norm(ctrl) == pytest.approx(
            want[tag + "ctrl_norm"], rel=RTOL)


TOY = '''
"""A family no existing file names: one stacked expert layer."""
import jax.numpy as jnp

ATTENTION_CAUSAL = True
STACKED = {"layers": 1, "experts": 1}


def reference(p, m, h, mm):
    return jnp.zeros_like(h)


def flops(s, m):
    return 1e30
'''


@pytest.fixture
def toy_family(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(textwrap.dedent(TOY))
    monkeypatch.setattr(backbones, "__path__",
                        [str(tmp_path)] + list(backbones.__path__))
    yield "toy"
    sys.modules.pop("backbones.toy", None)


def test_a_new_family_is_a_new_file(toy_family):
    import jax
    import jax.numpy as jnp

    import flops
    import readers
    import reference
    import weights

    n_layers, n_experts, d, f, lat, seq = 2, 8, 64, 32, 16, 128
    sds = jax.ShapeDtypeStruct
    structure = {
        "backbone": {
            "layers": {"experts": {"w_up": sds((n_layers, n_experts, d, f),
                                               jnp.bfloat16)},
                       "router": sds((n_layers, d, n_experts),
                                     jnp.bfloat16)},
            "final_norm": sds((d,), jnp.bfloat16)},
        "in_proj": sds((lat, d), jnp.bfloat16),
        "t_mlp1": sds((256, d), jnp.bfloat16),
        "t_mlp2": sds((d, d), jnp.bfloat16),
        "out_norm": sds((d,), jnp.bfloat16),
        "out_proj": sds((d, lat), jnp.bfloat16),
    }
    p = weights.draw(structure, 5, toy_family)
    # [L, E, d, f] has two stack axes: fan-in d, not E * d
    up = np.asarray(p["backbone"]["layers"]["experts"]["w_up"], np.float64)
    assert abs(up.std() * math.sqrt(d) - 1.0) < 0.05, up.std()
    router = np.asarray(p["backbone"]["layers"]["router"], np.float64)
    assert abs(router.std() * math.sqrt(d) - 1.0) < 0.1, router.std()

    m = {"family": toy_family, "d_model": d, "norm_eps": 1e-6,
         "num_heads": 4, "head_dim": 16}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, seq, lat))
    out = reference.make_drift(p, m)(x, jnp.asarray([0.1, 0.7]))
    # the toy backbone returns zeros, which the wrapper's norm keeps at 0
    assert out.shape == x.shape and not np.any(np.asarray(out))
    assert flops.drift_forward(m, seq, lat) == pytest.approx(1e30)

    tr = {"num_slots": 2, "num_cores": 4, "latent_shape": [1, seq, lat]}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e15}
    run = {"model": m, "traffic": tr, "peaks": peaks,
           "reduced_trace": {"kernel_calls": {"fa": 1},
                             "kernel_s": {"fa": 1.0}}}
    causal, _ = flops.flash_attention_call(8, 4, seq, seq, 16, 2, True)
    assert readers.attention_roofline(run, "fa") == pytest.approx(
        100.0 * causal / 1e12)


def test_unknown_family_stops_program_config():
    cell = bench_run.load_cell("dit-xl.img-steady")
    conf = dict(cell["config"], model=dict(cell["config"]["model"],
                                           family="nosuch"))
    with pytest.raises(SystemExit, match="bench/backbones/nosuch.py"):
        bench_run.program_config(dict(cell, config=conf), rehearse=True)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reference_takes_the_weights_as_stored(name):
    import jax
    import jax.numpy as jnp

    import reference

    _, model, latent, params = _rehearsal(name)
    bf16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    drift = reference.make_drift(bf16, model)
    assert drift.args[0] is bf16
    x = jnp.zeros((4, 1) + latent[1:], jnp.float32)
    t = jnp.zeros((4,), jnp.float32)
    closed = jax.make_jaxpr(drift.func)(*drift.args, x, t)
    n = len(jax.tree_util.tree_leaves(bf16))
    assert [v.aval.dtype for v in closed.jaxpr.invars[:n]] \
        == [jnp.dtype(jnp.bfloat16)] * n
    # inside, a stacked leaf is cast one layer at a time, never whole
    inner = closed.jaxpr.eqns[0].params["jaxpr"].jaxpr
    keys = set(backbones.load(model["family"]).STACKED)
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(bf16)[0]]
    stacked = {v for v, path in zip(inner.invars, paths)
               if keys & {getattr(k, "key", None) for k in path}}
    assert stacked
    for eqn in inner.eqns:
        if eqn.primitive.name == "convert_element_type":
            assert not stacked & set(eqn.invars), eqn
