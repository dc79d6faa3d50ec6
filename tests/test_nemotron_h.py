"""The Nemotron-H family (``models/nemotron_h.py``) and the pieces it added:
the dropless MoE layer, grouped Mamba2 B/C, the family as a denoiser.

* ``dropless_moe`` against a per-token NumPy loop: every token routed to
  every expert once, an expert no token picks, one expert taking every
  token (no token is ever dropped), and under the round's nested ``vmap``;
* top-k ties go to the lower expert index;
* grouped ``ssd_forward`` (8 heads, 2 groups, conv bias) against a
  token-by-token recurrence;
* with one group, ``ssd_forward`` for ``zamba2-2.7b`` reduced gives the
  output recorded from the tree before grouped B/C existed;
* the family through ``forward_hidden``: shape, finite, causal;
* XLA's TPU ops for the grouped matmuls are charged to ``moe.experts``;
* the ragged-dot tiles: aligned, inside the scoped VMEM, clamped at tiny
  shapes, the same on every call, and carried by both grouped matmuls of
  the round's lowering for a TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api, moe
from repro.models import mamba2 as M
from repro.utils.pspec import init_params

D, E, F, FS = 16, 8, 8, 24


def _moe_cfg(k):
    return get_config("nemotron-3-nano-30b-a3b", reduced=True).replace(
        d_model=D, num_experts=E, experts_per_tok=k, d_ff=F, shared_d_ff=FS)


def _moe_params(seed, router_shift=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda k, s: jax.random.normal(k, s, jnp.float32) / np.sqrt(s[-2])  # noqa: E731
    router = n(ks[0], (D, E))
    if router_shift is not None:
        # one expert's router column reads only feature 0, which the test
        # holds at 3: its score is then the lowest (negative shift) or the
        # highest (positive) for every token
        e, shift = router_shift
        router = router.at[:, e].set(0.0).at[0, e].set(shift)
    return {"router": router,
            "experts": {"w_up": n(ks[1], (E, D, F)), "w_down": n(ks[2], (E, F, D))},
            "shared": {"w_up": n(ks[3], (D, FS)), "w_down": n(ks[4], (FS, D))}}


def _moe_loop(p, cfg, x):
    """Per-token float64 loop: sigmoid scores, the k best (lower index on a
    tie), renormalised and scaled weights, relu^2 experts, shared expert."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    xs = np.asarray(x, np.float64).reshape(-1, D)
    relu2 = lambda v: np.maximum(v, 0.0) ** 2  # noqa: E731
    out = np.zeros_like(xs)
    picked = np.zeros(E, int)
    for i, tok in enumerate(xs):
        s = 1.0 / (1.0 + np.exp(-(tok @ p["router"])))
        top = np.argsort(-s, kind="stable")[: cfg.experts_per_tok]
        picked[top] += 1
        w = s[top] / s[top].sum() * cfg.routed_scale
        for e, we in zip(top, w):
            out[i] += we * relu2(tok @ p["experts"]["w_up"][e]) @ p["experts"]["w_down"][e]
        out[i] += relu2(tok @ p["shared"]["w_up"]) @ p["shared"]["w_down"]
    return out.reshape(np.shape(x)), picked


@pytest.mark.parametrize("k,shift,expect", [
    (E, None, "every expert once"),
    (2, (5, -10.0), "expert 5 idle"),
    (1, (3, 10.0), "expert 3 takes all"),
], ids=["every-expert-once", "idle-expert", "one-expert-takes-all"])
def test_dropless_moe_matches_per_token_loop(k, shift, expect):
    cfg = _moe_cfg(k)
    p = _moe_params(0, shift)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D), jnp.float32)
    x = x.at[..., 0].set(3.0)
    want, picked = _moe_loop(p, cfg, x)
    tokens = 2 * 24
    if expect == "every expert once":
        assert (picked == tokens).all(), picked
    elif expect == "expert 5 idle":
        assert picked[5] == 0 and picked.sum() == 2 * tokens, picked
    else:
        assert picked[3] == tokens and picked.sum() == tokens, picked
    got = np.asarray(moe.dropless_moe(p, cfg, x), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_dropless_moe_folds_mapped_lanes_into_tokens():
    # the round maps the drift over slots and cores: one sort and one pair of
    # grouped matmuls over every lane's rows, the same answer as lane by lane
    cfg = _moe_cfg(2)
    p = _moe_params(2)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 1, 16, D), jnp.float32)
    f = lambda xx: moe.dropless_moe(p, cfg, xx)  # noqa: E731
    got = np.asarray(jax.jit(jax.vmap(jax.vmap(f)))(x))
    want = np.stack([np.stack([np.asarray(f(x[i, j])) for j in range(3)])
                     for i in range(2)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    closed = jax.make_jaxpr(jax.vmap(jax.vmap(f)))(x)
    dots = [e for e in closed.jaxpr.eqns if e.primitive.name == "custom_vmap_call"]
    assert len(dots) == 1, closed


def test_topk_ties_go_to_the_lower_expert_index():
    cfg = _moe_cfg(3)
    scores = jnp.asarray([[0.5, 0.7, 0.5, 0.7, 0.5, 0.1, 0.7, 0.5],
                          [0.2] * E], jnp.float32)
    w, idx = moe.route(cfg, scores)
    assert np.asarray(idx).tolist() == [[1, 3, 6], [0, 1, 2]]
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scale, rtol=1e-6)
    # a zero router ties every expert for every token: experts 0..k-1 serve
    p = _moe_params(4)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, D), jnp.float32)
    want, picked = _moe_loop(p, cfg, x)
    assert picked.tolist() == [8, 8, 8, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(moe.dropless_moe(p, cfg, x)), want,
                               rtol=2e-5, atol=2e-5)


def _grouped_cfg():
    return get_config("nemotron-3-nano-30b-a3b", reduced=True).replace(
        d_model=32, ssm_heads=8, ssm_head_dim=4, ssm_groups=2, ssm_state=6,
        ssm_conv=4, ssm_chunk=8, ssm_conv_bias=True)


def _ssd_loop(p, cfg, x):
    """Token-by-token float64 recurrence of the grouped Mamba2 layer."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    b, s, _ = x.shape
    h, hd, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    din, gn, w = h * hd, g * n, cfg.ssm_conv
    silu = lambda v: v / (1.0 + np.exp(-v))  # noqa: E731
    proj = x @ p["in_proj"]
    z, xbc, dt = proj[..., :din], proj[..., din: 2 * din + 2 * gn], proj[..., 2 * din + 2 * gn:]
    pad = np.concatenate([np.zeros((b, w - 1, xbc.shape[-1])), xbc], 1)
    conv = silu(sum(pad[:, i: i + s] * p["conv_w"][i] for i in range(w)) + p["conv_b"])
    xc = conv[..., :din].reshape(b, s, h, hd)
    bm = conv[..., din: din + gn].reshape(b, s, g, n)
    cm = conv[..., din + gn:].reshape(b, s, g, n)
    dt = np.log1p(np.exp(dt + p["dt_bias"]))
    a = -np.exp(p["a_log"])
    y = np.zeros((b, s, h, hd))
    for bi in range(b):
        state = np.zeros((h, hd, n))
        for t in range(s):
            for hh in range(h):
                gi = hh // (h // g)
                state[hh] = (np.exp(dt[bi, t, hh] * a[hh]) * state[hh]
                             + dt[bi, t, hh] * np.outer(xc[bi, t, hh], bm[bi, t, gi]))
                y[bi, t, hh] = state[hh] @ cm[bi, t, gi] + p["d_skip"][hh] * xc[bi, t, hh]
    yz = (y.reshape(b, s, din) * silu(z)).reshape(b, s, g, din // g)
    yz = yz / np.sqrt(np.mean(yz * yz, -1, keepdims=True) + cfg.norm_eps)
    return (yz.reshape(b, s, din) * p["gate_norm"]) @ p["out_proj"]


def test_grouped_ssd_matches_token_recurrence():
    cfg = _grouped_cfg()
    p = init_params(M.ssd_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    # leaves the initializer sets to constants, drawn so each is exercised
    p["a_log"] = jnp.log(jax.random.uniform(ks[0], p["a_log"].shape, minval=1.0, maxval=4.0))
    p["dt_bias"] = jax.random.uniform(ks[1], p["dt_bias"].shape, minval=-3.0, maxval=-1.0)
    p["conv_b"] = 0.3 * jax.random.normal(ks[2], p["conv_b"].shape)
    p["gate_norm"] = 1.0 + 0.1 * jax.random.normal(ks[3], p["gate_norm"].shape)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg.d_model), jnp.float32)
    y, (_, state) = M.ssd_forward(p, cfg, x)
    assert state.shape == (2, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    np.testing.assert_allclose(np.asarray(y, np.float64), _ssd_loop(p, cfg, x),
                               rtol=1e-4, atol=1e-5)


# recorded from the tree before grouped B/C: ssd_forward of zamba2-2.7b
# reduced, init_params(PRNGKey(0)), x = normal(PRNGKey(1), (2, 32, 64))
ZAMBA2_ONE_GROUP = {"sum": 31.71261998117552, "sumsq": 3232.7101752368253,
                    "first": [0.3616613447666168, -0.12144379317760468,
                              0.653925359249115]}


def test_one_group_ssd_is_the_recorded_output():
    cfg = get_config("zamba2-2.7b", reduced=True)
    p = init_params(M.ssd_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    y = np.asarray(M.ssd_forward(p, cfg, x)[0], np.float64)
    # bitwise: the one-group path traces to the jaxpr it had
    assert float(y.sum()) == ZAMBA2_ONE_GROUP["sum"]
    assert float((y ** 2).sum()) == ZAMBA2_ONE_GROUP["sumsq"]
    assert y.ravel()[:3].tolist() == ZAMBA2_ONE_GROUP["first"]


def test_family_as_denoiser_trunk():
    cfg = get_config("nemotron-3-nano-30b-a3b", reduced=True)
    assert cfg.layer_pattern == "MEMEM*E" and cfg.num_experts >= 8
    p = api.init_model(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    h = np.asarray(api.forward_hidden(p, cfg, x, causal=False))
    assert h.shape == x.shape and np.isfinite(h).all()
    # recurrent trunk: causal whatever the caller asks; the last token's
    # change leaves every earlier position as it was
    x2 = x.at[:, -1].add(1.0)
    h2 = np.asarray(api.forward_hidden(p, cfg, x2, causal=False))
    np.testing.assert_array_equal(h2[:, :-1], h[:, :-1])
    assert not np.allclose(h2[:, -1], h[:, -1])


def test_layer_pattern_must_cover_every_layer():
    cfg = get_config("nemotron-3-nano-30b-a3b", reduced=True)
    with pytest.raises(ValueError, match="layer_pattern"):
        api.model_specs(cfg.replace(layer_pattern="MEMEM*"))
    with pytest.raises(ValueError, match="layer_pattern"):
        api.model_specs(cfg.replace(layer_pattern="MEMEMXE"))


def test_lowered_ragged_dot_ops_take_the_experts_scope():
    from repro.obs.scopes import hlo_op_scopes

    text = "\n".join([
        "ENTRY %main.1 (x: bf16[8,4]) -> bf16[8,4] {",
        '  %ragged-dot-metadata = (s32[9]{0}) custom-call(%g), metadata={op_name="ragged-dot-metadata"}',
        '  %ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%x), metadata={op_name="ragged-dot-none"}',
        '  ROOT %gather.2 = bf16[8,4]{1,0} gather(%ragged-dot-none.1), metadata={op_name="jit(round)/drift/moe.dispatch/gather"}',
        "}"])
    scopes = hlo_op_scopes(text)
    assert scopes["ragged-dot-none.1"].scope == "moe.experts"
    assert scopes["ragged-dot-metadata"].scope == "moe.experts"
    assert scopes["gather.2"].scope == "moe.dispatch"


CELL_ROWS = 196608  # nemotron3-nano.img-backlog: 8 latents x 4096 tokens x 6 experts


@pytest.mark.parametrize("rows,k,n,groups,itemsize", [
    (CELL_ROWS, 2688, 1856, 128, 2),
    (CELL_ROWS, 1856, 2688, 128, 2),
    (CELL_ROWS, 2688, 1856, 128, 4),
    (96, D, F, E, 4),
    (5, 3, 7, 1, 2),
], ids=["cell-up", "cell-down", "cell-up-f32", "tiny", "under-one-tile"])
def test_ragged_tiles_are_aligned_and_fit_vmem(rows, k, n, groups, itemsize):
    up = lambda v, a: -(-v // a) * a  # noqa: E731
    tm, tk, tn = moe._ragged_tiles(rows, k, n, groups, itemsize)
    assert tm % 8 == 0 and tk % 128 == 0 and tn % 128 == 0
    assert 8 <= tm <= min(2048, up(rows, 8))
    assert 128 <= tk <= up(k, 128) and 128 <= tn <= up(n, 128)
    vmem = 2 * (tm * tk + tk * tn) * itemsize + tm * tn * (2 * itemsize + 4)
    assert vmem < moe._VMEM_BYTES
    assert moe._ragged_tiles(rows, k, n, groups, itemsize) == (tm, tk, tn)
    if rows < 128:  # one row tile, the smallest the kernel may take
        assert (tm, tk, tn) == (up(rows, 8), 128, 128)


def test_ragged_tiles_beat_xla_default_at_the_cell():
    # at the cell's shapes the pick models under half the time of XLA's
    # default 512x128x128, and the up and down calls (k and n swapped) get
    # tiles of their own
    picks = {}
    for name, (k, n) in {"up": (2688, 1856), "down": (1856, 2688)}.items():
        picks[name] = moe._ragged_tiles(CELL_ROWS, k, n, 128, 2)
        t = lambda tiles: moe._ragged_time(CELL_ROWS, k, n, 128, 2, tiles)  # noqa: E731
        assert t(picks[name]) < t((512, 128, 128)) / 2, picks[name]
    assert picks["up"] != picks["down"]


def test_round_lowering_carries_tiles_on_both_grouped_matmuls():
    # the round maps the drift over slots and cores; the folded layer's two
    # ragged dots, lowered for a TPU at the cell's shapes, carry the tiles
    # picked for their own shapes
    cfg = get_config("nemotron-3-nano-30b-a3b")
    p = jax.eval_shape(lambda key: init_params(moe.dropless_specs(cfg), key, jnp.bfloat16),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 4, 1, 4096, cfg.d_model), jnp.bfloat16)

    def lanes(p, x):
        return jax.vmap(jax.vmap(lambda xx: moe.dropless_moe(p, cfg, xx)))(x)

    text = jax.jit(lanes).trace(p, x).lower(lowering_platforms=("tpu",)).as_text()
    got = [line.split('ragged_dot_tiling = "')[1].split('"')[0]
           for line in text.splitlines() if "chlo.ragged_dot" in line]
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    want = [moe._ragged_tiles(CELL_ROWS, d, ff, e, 2), moe._ragged_tiles(CELL_ROWS, ff, d, e, 2)]
    assert got == [",".join(map(str, t)) for t in want]
