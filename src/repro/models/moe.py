"""Mixture-of-Experts transformer (qwen2-moe-a2.7b, olmoe-1b-7b), and the
dropless routed-expert layer of the ``nemotron_h`` family.

The ``moe`` family's layer (:func:`moe_ffn`) uses a sort-based dropping
dispatch (MaxText-style "permute"): tokens are routed top-k, sorted by
expert id *within expander groups* (one group per data shard so routing
never crosses the DP axis), packed into [groups, experts, capacity, d]
buffers and processed with batched expert einsums sharded
experts->"model". Overflowing tokens are dropped (capacity factor config).
This keeps compiled FLOPs ~ active-expert FLOPs instead of the dense
E/k-times overcompute.

:func:`dropless_moe` drops nothing, whatever the routing: in a denoiser a
dropped token changes the latent. Its (token, expert) rows are sorted by
expert and the expert products run as grouped matmuls over the real group
sizes (``jax.lax.ragged_dot``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.models import layers as L
from repro.utils.pspec import spec


def moe_specs(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    Ld = () if layers is None else (layers,)
    La = () if layers is None else ("layers",)

    def s(shape, axes, **kw):
        return spec(Ld + tuple(shape), La + tuple(axes), **kw)

    specs = {
        "router": s((d, e), ("embed", "experts")),
        "w_gate": s((e, d, f), ("experts", "embed", "ffn")),
        "w_up": s((e, d, f), ("experts", "embed", "ffn")),
        "w_down": s((e, f, d), ("experts", "ffn", "embed")),
    }
    if cfg.num_shared_experts:
        fs = cfg.d_ff * cfg.num_shared_experts
        specs["shared"] = {
            "w_gate": s((d, fs), ("embed", "ffn")),
            "w_up": s((d, fs), ("embed", "ffn")),
            "w_down": s((fs, d), ("ffn", "embed")),
            "gate": s((d, 1), ("embed", None)),
        }
    return specs


def _capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    c = int(tokens_per_group * cfg.experts_per_tok * cfg.moe_capacity_factor
            / cfg.num_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to multiple of 8


def moe_ffn(p, cfg: ModelConfig, x, num_groups: int = 1):
    """x: [B, S, D] -> [B, S, D]. num_groups should equal the DP shard count."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_tok
    t = b * s
    assert t % num_groups == 0, (t, num_groups)
    tg = t // num_groups
    cap = _capacity(tg, cfg)
    xg = x.reshape(num_groups, tg, d)
    xg = shard_act(xg, ("groups", None, "embed_act"))

    logits = jnp.einsum("gtd,de->gte", xg, p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # [G, Tg, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    def route_one(xg1, top_e1, top_p1):
        # xg1: [Tg, D]; top_e1/top_p1: [Tg, k]
        flat_e = top_e1.reshape(-1)  # [Tg*k]
        flat_t = jnp.repeat(jnp.arange(tg, dtype=jnp.int32), k)
        flat_p = top_p1.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        se, st, sp = flat_e[order], flat_t[order], flat_p[order]
        # rank within expert = index - first index of this expert id
        first = jnp.searchsorted(se, se, side="left")
        rank = jnp.arange(se.shape[0], dtype=jnp.int32) - first.astype(jnp.int32)
        keep = rank < cap
        dest = jnp.where(keep, se * cap + rank, e * cap)  # drop bucket at end
        buf = jnp.zeros((e * cap + 1, d), xg1.dtype).at[dest].set(xg1[st])
        return buf[: e * cap].reshape(e, cap, d), (se, st, sp, keep, dest)

    buf, (se, st, sp, keep, dest) = jax.vmap(route_one)(xg, top_e, top_p)
    buf = shard_act(buf, ("groups", "experts", None, "embed_act"))

    act = jax.nn.gelu if cfg.act == "geglu" else jax.nn.silu
    wg = p["w_gate"].astype(buf.dtype)
    wu = p["w_up"].astype(buf.dtype)
    wd = p["w_down"].astype(buf.dtype)
    h = act(jnp.einsum("gecd,edf->gecf", buf, wg)) * jnp.einsum("gecd,edf->gecf", buf, wu)
    h = shard_act(h, ("groups", "experts", None, "ffn"))
    out_buf = jnp.einsum("gecf,efd->gecd", h, wd)
    out_buf = shard_act(out_buf, ("groups", "experts", None, "embed_act"))

    def combine_one(out_buf1, se1, st1, sp1, keep1, dest1):
        flat = out_buf1.reshape(e * cap, d)
        vals = jnp.where(keep1[:, None], flat[jnp.minimum(dest1, e * cap - 1)], 0.0)
        vals = vals * sp1[:, None].astype(vals.dtype)
        return jnp.zeros((tg, d), vals.dtype).at[st1].add(vals)

    out = jax.vmap(combine_one)(out_buf, se, st, sp, keep, dest)
    out = out.reshape(b, s, d)

    if "shared" in p:
        sh = p["shared"]
        g = jnp.einsum("bsd,df->bsf", x, sh["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, sh["w_up"].astype(x.dtype))
        hh = act(g) * u
        hh = shard_act(hh, ("batch", "seq", "ffn"))
        shared_out = jnp.einsum("bsf,fd->bsd", hh, sh["w_down"].astype(x.dtype))
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dz->bsz", x, sh["gate"].astype(x.dtype)))
        out = out + gate * shared_out
    return out.astype(x.dtype)


def dropless_specs(cfg: ModelConfig) -> dict:
    """Router ``[d, E]``, the expert stacks under ``experts`` (``[E, d, f]``
    up, ``[E, f, d]`` down) and, with ``shared_d_ff``, one shared expert."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    specs = {
        "router": spec((d, e), ("embed", "experts")),
        "experts": {
            "w_up": spec((e, d, f), ("experts", "embed", "ffn")),
            "w_down": spec((e, f, d), ("experts", "ffn", "embed")),
        },
    }
    if cfg.shared_d_ff:
        specs["shared"] = {
            "w_up": spec((d, cfg.shared_d_ff), ("embed", "ffn")),
            "w_down": spec((cfg.shared_d_ff, d), ("ffn", "embed")),
        }
    return specs


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


# What _ragged_tiles models of XLA's ragged-dot kernel on a TPU v5e: the
# bf16 MXU peak and HBM bandwidth (published), a cost per grid step, and the
# scoped VMEM a tiling may take. Per the grid step: the default tiling's
# 160,965 steps a call at a round's 196,608 rows run 45-48 ms past the modelled
# bytes (nemotron3-nano.img-backlog). VMEM: compiled for a described v5e
# over a grid of tilings, every working set below 12 MiB compiles, and from
# there up the compiler, adding scratch of its own, can pass its 16 MiB.
_PEAK_FLOP_S, _HBM_BYTES_S = 197e12, 819e9
_STEP_S = 0.29e-6
_VMEM_BYTES = 12 << 20


def _ragged_time(rows: int, k: int, n: int, groups: int, itemsize: int, tiles):
    """Modelled seconds of ``jax.lax.ragged_dot`` of ``[rows, k]`` rows against
    ``[groups, k, n]`` on XLA's TPU kernel under ``tiles = (tm, tk, tn)``.

    The kernel visits a row tile once for each group in it, so its grid has
    up to ``ceil(rows / tm) + groups - 1`` row tiles, each computed whole over
    k and n padded to the tiles. Time: the padded FLOPs at the peak or the
    bytes at HBM bandwidth (lhs once per column tile, rhs once per row tile,
    the output once), whichever is longer, plus the grid's steps at
    ``_STEP_S``."""
    tm, tk, tn = tiles
    m_tiles = -(-rows // tm) + groups - 1
    kt, nt = -(-k // tk), -(-n // tn)
    flops = 2 * m_tiles * tm * kt * tk * nt * tn
    moved = itemsize * m_tiles * (kt * tk * (tm * nt + nt * tn) + tm * nt * tn)
    return (max(flops / _PEAK_FLOP_S, moved / _HBM_BYTES_S)
            + m_tiles * kt * nt * _STEP_S)


def _ragged_tiles(rows: int, k: int, n: int, groups: int, itemsize: int):
    """``(tm, tk, tn)`` for ``jax.lax.ragged_dot`` of ``[rows, k]`` rows
    against ``[groups, k, n]`` on XLA's TPU kernel: the tiling of least
    :func:`_ragged_time` whose working set (double-buffered lhs and rhs
    tiles, the output tile and its float32 accumulator) fits the scoped
    VMEM. tk and tn are multiples of 128 up to the dim rounded up to 128; tm
    is one of 128..2048, at most ``rows`` rounded up to 8."""
    up = lambda v, a: -(-v // a) * a  # noqa: E731
    fits = [(tm, tk, tn)
            for tm in sorted({min(t, up(rows, 8)) for t in (128, 256, 512, 1024, 2048)})
            for tk in range(128, up(k, 128) + 1, 128)
            for tn in range(128, up(n, 128) + 1, 128)
            if 2 * (tm * tk + tk * tn) * itemsize + tm * tn * (2 * itemsize + 4)
            < _VMEM_BYTES]
    return min(fits, key=lambda t: (_ragged_time(rows, k, n, groups, itemsize, t), t))


def _tiled_ragged_dot(lhs, rhs, sizes):
    """``jax.lax.ragged_dot`` under the tiles :func:`_ragged_tiles` picks
    for its shapes (XLA's TPU kernel reads them; other backends ignore it)."""
    tiles = _ragged_tiles(lhs.shape[0], lhs.shape[1], rhs.shape[2], rhs.shape[0],
                          lhs.dtype.itemsize)
    with set_xla_metadata(ragged_dot_tiling=",".join(map(str, tiles))):
        return jax.lax.ragged_dot(lhs, rhs, sizes)


def route(cfg: ModelConfig, scores):
    """Top-k of float32 router ``scores`` [T, E] -> (weights [T, k] float32,
    experts [T, k] int32). Ties go to the lower expert index
    (``jax.lax.top_k``); the k weights are renormalised to sum to 1 and
    scaled by ``routed_scale``."""
    w, idx = jax.lax.top_k(scores, cfg.experts_per_tok)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale
    return w, idx.astype(jnp.int32)


def dropless_moe(p, cfg: ModelConfig, x):
    """x: [B, S, D] -> [B, S, D]: routed relu^2 experts, no token dropped,
    plus the ungated shared expert (:func:`_dropless`).

    Tokens are independent, so under ``vmap`` (the round maps the drift over
    cores and slots) the mapped axis folds into the tokens: every lane's
    rows go through one sort and one pair of grouped matmuls."""
    leaves, tree = jax.tree_util.tree_flatten(p)

    @jax.custom_batching.custom_vmap
    def layer(x, *leaves):
        return _dropless(jax.tree_util.tree_unflatten(tree, leaves), cfg, x)

    @layer.def_vmap
    def _fold(axis_size, in_batched, x, *leaves):
        if any(in_batched[1:]):
            raise NotImplementedError("dropless_moe maps over tokens, not weights")
        return layer(x.reshape((-1,) + x.shape[2:]), *leaves).reshape(x.shape), True

    return layer(x, *leaves)


def _dropless(p, cfg: ModelConfig, x):
    """The layer on one batch of tokens.

    The router's sigmoid scores are computed in float32. Each
    token's k (token, expert) rows are sorted by expert, gathered, run
    through the two grouped matmuls over the real group sizes, put back in
    token order and summed with the gate weights. Parts run under the named
    scopes ``moe.router``, ``moe.dispatch`` (sort, gather, un-permute,
    combine), ``moe.experts`` and ``moe.shared`` (``repro.obs.scopes``)."""
    if (cfg.act, cfg.router) != ("relu2", "sigmoid"):
        raise ValueError(f"dropless_moe runs relu2 experts under a sigmoid router, "
                         f"not {cfg.act!r} under {cfg.router!r}")
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_tok
    t = b * s
    xf = x.reshape(t, d)
    with jax.named_scope("moe.router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        gate, expert = route(cfg, jax.nn.sigmoid(logits))  # [T, k]
    with jax.named_scope("moe.dispatch"):
        flat = expert.reshape(t * k)
        order = jnp.argsort(flat, stable=True)  # rows grouped by expert
        # compare-and-sum, not jnp.bincount: timed alone on a v5e at a round's
        # 196,608 rows, 0.69 ms against bincount's (scatter-add) 2.39 ms
        sizes = jnp.sum(flat[:, None] == jnp.arange(e, dtype=flat.dtype), axis=0,
                        dtype=jnp.int32)
        rows = xf[order // k]
    with jax.named_scope("moe.experts"):
        ex = p["experts"]
        hid = _relu2(_tiled_ragged_dot(rows, ex["w_up"].astype(x.dtype), sizes))
        out_rows = _tiled_ragged_dot(hid, ex["w_down"].astype(x.dtype), sizes)
    with jax.named_scope("moe.dispatch"):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
        back = out_rows[inv].reshape(t, k, d).astype(jnp.float32)
        out = jnp.sum(back * gate[..., None], axis=1)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            sh = p["shared"]
            hs = _relu2(jnp.einsum("td,df->tf", xf, sh["w_up"].astype(x.dtype)))
            out = out + jnp.einsum("tf,fd->td", hs, sh["w_down"].astype(x.dtype))
    return out.astype(x.dtype).reshape(b, s, d)


# ---------------------------------------------------------------------------
# Full model: dense attention + MoE FFN
# ---------------------------------------------------------------------------


def specs(cfg: ModelConfig) -> dict:
    n = cfg.num_layers
    return {
        "embed": L.embed_specs(cfg),
        "blocks": {
            "ln1": spec((n, cfg.d_model), ("layers", None), init="ones"),
            "attn": L.attention_specs(cfg, layers=n),
            "ln2": spec((n, cfg.d_model), ("layers", None), init="ones"),
            "moe": moe_specs(cfg, layers=n),
        },
        "final_norm": spec((cfg.d_model,), (None,), init="ones"),
    }


def _block(cfg, p, h, positions, causal, attn_impl, num_groups, cache=None, cur_len=None):
    x = L.rmsnorm(h, p["ln1"], cfg.norm_eps)
    q, k, v = L.qkv_proj(p["attn"], cfg, x, positions)
    new_kv = None
    if cache is not None and cur_len is not None:
        k_cache, v_cache = cache
        idx = cur_len[0]
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), idx, axis=1)
        attn = L.attend_decode(q, k_cache, v_cache, cur_len + 1)
        new_kv = (k_cache, v_cache)
    else:
        attn = L.attend(q, k, v, positions, positions, causal, impl=attn_impl)
        if cache == "collect":
            new_kv = (k, v)
    h = h + L.out_proj(p["attn"], attn)
    x = L.rmsnorm(h, p["ln2"], cfg.norm_eps)
    h = h + moe_ffn(p["moe"], cfg, x, num_groups)
    h = shard_act(h, ("batch", "seq", "embed_act"))
    return h, new_kv


def forward_hidden(params, cfg, embeds, positions=None, causal=False,
                   attn_impl="auto", remat=False, num_groups=1):
    b, s, _ = embeds.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(h, p):
        h, _ = _block(cfg, p, h, positions, causal, attn_impl, num_groups)
        return h, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    h, _ = jax.lax.scan(body, embeds, params["blocks"])
    return L.rmsnorm(h, params["final_norm"], cfg.norm_eps)


def forward_train(params, cfg, tokens, attn_impl="auto", remat=True, num_groups=1):
    e = L.embed(params["embed"], cfg, tokens)
    e = shard_act(e, ("batch", "seq", "embed_act"))
    h = forward_hidden(params, cfg, e, causal=True, attn_impl=attn_impl, remat=remat,
                       num_groups=num_groups)
    return L.unembed(params["embed"], cfg, h)


init_cache = None  # set below (same as dense)
from repro.models import dense as _dense  # noqa: E402

init_cache = _dense.init_cache
cache_specs = _dense.cache_specs
cache_axes = _dense.cache_axes


def prefill(params, cfg, tokens, max_len, attn_impl="auto", num_groups=1):
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    e = L.embed(params["embed"], cfg, tokens)
    e = shard_act(e, ("batch", "seq", "embed_act"))

    def body(h, p):
        h, kv = _block(cfg, p, h, positions, True, attn_impl, num_groups, cache="collect")
        return h, kv

    h, (ks, vs) = jax.lax.scan(body, e, params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    pad = max_len - s
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16),
        "len": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def decode_step(params, cfg, tokens, cache, attn_impl="auto", num_groups=1):
    b = tokens.shape[0]
    cur = cache["len"]
    positions = jnp.broadcast_to(cur[0][None, None], (b, 1)).astype(jnp.int32)
    e = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        p, k_cache, v_cache = xs
        h, new_kv = _block(cfg, p, h, positions, True, attn_impl, num_groups,
                           cache=(k_cache, v_cache), cur_len=cur)
        return h, new_kv

    h, (ks, vs) = jax.lax.scan(body, e, (params["blocks"], cache["k"], cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    return logits, {"k": ks, "v": vs, "len": cur + 1}
