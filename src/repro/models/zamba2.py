"""Zamba2-2.7B: Mamba2 backbone with a single *shared* attention+MLP block.

54 SSD layers; after every 6th layer the shared block (one parameter set,
9 invocations) runs on concat(hidden, initial_embedding) per the Zamba design.
Decode keeps 9 separate KV caches (one per invocation) + per-layer SSM states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.utils.pspec import spec


def _groups(cfg: ModelConfig) -> tuple[int, int]:
    per = cfg.attn_every
    assert cfg.num_layers % per == 0, (cfg.num_layers, per)
    return cfg.num_layers // per, per  # (num_groups, layers_per_group)


def specs(cfg: ModelConfig) -> dict:
    n = cfg.num_layers
    d = cfg.d_model
    return {
        "embed": L.embed_specs(cfg),
        "mamba": {
            "ln": spec((n, d), ("layers", None), init="ones"),
            "ssd": M.ssd_specs(cfg, layers=n),
        },
        "shared": {
            "ln_in": spec((2 * d,), (None,), init="ones"),
            "w_in": spec((2 * d, d), ("embed", None)),
            "ln1": spec((d,), (None,), init="ones"),
            "attn": L.attention_specs(cfg),
            "ln2": spec((d,), (None,), init="ones"),
            "mlp": L.mlp_specs(cfg),
            "w_out": spec((d, d), (None, "embed")),
        },
        "final_norm": spec((d,), (None,), init="ones"),
    }


def _reshape_groups(tree, g, per):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((g, per) + x.shape[1:]), tree
    )


def _shared_block(cfg, sp, h, h0, positions, attn_impl, kv_cache=None, cur_len=None):
    """The shared attention+MLP block, under the named scope
    ``shared_block`` with its own ``norm``/``attn``/``mlp`` inside."""
    with jax.named_scope("shared_block"):
        uk = cfg.use_kernels
        x = jnp.concatenate([h, h0], axis=-1)
        with jax.named_scope("norm"):
            x = L.rmsnorm(x, sp["ln_in"], cfg.norm_eps, use_kernel=uk)
        x = jnp.einsum("bse,ed->bsd", x, sp["w_in"].astype(h.dtype))
        with jax.named_scope("norm"):
            a_in = L.rmsnorm(x, sp["ln1"], cfg.norm_eps, use_kernel=uk)
        with jax.named_scope("attn"):
            q, k, v = L.qkv_proj(sp["attn"], cfg, a_in, positions)
            new_kv = None
            if kv_cache is not None and cur_len is not None:
                kc, vc = kv_cache
                idx = cur_len[0]
                kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), idx, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), idx, axis=1)
                attn = L.attend_decode(q, kc, vc, cur_len + 1)
                new_kv = (kc, vc)
            else:
                attn = L.attend(q, k, v, positions, positions, True, impl=attn_impl,
                                use_kernel=uk)
                if kv_cache == "collect":
                    new_kv = (k, v)
            x = x + L.out_proj(sp["attn"], attn)
        with jax.named_scope("norm"):
            m_in = L.rmsnorm(x, sp["ln2"], cfg.norm_eps, use_kernel=uk)
        with jax.named_scope("mlp"):
            x = x + L.mlp(sp["mlp"], cfg, m_in)
        out = jnp.einsum("bsd,de->bse", x, sp["w_out"].astype(h.dtype))
        return h + out, new_kv


def forward_hidden(params, cfg: ModelConfig, embeds, positions=None, causal=True,
                   attn_impl="auto", remat=False, state=None, collect_kv=False):
    """Returns (hidden, (mamba_states, kv_list)) — states None unless requested."""
    b, s, _ = embeds.shape
    g, per = _groups(cfg)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h0 = embeds
    mamba = _reshape_groups(params["mamba"], g, per)

    def inner(h, p, conv_st, ssm_st):
        with jax.named_scope("norm"):
            x = L.rmsnorm(h, p["ln"], cfg.norm_eps,
                          use_kernel=cfg.use_kernels)
        y, (new_conv, new_ssm) = M.ssd_forward(p["ssd"], cfg, x, conv_st, ssm_st)
        with jax.named_scope("mamba2.out"):
            return h + y, new_conv, new_ssm

    def outer(h, xs):
        pg = xs
        def step(hc, pp):
            hh, nc_, ns_ = inner(hc, pp, None, None)
            return hh, (nc_, ns_)
        h, (convs, ssms) = jax.lax.scan(step, h, pg)
        h, kv = _shared_block(cfg, params["shared"], h, h0, positions, attn_impl,
                              kv_cache="collect" if collect_kv else None)
        return h, (convs, ssms, kv)

    if remat:
        outer = jax.checkpoint(outer, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    h, (convs, ssms, kvs) = jax.lax.scan(outer, embeds, mamba)
    with jax.named_scope("norm"):
        h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps,
                      use_kernel=cfg.use_kernels)

    aux = None
    if collect_kv:
        # convs/ssms: [G, per, B, ...] -> [L, B, ...]
        flat = lambda t: t.reshape((cfg.num_layers,) + t.shape[2:])
        aux = (flat(convs), flat(ssms), kvs)
    return h, aux


def forward_train(params, cfg: ModelConfig, tokens, attn_impl="auto", remat=True):
    e = L.embed(params["embed"], cfg, tokens)
    e = shard_act(e, ("batch", "seq", "embed_act"))
    h, _ = forward_hidden(params, cfg, e, attn_impl=attn_impl, remat=remat)
    return L.unembed(params["embed"], cfg, h)


def cache_specs(cfg: ModelConfig, batch, max_len, dtype=jnp.bfloat16):
    g, _ = _groups(cfg)
    kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    ssm = M.ssd_state_specs(cfg, batch, cfg.num_layers)
    return {
        "conv": ssm["conv"],
        "ssm": ssm["ssm"],
        "k": jax.ShapeDtypeStruct((g, batch, max_len, kv, dh), dtype),
        "v": jax.ShapeDtypeStruct((g, batch, max_len, kv, dh), dtype),
        "len": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig):
    ssm_ax = M.ssd_state_axes()
    kv_ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"conv": ssm_ax["conv"], "ssm": ssm_ax["ssm"], "k": kv_ax, "v": kv_ax,
            "len": ("batch",)}


def init_cache(cfg: ModelConfig, batch, max_len, dtype=jnp.bfloat16):
    return jax.tree_util.tree_map(
        lambda t: jnp.zeros(t.shape, t.dtype), cache_specs(cfg, batch, max_len, dtype)
    )


def prefill(params, cfg: ModelConfig, tokens, max_len, attn_impl="auto"):
    b, s = tokens.shape
    e = L.embed(params["embed"], cfg, tokens)
    h, aux = forward_hidden(params, cfg, e, attn_impl=attn_impl, collect_kv=True)
    logits = L.unembed(params["embed"], cfg, h)
    convs, ssms, (ks, vs) = aux
    pad = max_len - s
    cache = {
        "conv": convs.astype(jnp.bfloat16),
        "ssm": ssms,
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16),
        "len": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def decode_step(params, cfg: ModelConfig, tokens, cache, attn_impl="auto"):
    """Caches pass through scan xs/ys: both alternatives were REFUTED on the
    dry-run (§Perf cell B): carry-indexed updates resharded the seq-sharded
    cache (collectives blew up 100x); unrolling the 9 groups inflated
    collectives via per-group activation resharding. The xs/ys form keeps
    each group's cache slice local; remaining DUS stacking cost is an
    XLA-CPU artifact that TPU buffer donation avoids."""
    b = tokens.shape[0]
    g, per = _groups(cfg)
    cur = cache["len"]
    positions = jnp.broadcast_to(cur[0][None, None], (b, 1)).astype(jnp.int32)
    e = L.embed(params["embed"], cfg, tokens)
    h0 = e
    mamba = _reshape_groups(params["mamba"], g, per)
    conv_g = cache["conv"].reshape((g, per) + cache["conv"].shape[1:])
    ssm_g = cache["ssm"].reshape((g, per) + cache["ssm"].shape[1:])

    def outer(h, xs):
        pg, conv_st, ssm_st, kc, vc = xs

        def step(hc, inp):
            pp, cst, sst = inp
            x = L.rmsnorm(hc, pp["ln"], cfg.norm_eps)
            y, (nc_, ns_) = M.ssd_decode_step(pp["ssd"], cfg, x, cst, sst)
            return hc + y, (nc_, ns_)

        h, (new_conv, new_ssm) = jax.lax.scan(step, h, (pg, conv_st, ssm_st))
        h, (nk, nv) = _shared_block(cfg, params["shared"], h, h0, positions, attn_impl,
                                    kv_cache=(kc, vc), cur_len=cur)
        return h, (new_conv, new_ssm, nk, nv)

    h, (convs, ssms, ks, vs) = jax.lax.scan(
        outer, e, (mamba, conv_g, ssm_g, cache["k"], cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    flat = lambda t: t.reshape((cfg.num_layers,) + t.shape[2:])
    new_cache = {
        "conv": flat(convs), "ssm": flat(ssms), "k": ks, "v": vs, "len": cur + 1,
    }
    return logits, new_cache
