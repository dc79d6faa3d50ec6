"""Dense decoder-only transformer (qwen1.5-*, gemma-7b, internlm2, qwen2-vl, DiT).

Scan-over-layers with stacked params (compile-time + remat friendly). Four
entry points share one layer body:

  * ``forward_hidden``  — embeds in, hidden out (diffusion-denoiser role;
                          optionally non-causal)
  * ``forward_train``   — tokens -> logits (full sequence, causal)
  * ``prefill``         — tokens -> logits + KV cache
  * ``decode_step``     — one token + cache -> logits + cache
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.models import layers as L
from repro.utils.pspec import spec


def specs(cfg: ModelConfig) -> dict:
    n = cfg.num_layers
    return {
        "embed": L.embed_specs(cfg),
        "blocks": {
            "ln1": spec((n, cfg.d_model), ("layers", None), init="ones"),
            "attn": L.attention_specs(cfg, layers=n),
            "ln2": spec((n, cfg.d_model), ("layers", None), init="ones"),
            "mlp": L.mlp_specs(cfg, layers=n),
        },
        "final_norm": spec((cfg.d_model,), (None,), init="ones"),
    }


def _block(cfg: ModelConfig, p, h, positions, causal, attn_impl, cache=None,
           cur_len=None):
    """One transformer block. Returns (h, new_kv or None).

    Its parts run under the named scopes ``norm``, ``attn`` and ``mlp``
    (``repro.obs.scopes``), which name them in compiled ops' metadata.

    ``cfg.use_kernels`` routes the norms and the (non-decode) attention
    through the Pallas kernel library (``repro.kernels``); positions here
    are 0-based aranges, which is the flash kernel's causal contract.
    """
    uk = cfg.use_kernels
    with jax.named_scope("norm"):
        x = L.rmsnorm(h, p["ln1"], cfg.norm_eps, use_kernel=uk)
    with jax.named_scope("attn"):
        q, k, v = L.qkv_proj(p["attn"], cfg, x, positions)
        new_kv = None
        if cache is not None and cur_len is not None:  # decode: append to cache
            k_cache, v_cache = cache
            idx = cur_len[0]  # uniform position across batch (batched decode)
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), idx, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), idx, axis=1)
            attn = L.attend_decode(q, k_cache, v_cache, cur_len + 1)
            new_kv = (k_cache, v_cache)
        else:
            q_pos = positions[0] if cfg.mrope_sections else positions
            attn = L.attend(q, k, v, q_pos, q_pos, causal, impl=attn_impl,
                            use_kernel=uk)
            if cache == "collect":
                new_kv = (k, v)
        h = h + L.out_proj(p["attn"], attn)
    h = shard_act(h, ("batch", "seq", "embed_act"))
    with jax.named_scope("norm"):
        x = L.rmsnorm(h, p["ln2"], cfg.norm_eps, use_kernel=uk)
    with jax.named_scope("mlp"):
        h = h + L.mlp(p["mlp"], cfg, x)
    h = shard_act(h, ("batch", "seq", "embed_act"))
    return h, new_kv


def _positions(cfg: ModelConfig, b, s, offset=0):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (b, s))
    if cfg.mrope_sections:
        return jnp.broadcast_to(pos[None], (3, b, s))  # all-text M-RoPE
    return pos


def forward_hidden(params, cfg: ModelConfig, embeds, positions=None, causal=False,
                   attn_impl="auto", remat=False):
    """embeds: [B, S, D] -> hidden [B, S, D]."""
    b, s, _ = embeds.shape
    if positions is None:
        positions = _positions(cfg, b, s)

    def body(h, p):
        h, _ = _block(cfg, p, h, positions, causal, attn_impl)
        return h, None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    h, _ = jax.lax.scan(body, embeds, params["blocks"])
    with jax.named_scope("norm"):
        return L.rmsnorm(h, params["final_norm"], cfg.norm_eps,
                         use_kernel=cfg.use_kernels)


def forward_train(params, cfg: ModelConfig, tokens, positions=None, attn_impl="auto",
                  remat=True, embeds=None):
    e = embeds if embeds is not None else L.embed(params["embed"], cfg, tokens)
    e = shard_act(e, ("batch", "seq", "embed_act"))
    h = forward_hidden(params, cfg, e, positions, causal=True, attn_impl=attn_impl,
                       remat=remat)
    return L.unembed(params["embed"], cfg, h)


def init_cache(cfg: ModelConfig, batch, max_len, dtype=jnp.bfloat16):
    kv, dh, n = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    shape = (n, batch, max_len, kv, dh)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, batch, max_len, dtype=jnp.bfloat16):
    kv, dh, n = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    shape = (n, batch, max_len, kv, dh)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "len": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def cache_axes(cfg: ModelConfig):
    ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": ax, "v": ax, "len": ("batch",)}


def prefill(params, cfg: ModelConfig, tokens, max_len, attn_impl="auto", embeds=None):
    """tokens: [B, S] -> (logits [B, S, V], cache filled to S)."""
    b, s = tokens.shape[:2]
    positions = _positions(cfg, b, s)
    e = embeds if embeds is not None else L.embed(params["embed"], cfg, tokens)
    e = shard_act(e, ("batch", "seq", "embed_act"))

    def body(h, p):
        h, kv = _block(cfg, p, h, positions, True, attn_impl, cache="collect")
        return h, kv

    h, (ks, vs) = jax.lax.scan(body, e, params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    pad = max_len - s
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16)
        if pad else ks.astype(jnp.bfloat16),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))).astype(jnp.bfloat16)
        if pad else vs.astype(jnp.bfloat16),
        "len": jnp.full((b,), s, jnp.int32),
    }
    return logits, cache


def decode_step(params, cfg: ModelConfig, tokens, cache, attn_impl="auto"):
    """tokens: [B, 1]; returns (logits [B, 1, V], cache)."""
    b = tokens.shape[0]
    cur = cache["len"]
    positions = _positions(cfg, b, 1, offset=cur[0])
    e = L.embed(params["embed"], cfg, tokens)

    def body(h, xs):
        p, k_cache, v_cache = xs
        h, new_kv = _block(cfg, p, h, positions, True, attn_impl,
                           cache=(k_cache, v_cache), cur_len=cur)
        return h, new_kv

    h, (ks, vs) = jax.lax.scan(body, e, (params["blocks"], cache["k"], cache["v"]))
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], cfg, h)
    new_cache = {"k": ks, "v": vs, "len": cur + 1}
    return logits, new_cache
