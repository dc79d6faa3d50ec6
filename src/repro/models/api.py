"""Unified model API: dispatch by config family + shared loss functions.

Every family module exposes (duck-typed):
  specs(cfg), forward_train(params, cfg, tokens, ...), prefill(...),
  decode_step(params, cfg, tokens, cache, ...), cache_specs / cache_axes /
  init_cache, and forward_hidden (diffusion-denoiser role). ``nemotron_h``
  is a denoiser trunk only: specs and forward_hidden.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.models import dense, encdec, moe, nemotron_h, xlstm, zamba2
from repro.utils import pspec

_FAMILY = {
    "dense": dense,
    "vlm": dense,
    "moe": moe,
    "hybrid": zamba2,
    "nemotron_h": nemotron_h,
    "ssm": xlstm,
    "encdec": encdec,
    "audio": encdec,
}


def get_module(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def model_specs(cfg: ModelConfig) -> dict:
    return get_module(cfg).specs(cfg)


def param_count(cfg: ModelConfig) -> int:
    return pspec.count_params(model_specs(cfg))


def init_model(cfg: ModelConfig, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return pspec.init_params(model_specs(cfg), key, dtype)


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.family in ("encdec", "audio")


def lm_loss(params, cfg: ModelConfig, batch: dict, **fw_kwargs) -> jax.Array:
    """Next-token CE loss. batch: {tokens, labels[, src_embeds]}; labels -100=pad."""
    mod = get_module(cfg)
    tokens = batch["tokens"]
    if is_encdec(cfg):
        logits = mod.forward_train(params, cfg, tokens, batch["src_embeds"], **fw_kwargs)
    else:
        logits = mod.forward_train(params, cfg, tokens, **fw_kwargs)
    labels = batch["labels"]
    logits = shard_act(logits, ("batch", "seq", "vocab"))
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def forward_hidden(params, cfg: ModelConfig, embeds, **kw):
    """Backbone as a denoiser trunk: embeds in, hidden out (non-causal)."""
    mod = get_module(cfg)
    if is_encdec(cfg):
        # decoder trunk, bidirectional self-attn, cross-attn to conditioning
        memory = kw.pop("memory", None)
        if memory is None:
            b = embeds.shape[0]
            memory = jnp.zeros((b, 16, cfg.d_model), embeds.dtype)
        s = embeds.shape[1]
        b = embeds.shape[0]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        mem_pos = jnp.broadcast_to(
            jnp.arange(memory.shape[1], dtype=jnp.int32)[None], (b, memory.shape[1]))

        def body(h, p):
            h, _ = encdec._dec_block(cfg, p, h, memory, pos, mem_pos,
                                     kw.get("attn_impl", "auto"))
            return h, None

        h, _ = jax.lax.scan(body, embeds, params["dec"])
        from repro.models import layers as L
        return L.rmsnorm(h, params["final_norm"], cfg.norm_eps,
                         use_kernel=cfg.use_kernels)
    if cfg.family in ("hybrid",):
        # zamba2 returns (hidden, aux); recurrent backbones are causal-only
        kw.pop("causal", None)
        h, _ = mod.forward_hidden(params, cfg, embeds, causal=True, **kw)
        return h
    if cfg.family in ("ssm", "nemotron_h"):
        # recurrent backbones are causal-only
        kw.pop("causal", None)
        return mod.forward_hidden(params, cfg, embeds, **kw)
    return mod.forward_hidden(params, cfg, embeds, **kw)
