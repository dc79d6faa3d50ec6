"""Nemotron-H hybrid (NVIDIA-Nemotron-3-Nano-30B-A3B) as a denoiser trunk.

One pre-norm layer per letter of ``cfg.layer_pattern``, each with one
mixer: ``h <- h + mixer(RMSNorm(h))``, then a final RMSNorm.

* ``M``: Mamba2 with grouped B/C, a conv bias and the group-wise gated norm
  (``models/mamba2.py``);
* ``E``: the dropless MoE, sigmoid router, relu^2 experts and one shared
  expert (``models/moe.py`` ``dropless_moe``);
* ``*``: GQA attention with no positional encoding (the published
  ``nemotron_h`` attention applies none), no bias.

Each layer's weights are a dict of their own in the list ``blocks``: the
layers differ in kind, and a per-layer leaf is an argument of the compiled
program, never a slice of a stack. The family has no token embedding, LM
head or cache: the diffusion wrapper (``repro.diffusion``) projects the
latent in and out. The scan is causal, so is the attention, as for every
recurrent backbone (``models/api.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe
from repro.utils.pspec import spec

MIXERS = {"M": "mamba", "E": "moe", "*": "attn"}


def specs(cfg: ModelConfig) -> dict:
    if len(cfg.layer_pattern) != cfg.num_layers or set(cfg.layer_pattern) - set(MIXERS):
        raise ValueError(f"layer_pattern {cfg.layer_pattern!r} does not give one of "
                         f"{''.join(MIXERS)} for each of {cfg.num_layers} layers")
    d = cfg.d_model

    def layer(kind):
        mixer = {"M": M.ssd_specs, "E": moe.dropless_specs,
                 "*": L.attention_specs}[kind](cfg)
        return {"ln": spec((d,), (None,), init="ones"), MIXERS[kind]: mixer}

    return {
        "blocks": [layer(kind) for kind in cfg.layer_pattern],
        "final_norm": spec((d,), (None,), init="ones"),
    }


def forward_hidden(params, cfg: ModelConfig, embeds, attn_impl="auto"):
    """embeds [B, S, D] -> hidden [B, S, D], causal over S."""
    b, s, _ = embeds.shape
    uk = cfg.use_kernels
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h = embeds
    for kind, p in zip(cfg.layer_pattern, params["blocks"]):
        with jax.named_scope("norm"):
            x = L.rmsnorm(h, p["ln"], cfg.norm_eps, use_kernel=uk)
        if kind == "M":
            y, _ = M.ssd_forward(p["mamba"], cfg, x)
            with jax.named_scope("mamba2.out"):
                h = h + y
        elif kind == "E":
            y = moe.dropless_moe(p["moe"], cfg, x)
            with jax.named_scope("moe.dispatch"):
                h = h + y
        else:
            with jax.named_scope("attn"):
                # positions=None: no RoPE; the causal mask still reads them
                q, k, v = L.qkv_proj(p["attn"], cfg, x, None)
                a = L.attend(q, k, v, positions, positions, True, impl=attn_impl,
                             use_kernel=uk)
                h = h + L.out_proj(p["attn"], a)
    with jax.named_scope("norm"):
        return L.rmsnorm(h, params["final_norm"], cfg.norm_eps, use_kernel=uk)
