"""DiffusionWrapper: turn any assigned backbone into f_theta(x, t).

Latent-sequence denoiser (DiT/diffusion-LM style): in-proj latent -> d_model,
sinusoidal time embedding (MLP'd) added to every position, backbone run
non-causally in hidden mode, out-proj back to the latent dim. The wrapped
drift is velocity-prediction under rectified flow, so CHORDS/Euler on it is
exactly the paper's Flux/SD3 setting.

Kernel plumbing: the ``cfg`` captured by :func:`make_drift` carries
``use_kernels`` (``repro.configs.base.ModelConfig``), so a drift built from
``cfg.replace(use_kernels=True)`` dispatches the backbone's rmsnorm /
attention / ssd-scan through the Pallas kernel library everywhere it is
called — ``make_slot_round_body`` → ``RoundExecutor`` → the serve engines —
with no extra arguments threaded through the sampler stack (see
kernels/README.md).

Weights: :func:`make_drift` returns a :class:`repro.core.ode.ParamDrift`,
so the serve executor passes ``params`` to its compiled programs as an
argument rather than baking them in as constants, and
:func:`init_wrapper` draws the parameters on the device in their final
dtype under ``jit``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ode import ParamDrift
from repro.models import api as model_api
from repro.utils.pspec import init_params, spec


def wrapper_specs(cfg: ModelConfig, latent_dim: int) -> dict:
    d = cfg.d_model
    return {
        "backbone": model_api.model_specs(cfg),
        "in_proj": spec((latent_dim, d), (None, "embed")),
        "t_mlp1": spec((256, d), (None, "embed")),
        "t_mlp2": spec((d, d), ("embed", "embed_act")),
        "out_norm": spec((d,), (None,), init="ones"),
        "out_proj": spec((d, latent_dim), ("embed", None), init="zeros"),
    }


def init_wrapper(cfg: ModelConfig, latent_dim: int, key, dtype=jnp.float32):
    """Wrapper + backbone parameters in ``dtype``, drawn on the default
    device. f32 suits training; serving passes ``cfg.param_dtype``."""
    return init_params(wrapper_specs(cfg, latent_dim), key, dtype)


def time_embedding(t, dim=256, max_period=1e4):
    """t: scalar or [B] in [0,1] -> [.., dim] sinusoidal features."""
    t = jnp.asarray(t, jnp.float32) * 1000.0
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = t[..., None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def denoise(params, cfg: ModelConfig, x, t, **fw_kwargs):
    """x: [B, S, latent_dim]; t: scalar in [0,1]. Returns velocity [B,S,latent].

    The in-projection with the time embedding and the output norm with the
    out-projection run under the named scopes ``wrapper.in`` and
    ``wrapper.out`` (``repro.obs.scopes``)."""
    dt_ = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("wrapper.in"):
        h = jnp.einsum("bsl,ld->bsd", x.astype(dt_), params["in_proj"].astype(dt_))
        te = time_embedding(t)  # [256]
        te = jax.nn.silu(te @ params["t_mlp1"].astype(jnp.float32))
        te = te @ params["t_mlp2"].astype(jnp.float32)
        h = h + te.astype(dt_)
    h = model_api.forward_hidden(params["backbone"], cfg, h, causal=False, **fw_kwargs)
    with jax.named_scope("wrapper.out"):
        hf = h.astype(jnp.float32)
        hf = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + cfg.norm_eps)
        hf = hf * params["out_norm"].astype(jnp.float32)
        return jnp.einsum("bsd,dl->bsl", hf, params["out_proj"].astype(jnp.float32)).astype(
            x.dtype)


def make_drift(params, cfg: ModelConfig, **fw_kwargs) -> ParamDrift:
    """Drift for repro.core samplers. x: [B, S, latent]; t scalar."""

    def drift(p, x, t):
        return denoise(p, cfg, x, t, **fw_kwargs)

    return ParamDrift(drift, params)


def diffusion_loss(params, cfg: ModelConfig, x1, key, **fw_kwargs):
    """Rectified-flow training loss: E ||v_theta(x_t, t) - (x1 - eps)||^2."""
    b = x1.shape[0]
    k1, k2 = jax.random.split(key)
    t = jax.random.uniform(k1, (b, 1, 1), minval=0.0, maxval=1.0)
    eps = jax.random.normal(k2, x1.shape, x1.dtype)
    x_t = (1.0 - t) * eps + t * x1
    # per-sample t: broadcast inside as scalar per batch via vmap
    v = _denoise_batch_t(params, cfg, x_t, t[:, 0, 0], **fw_kwargs)
    target = x1 - eps
    return jnp.mean((v.astype(jnp.float32) - target.astype(jnp.float32)) ** 2)


def _denoise_batch_t(params, cfg, x, t_vec, **fw_kwargs):
    """Per-sample timesteps (training); x: [B,S,L], t_vec: [B]."""
    dt_ = jnp.dtype(cfg.compute_dtype)
    h = jnp.einsum("bsl,ld->bsd", x.astype(dt_), params["in_proj"].astype(dt_))
    te = time_embedding(t_vec)  # [B, 256]
    te = jax.nn.silu(te @ params["t_mlp1"].astype(jnp.float32))
    te = te @ params["t_mlp2"].astype(jnp.float32)
    h = h + te[:, None, :].astype(dt_)
    h = model_api.forward_hidden(params["backbone"], cfg, h, causal=False, **fw_kwargs)
    hf = h.astype(jnp.float32)
    hf = hf * jax.lax.rsqrt(jnp.mean(hf * hf, -1, keepdims=True) + cfg.norm_eps)
    hf = hf * params["out_norm"].astype(jnp.float32)
    return jnp.einsum("bsd,dl->bsl", hf, params["out_proj"].astype(jnp.float32)).astype(
        x.dtype)
