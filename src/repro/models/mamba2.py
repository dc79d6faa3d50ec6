"""Mamba2 (SSD) layer: chunked parallel scan for train/prefill, O(1) decode.

State-space duality form (Dao & Gu 2024) adapted for TPU:
  * depthwise causal conv implemented as w shifted multiplies (layout-friendly)
  * intra-chunk term = masked [Lc, Lc] einsum per head (MXU-shaped)
  * inter-chunk recurrence = lax.scan over chunks carrying [B, H, hd, N] state
The Pallas kernel in ``repro.kernels.ssd_scan`` implements the intra-chunk
block; this module is the XLA reference path used by dry-run and CPU tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.kernels import resolve_kernel_mode
from repro.utils.pspec import spec


def d_inner(cfg: ModelConfig) -> int:
    if cfg.ssm_heads:
        return cfg.ssm_heads * cfg.ssm_head_dim
    return cfg.ssm_expand * cfg.d_model


def num_ssm_heads(cfg: ModelConfig) -> int:
    return cfg.ssm_heads or d_inner(cfg) // cfg.ssm_head_dim


def _bc_width(cfg: ModelConfig) -> int:
    """Width of each of B and C: ``ssm_groups`` groups of ``ssm_state``."""
    return cfg.ssm_groups * cfg.ssm_state


def ssd_specs(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    d, din, n, h, w = (cfg.d_model, d_inner(cfg), _bc_width(cfg), num_ssm_heads(cfg),
                       cfg.ssm_conv)
    conv_ch = din + 2 * n
    Ld = () if layers is None else (layers,)
    La = () if layers is None else ("layers",)

    def s(shape, axes, **kw):
        return spec(Ld + tuple(shape), La + tuple(axes), **kw)

    specs = {
        "in_proj": s((d, 2 * din + 2 * n + h), ("embed", "ffn")),
        "conv_w": s((w, conv_ch), ("conv", "ffn"), init="normal", scale=0.5),
        "a_log": s((h,), ("heads",), init="zeros"),
        "d_skip": s((h,), ("heads",), init="ones"),
        "dt_bias": s((h,), ("heads",), init="zeros"),
        "gate_norm": s((din,), ("ffn",), init="ones"),
        "out_proj": s((din, d), ("ffn", "embed")),
    }
    if cfg.ssm_conv_bias:
        specs["conv_b"] = s((conv_ch,), ("ffn",), init="zeros")
    return specs


def _depthwise_causal_conv(x, w, state=None):
    """x: [B, S, C]; w: [W, C]. Returns (y [B,S,C], new_state [B, W-1, C])."""
    wlen = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], wlen - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # [B, S+W-1, C]
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(wlen)
    )
    new_state = xp[:, xp.shape[1] - (wlen - 1):, :]
    return y, new_state


def _split(cfg, proj):
    din, n = d_inner(cfg), _bc_width(cfg)
    z = proj[..., :din]
    xc = proj[..., din : 2 * din]
    b_ = proj[..., 2 * din : 2 * din + n]
    c_ = proj[..., 2 * din + n : 2 * din + 2 * n]
    dt = proj[..., 2 * din + 2 * n :]
    return z, xc, b_, c_, dt


def _gated_norm(y, z, w, eps, groups=1):
    """RMSNorm of ``y * SiLU(z)``, over each of ``groups`` equal groups of
    channels, times ``w``."""
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    dt_ = y.dtype
    y = y.astype(jnp.float32)
    if groups > 1:
        y = y.reshape(y.shape[:-1] + (groups, y.shape[-1] // groups))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    if groups > 1:
        y = y.reshape(y.shape[:-2] + (-1,))
    return (y * w.astype(jnp.float32)).astype(dt_)


def _conv_act(cfg, p, conv_in, conv_state):
    """Causal depthwise conv (plus its bias, where the config has one), SiLU."""
    conv_out, new_conv = _depthwise_causal_conv(conv_in, p["conv_w"].astype(conv_in.dtype),
                                                conv_state)
    if cfg.ssm_conv_bias:
        conv_out = conv_out + p["conv_b"].astype(conv_out.dtype)
    return jax.nn.silu(conv_out), new_conv


def ssd_forward(p, cfg: ModelConfig, x, conv_state=None, ssm_state=None):
    """Chunked SSD. x: [B, S, D] -> (y [B, S, D], (conv_state, ssm_state)).

    With ``ssm_groups`` G > 1, B and C come in G groups and head ``h``
    reads group ``h // (H / G)``: the scan runs with heads split as
    ``[G, H / G]``, and the gated norm normalises each group's channels.

    Its parts run under the named scopes ``mamba2.in_proj``,
    ``mamba2.conv``, ``mamba2.scan`` and ``mamba2.out``
    (``repro.obs.scopes``)."""
    bsz, s, _ = x.shape
    din, n, h, hd = d_inner(cfg), cfg.ssm_state, num_ssm_heads(cfg), cfg.ssm_head_dim
    grp, gn = cfg.ssm_groups, _bc_width(cfg)
    # the head and B/C axes: [H] and [] with one group, [G, H/G] and [G]
    hs, gs = ((h,), ()) if grp == 1 else ((grp, h // grp), (grp,))
    lc = min(cfg.ssm_chunk, s)
    assert s % lc == 0, (s, lc)
    nc = s // lc

    with jax.named_scope("mamba2.in_proj"):
        proj = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))
    with jax.named_scope("mamba2.conv"):
        z, xc, b_, c_, dt = _split(cfg, proj)
        conv_in = jnp.concatenate([xc, b_, c_], axis=-1)
        conv_out, new_conv = _conv_act(cfg, p, conv_in, conv_state)
        xc = conv_out[..., :din]
        b_ = conv_out[..., din : din + gn]
        c_ = conv_out[..., din + gn :]
    with jax.named_scope("mamba2.scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))  # [H]
        loga = dt * a[None, None, :]  # [B, S, H]  (log decay, <= 0)

        xh = xc.reshape((bsz, nc, lc) + hs + (hd,))
        bh = b_.reshape((bsz, nc, lc) + gs + (n,)).astype(jnp.float32)
        ch = c_.reshape((bsz, nc, lc) + gs + (n,)).astype(jnp.float32)
        dth = dt.reshape((bsz, nc, lc) + hs)
        logc = loga.reshape((bsz, nc, lc) + hs)
        xh = shard_act(xh, ("batch", None, None) + (None,) * len(gs) + ("heads", None))

        mask = jnp.tril(jnp.ones((lc, lc), bool))
        init = (jnp.zeros((bsz,) + hs + (hd, n), jnp.float32) if ssm_state is None
                else ssm_state.astype(jnp.float32).reshape((bsz,) + hs + (hd, n)))

        mode = resolve_kernel_mode(cfg.use_kernels)
        if mode is not None:
            if grp > 1:
                raise NotImplementedError(
                    f"the ssd_scan kernel has no grouped B/C (ssm_groups={grp})")
            # Pallas intra-chunk path (repro.kernels.ssd_scan): every chunk's
            # masked decay-attention block and chunk-local state run in one
            # kernel launch over a (batch*chunks, heads) grid; only the tiny
            # [B, H, hd, N] inter-chunk recurrence stays in the scan below.
            from repro.kernels.ssd_scan.kernel import ssd_chunk
            cum = jnp.cumsum(logc, axis=2)                  # [B,nc,Lc,H]
            total = cum[:, :, -1, :]                        # [B,nc,H]
            xdt = xh.astype(jnp.float32) * dth[..., None]   # [B,nc,Lc,H,hd]
            gdim = bsz * nc
            y_k, s_k = ssd_chunk(
                ch.reshape(gdim, lc, n), bh.reshape(gdim, lc, n),
                xdt.transpose(0, 1, 3, 2, 4).reshape(gdim, h, lc, hd),
                cum.transpose(0, 1, 3, 2).reshape(gdim, h, lc),
                interpret=mode)
            y_intra = y_k.reshape(bsz, nc, h, lc, hd).transpose(0, 1, 3, 2, 4)
            s_local = s_k.reshape(bsz, nc, h, hd, n)

            def body(carry, inp):
                y_i, s_l, cum_c, ch_c, total_c = inp
                y_inter = jnp.einsum("blh,bln,bhpn->blhp", jnp.exp(cum_c),
                                     ch_c, carry)
                new = jnp.exp(total_c)[:, :, None, None] * carry + s_l
                return new, (y_i + y_inter).astype(x.dtype)

            xs = tuple(jnp.moveaxis(t, 1, 0)
                       for t in (y_intra, s_local, cum, ch, total))
            final_state, y = jax.lax.scan(body, init, xs)
        else:
            # einsum specs name the group axis "G"; with one group it is absent
            ein = lambda spec: spec.replace("G", "g" if gs else "")  # noqa: E731
            mask_ix = (None, slice(None), slice(None)) + (None,) * len(hs)

            def body(carry, inp):
                # carry: inter-chunk state [B,H,hd,N]; one chunk's tensors:
                xh_c, bh_c, ch_c, dth_c, logc_c = inp
                cum = jnp.cumsum(logc_c, axis=1)  # [B,Lc,H]
                total = cum[:, -1, :]  # [B,H]
                xdt = xh_c.astype(jnp.float32) * dth_c[..., None]  # [B,Lc,H,hd]
                # intra-chunk: G[l,m] = C_l . B_m ; M[h,l,m] = exp(cum_l - cum_m),
                # m<=l
                g = jnp.einsum(ein("blGn,bmGn->blGm"), ch_c, bh_c)
                dlog = cum[:, :, None, :] - cum[:, None, :, :]  # [B,Lc(l),Lc(m),H]
                mexp = jnp.where(mask[mask_ix], jnp.exp(dlog), 0.0)
                y_intra = jnp.einsum(ein("blGm,blmGh,bmGhp->blGhp"), g, mexp, xdt)
                # inter-chunk contribution from the carried state
                y_inter = jnp.einsum(ein("blGh,blGn,bGhpn->blGhp"), jnp.exp(cum), ch_c,
                                     carry)
                # chunk-local state + recurrence
                w_local = jnp.exp(total[:, None, :] - cum)  # [B,Lc,H]
                s_local = jnp.einsum(ein("bmGh,bmGhp,bmGn->bGhpn"), w_local, xdt, bh_c)
                decay = jnp.exp(total)[(slice(None),) * total.ndim + (None, None)]
                new = decay * carry + s_local
                return new, (y_intra + y_inter).astype(x.dtype)

            xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xh, bh, ch, dth, logc))
            final_state, y = jax.lax.scan(body, init, xs)
        if gs:
            final_state = final_state.reshape(bsz, h, hd, n)
        y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, h, hd).astype(jnp.float32)
        y = y + xh.reshape(bsz, s, h, hd).astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
        y = y.reshape(bsz, s, din).astype(x.dtype)
    with jax.named_scope("mamba2.out"):
        y = _gated_norm(y, z, p["gate_norm"], cfg.norm_eps, grp)
        out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(x.dtype))
    return out, (new_conv, final_state.astype(jnp.float32))


def ssd_decode_step(p, cfg: ModelConfig, x, conv_state, ssm_state):
    """x: [B, 1, D]; O(1) recurrent update. Returns (y, (conv_state, ssm_state))."""
    if cfg.ssm_groups > 1:
        raise NotImplementedError("ssd_decode_step has no grouped B/C")
    bsz = x.shape[0]
    din, n, h, hd = d_inner(cfg), cfg.ssm_state, num_ssm_heads(cfg), cfg.ssm_head_dim
    proj = jnp.einsum("bsd,dk->bsk", x, p["in_proj"].astype(x.dtype))
    z, xc, b_, c_, dt = _split(cfg, proj)
    conv_in = jnp.concatenate([xc, b_, c_], axis=-1)  # [B,1,C]
    conv_out, new_conv = _conv_act(cfg, p, conv_in, conv_state)
    conv_out = conv_out[:, 0]  # [B, C]
    xc = conv_out[..., :din].reshape(bsz, h, hd)
    b_ = conv_out[..., din : din + n].astype(jnp.float32)
    c_ = conv_out[..., din + n :].astype(jnp.float32)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a[None, :])  # [B,H]

    xdt = xc.astype(jnp.float32) * dt[..., None]  # [B,H,hd]
    new_state = decay[:, :, None, None] * ssm_state + jnp.einsum("bhp,bn->bhpn", xdt, b_)
    y = jnp.einsum("bn,bhpn->bhp", c_, new_state)
    y = y + xc.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(bsz, 1, din).astype(x.dtype)
    y = _gated_norm(y, z, p["gate_norm"], cfg.norm_eps)
    out = jnp.einsum("bsk,kd->bsd", y, p["out_proj"].astype(x.dtype))
    return out, (new_conv, new_state)


def ssd_state_specs(cfg: ModelConfig, batch, layers: int, dtype=jnp.float32):
    din, n, h, hd, w = (d_inner(cfg), cfg.ssm_state, num_ssm_heads(cfg),
                        cfg.ssm_head_dim, cfg.ssm_conv)
    return {
        "conv": jax.ShapeDtypeStruct((layers, batch, w - 1, din + 2 * _bc_width(cfg)),
                                     jnp.bfloat16),
        "ssm": jax.ShapeDtypeStruct((layers, batch, h, hd, n), dtype),
    }


def ssd_state_axes():
    return {
        "conv": ("layers", "batch", "conv", "ffn"),
        "ssm": ("layers", "batch", "heads", None, "state"),
    }


def ssd_init_state(cfg: ModelConfig, batch, layers: int, dtype=jnp.float32):
    s = ssd_state_specs(cfg, batch, layers, dtype)
    return jax.tree_util.tree_map(lambda t: jnp.zeros(t.shape, t.dtype), s)
