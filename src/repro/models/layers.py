"""Shared transformer layers: RMSNorm, RoPE/M-RoPE, GQA attention, gated MLPs.

Attention implementations:
  * ``attend_full``     — materialized scores; smoke tests / short sequences.
  * ``attend_chunked``  — online-softmax scan over KV chunks; compile- and
                          memory-friendly at 32k+ (the XLA path mirroring the
                          Pallas flash kernel in ``repro.kernels.flash_attention``).
  * ``attend_decode``   — one query position against a KV cache.

All are causal-aware via explicit position ids and support GQA (num_kv_heads
< num_heads) by grouping query heads.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import shard_act
from repro.kernels import resolve_kernel_mode
from repro.utils.pspec import spec

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(d):
    return spec((d,), (None,), init="ones")


def rmsnorm(x, w, eps=1e-6, use_kernel=False):
    """RMSNorm with optional Pallas dispatch (``repro.kernels.rmsnorm``).

    ``use_kernel`` follows ``ModelConfig.use_kernels`` — the jnp body below
    is op-for-op the kernel's oracle (``rmsnorm_ref``), so the
    bitwise-neutral mode (use_kernel=True off the TPU) simply runs it.
    """
    mode = resolve_kernel_mode(use_kernel)
    if mode is not None:
        from repro.kernels.rmsnorm.kernel import rmsnorm as rmsnorm_kernel
        return rmsnorm_kernel(x, w, eps=eps, interpret=mode)
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [B, S, H, Dh]; positions: [B, S] int32."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [Dh/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections: tuple):
    """Qwen2-VL M-RoPE. x: [B, S, H, Dh]; positions3: [3, B, S] (t, h, w)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # [Dh/2]
    # Each frequency slot takes its position id from its (t|h|w) section.
    sec = jnp.asarray(
        sum(([i] * s for i, s in enumerate(sections)), []), dtype=jnp.int32
    )  # [Dh/2] in {0,1,2}
    # gather section-wise positions: [B, S, Dh/2]
    pos = positions3.astype(jnp.float32)[sec, :, :]  # [Dh/2, B, S]
    pos = jnp.moveaxis(pos, 0, -1)  # [B, S, Dh/2]
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention param specs
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, layers: Optional[int] = None) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    L = () if layers is None else (layers,)
    lax_ = () if layers is None else ("layers",)

    def s(shape, axes, **kw):
        return spec(L + tuple(shape), lax_ + tuple(axes), **kw)

    specs = {
        "wq": s((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": s((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": s((d, kv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": s((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = s((h, dh), ("heads", "head_dim"), init="zeros")
        specs["bk"] = s((kv, dh), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = s((kv, dh), ("kv_heads", "head_dim"), init="zeros")
    return specs


def qkv_proj(p, cfg: ModelConfig, x, positions, theta=None, cross_kv=None):
    """x: [B, S, D] -> q [B, S, H, Dh], k/v [B, Skv, KV, Dh] (RoPE applied)."""
    theta = cfg.rope_theta if theta is None else theta
    src = x if cross_kv is None else cross_kv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if positions is not None:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, theta, cfg.mrope_sections)
            if cross_kv is None:
                k = apply_mrope(k, positions, theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, theta)
            if cross_kv is None:
                k = apply_rope(k, positions, theta)
    q = shard_act(q, ("batch", "seq", "heads", "head_dim"))
    k = shard_act(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard_act(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def out_proj(p, attn_out):
    """attn_out: [B, S, H, Dh] -> [B, S, D]."""
    return jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"].astype(attn_out.dtype))


# ---------------------------------------------------------------------------
# Attention math (GQA-aware)
# ---------------------------------------------------------------------------


def _group_q(q, num_kv: int):
    """[B, S, H, Dh] -> [B, S, KV, G, Dh]."""
    b, s, h, dh = q.shape
    return q.reshape(b, s, num_kv, h // num_kv, dh)


def attend_full(q, k, v, q_pos, k_pos, causal: bool, scale: Optional[float] = None):
    """Materialized attention. q: [B,Sq,H,Dh], k/v: [B,Sk,KV,Dh]."""
    kvh = k.shape[2]
    qg = _group_q(q, kvh)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    if causal:
        mask = q_pos[:, None, None, :, None] >= k_pos[:, None, None, None, :]
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", w, v.astype(jnp.float32))
    b, sq, h, g, dh = out.shape
    return out.reshape(b, sq, h * g, dh).astype(q.dtype)


def attend_chunked(q, k, v, q_pos, k_pos, causal: bool, chunk: int = 1024,
                   scale: Optional[float] = None, prob_dtype=None):
    """Online-softmax attention, scanning KV chunks (flash-style, XLA path).

    Memory high-water ~ [B, H, Sq, chunk] instead of [B, H, Sq, Sk].
    prob_dtype=bf16 (§Perf): cast the probability tensor before the PV matmul
    — halves the dominant HBM traffic of the XLA path; max/denominator stay
    f32 so the softmax remains stable (matches flash-kernel numerics).
    """
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=jnp.iinfo(jnp.int32).max)
        sk += pad
    n_chunks = sk // chunk
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = _group_q(q, kvh).astype(jnp.float32) * scale  # [B,Sq,KV,G,Dh]

    kc = k.reshape(b, n_chunks, chunk, kvh, dh)
    vc = v.reshape(b, n_chunks, chunk, kvh, dh)
    pc = k_pos.reshape(b, n_chunks, chunk)

    def body(carry, inp):
        m, l, acc = carry  # [B,KV,G,Sq], [B,KV,G,Sq], [B,Sq,KV,G,Dh]
        kj, vj, pj = inp  # [B,chunk,KV,Dh], ..., [B,chunk]
        s = jnp.einsum("bqhgk,bchk->bhgqc", qg, kj.astype(jnp.float32))
        valid = pj[:, None, None, None, :] <= jnp.iinfo(jnp.int32).max - 1
        if causal:
            valid = valid & (q_pos[:, None, None, :, None] >= pj[:, None, None, None, :])
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        if prob_dtype is not None:
            pv = jnp.einsum("bhgqc,bchk->bqhgk", p.astype(prob_dtype),
                            vj.astype(prob_dtype)).astype(jnp.float32)
        else:
            pv = jnp.einsum("bhgqc,bchk->bqhgk", p, vj.astype(jnp.float32))
        acc_new = acc * jnp.moveaxis(corr, 3, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    g = h // kvh
    init = (
        jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, g, sq), jnp.float32),
        jnp.zeros((b, sq, kvh, g, dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        body, init, (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.moveaxis(pc, 1, 0))
    )
    l = jnp.maximum(l, 1e-30)
    out = acc / jnp.moveaxis(l, 3, 1)[..., None]
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def attend_decode(q, k_cache, v_cache, cur_len, scale: Optional[float] = None):
    """Decode: q [B,1,H,Dh] against cache [B,Smax,KV,Dh]; cur_len [B] int32.

    The cache operands stay in their storage dtype with f32 accumulation
    (``preferred_element_type``) — an ``astype(f32)`` here would materialize
    a full f32 copy of the cache shard every step and break in-place
    dynamic-update-slice aliasing (measured 2x step traffic, §Perf cell B).
    """
    b, _, h, dh = q.shape
    smax, kvh = k_cache.shape[1], k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qg = _group_q(q, kvh).astype(k_cache.dtype) * jnp.asarray(
        scale, k_cache.dtype)  # [B,1,KV,G,Dh]
    s = jnp.einsum("bqhgk,bshk->bhgqs", qg, k_cache,
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(smax, dtype=jnp.int32)
    mask = pos[None, None, None, None, :] < cur_len[:, None, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqs,bshk->bqhgk", w.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def flash_kernel_compatible(q, k) -> bool:
    """Whether the Pallas flash kernel's tiling accepts these shapes:
    Sq/Sk must each be a multiple of 128 or shorter than that (the kernel
    then picks its tiles from 512 / 256 / 128). The kernel additionally
    assumes positions are 0-based aranges (it derives the causal mask from
    tile indices) — true for every backbone path that enables kernels."""
    sq, sk = q.shape[1], k.shape[1]
    return sq % min(128, sq) == 0 and sk % min(128, sk) == 0


def attend(q, k, v, q_pos, k_pos, causal: bool, impl: str = "auto",
           chunk: int = 1024, scale: Optional[float] = None,
           use_kernel=False):
    """GQA attention with optional Pallas flash-kernel dispatch.

    ``use_kernel`` follows ``ModelConfig.use_kernels``. The kernel path
    requires 0-based arange positions (what ``forward_hidden`` passes) and
    tile-divisible sequence lengths; a kernel request the flash kernel
    cannot serve raises instead of quietly running the jnp path.
    Kernel-vs-jnp parity is tolerance-level, not bitwise: the flash kernel
    feeds its dots the operands' dtype (rounding the probabilities to it
    before the PV dot) and its online softmax reassociates the reduction
    (see kernels/README.md).
    """
    mode = resolve_kernel_mode(use_kernel)
    if mode is not None:
        if not flash_kernel_compatible(q, k):
            raise ValueError(
                f"flash attention kernel requested but it cannot tile q "
                f"{tuple(q.shape)} / k {tuple(k.shape)}: sequence lengths "
                f"must divide into tiles of min(128, len)")
        from repro.kernels.flash_attention.kernel import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=mode)
    if impl == "auto":
        impl = "chunked" if k.shape[1] > 2048 else "full"
    if impl == "full":
        return attend_full(q, k, v, q_pos, k_pos, causal, scale)
    if impl == "chunked":
        return attend_chunked(q, k, v, q_pos, k_pos, causal, chunk, scale)
    if impl == "chunked_bf16p":
        return attend_chunked(q, k, v, q_pos, k_pos, causal, chunk, scale,
                              prob_dtype=jnp.bfloat16)
    raise ValueError(f"unknown attention impl {impl}")


# ---------------------------------------------------------------------------
# Gated MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None, layers: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    L = () if layers is None else (layers,)
    lax_ = () if layers is None else ("layers",)

    def s(shape, axes):
        return spec(L + tuple(shape), lax_ + tuple(axes))

    return {
        "w_gate": s((d, f), ("embed", "ffn")),
        "w_up": s((d, f), ("embed", "ffn")),
        "w_down": s((f, d), ("ffn", "embed")),
    }


def mlp(p, cfg: ModelConfig, x):
    act = jax.nn.gelu if cfg.act == "geglu" else jax.nn.silu
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = act(g) * u
    h = shard_act(h, ("batch", "seq", "ffn"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_specs(cfg: ModelConfig):
    specs = {"tok": spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed",
                         scale=1.0 / math.sqrt(cfg.d_model))}
    if not cfg.tie_embeddings:
        specs["unembed"] = spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return specs


def embed(p, cfg: ModelConfig, tokens):
    e = jnp.take(p["tok"], tokens, axis=0).astype(_dt(cfg))
    if cfg.emb_scale:
        e = e * jnp.asarray(math.sqrt(cfg.d_model), e.dtype)
    return e


def unembed(p, cfg: ModelConfig, h):
    w = p.get("unembed")
    if w is None:
        w = p["tok"].T
    return jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)
