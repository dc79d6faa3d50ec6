"""Pallas TPU flash attention (forward): online softmax over KV tiles.

Tiling: grid = (B, H, Sq/BQ); each program holds one (BQ, Dh) query tile and
streams the head's whole KV through an inner loop of BK-row tiles. Tiles
default to the largest of 512 / 256 / 128 that divides the length (the length
itself below 128): at 4096 tokens a 512 x 512 score tile gives each loop step
dots large enough to keep the MXU busy, where 128 x 128 steps left it waiting
on each step's fixed cost (74.6 -> 13.2 ms a DiT call on a v5e, PERF.md).

MXU: both dots take the operands in their own dtype with f32 accumulation.
QK^T is exact on bf16 products (the softmax scale multiplies the f32 logits,
not q); the probabilities are rounded to V's dtype for the PV dot, as every
flash kernel does. f32 inputs keep f32 dots.

VPU: the running max and sum are kept lane-replicated as [BQ, 128] f32, the
layout the score tile's row reductions produce, so nothing is relaid out per
KV tile. Scores, max, sum and accumulator live in f32 VMEM scratch, which the
launch description lists. A non-causal loop has a static trip count and is
unrolled, so one tile's softmax overlaps the next tile's dots.

Causal masking skips *whole* KV tiles past the diagonal (the triangle-skip the
XLA chunked path cannot express — ~2x FLOP reduction at long seq) and masks
only the tiles the diagonal crosses. GQA maps query head h to KV head h // g.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta import (BlockMeta, KernelLaunch, ScratchMeta,
                                block_specs, vmem_bytes)

NEG_INF = -1e30
LANES = 128
TILES = (512, 256, 128)
SCOPED_VMEM_BYTES = 16 << 20  # Mosaic's default scoped-VMEM limit on the v5e


def pick_tile(n: int) -> int:
    """Default tile along a sequence of length ``n``: the largest of
    ``TILES`` that divides it, or ``n`` itself when it is under 128."""
    if n < LANES:
        return n
    for t in TILES:
        if n % t == 0:
            return t
    raise ValueError(f"flash attention cannot tile a length of {n}: it must "
                     f"be a multiple of {LANES} or shorter than that")


def tiles(sq: int, sk: int, bq: int = None, bk: int = None):
    """The (bq, bk) a call runs: explicit tiles capped at the lengths,
    else :func:`pick_tile` of each."""
    return (min(bq, sq) if bq else pick_tile(sq),
            min(bk, sk) if bk else pick_tile(sk))


def launch_meta(b: int, sq: int, h: int, dh: int, sk: int, kvh: int,
                bq: int = None, bk: int = None, dtype="float32"
                ) -> KernelLaunch:
    """Static launch description (operands in [B, H, S, Dh] kernel layout).

    Each program owns one (batch, head, query-tile) output block and streams
    the whole per-head KV through VMEM; GQA maps query head ``ih`` to KV head
    ``ih // g``. ``bq``/``bk`` default to :func:`pick_tile`. ``bk`` only
    shapes the in-kernel loop — the BlockSpec working set is the full
    [Sk, Dh] KV — and its f32 score tile, with the lane-replicated running
    max and sum and the accumulator, are the program's VMEM scratch.
    """
    bq, bk = tiles(sq, sk, bq, bk)
    g = h // kvh
    grid = (b, h, sq // bq)
    dtype = str(jnp.dtype(dtype))
    q_map = lambda ib, ih, iq: (ib, ih, iq, 0)
    kv_map = lambda ib, ih, iq, g=g: (ib, ih // g, 0, 0)
    inputs = (
        BlockMeta("q", (None, None, bq, dh), q_map, (b, h, sq, dh), dtype),
        BlockMeta("k", (None, None, sk, dh), kv_map, (b, kvh, sk, dh), dtype),
        BlockMeta("v", (None, None, sk, dh), kv_map, (b, kvh, sk, dh), dtype),
    )
    out = BlockMeta("o", (None, None, bq, dh), q_map, (b, h, sq, dh), dtype)
    scratch = (
        ScratchMeta("scores", (bq, bk), "float32"),
        ScratchMeta("m", (bq, LANES), "float32"),
        ScratchMeta("l", (bq, LANES), "float32"),
        ScratchMeta("acc", (bq, dh), "float32"),
    )
    return KernelLaunch("flash_attention.flash_attention", grid, inputs,
                        (out,), scratch)


def _lanes(x, n):
    """A lane-replicated [R, 128] statistic laid against an [R, n] tile."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, s_ref, m_ref, l_ref, acc_ref, *,
                  bq, bk, sk, causal, scale):
    # q_ref: [BQ, Dh]; k_ref/v_ref: [Sk, Dh] (whole KV stream for this head);
    # f32 scratch: s_ref [BQ, BK] scores, m_ref/l_ref [BQ, 128], acc_ref [BQ, Dh]
    qi = pl.program_id(2)
    q = q_ref[...]
    dh = q.shape[-1]
    n_kv = sk // bk

    def body(kv_i, carry, masked):
        kt = k_ref[pl.ds(kv_i * bk, bk), :]
        vt = v_ref[pl.ds(kv_i * bk, bk), :]
        s = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        s_ref[...] = s
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s_ref[...], axis=-1, keepdims=True))
        p = jnp.exp(s_ref[...] - _lanes(m_new, bk))
        corr = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.dot(p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _lanes(corr, dh) + pv
        return carry

    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    unmasked = functools.partial(body, masked=False)
    if causal:
        # tiles wholly at or below the diagonal run unmasked; those the
        # diagonal crosses are masked; those past it are never streamed
        n_full = jnp.minimum((qi * bq + 1) // bk, n_kv)
        last = jnp.minimum(((qi + 1) * bq + bk - 1) // bk, n_kv)
        jax.lax.fori_loop(0, n_full, unmasked, 0)
        jax.lax.fori_loop(n_full, last, functools.partial(body, masked=True), 0)
    else:
        # a static trip count: unrolled, the tiles' dots and softmax overlap
        jax.lax.fori_loop(0, n_kv, unmasked, 0, unroll=True)
    l = _lanes(jnp.maximum(l_ref[...], 1e-30), dh)
    o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret", "scale"))
def flash_attention(q, k, v, causal: bool = True, bq: int = None,
                    bk: int = None, scale=None, interpret: bool = True):
    """q: [B, Sq, H, Dh]; k/v: [B, Sk, KV, Dh] -> [B, Sq, H, Dh].

    ``bq``/``bk`` default to the shape-chosen tiles of :func:`pick_tile`.
    """
    b, sq, h, dh = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    bq, bk = tiles(sq, sk, bq, bk)
    meta = launch_meta(b, sq, h, dh, sk, kvh, bq, bk, dtype=q.dtype)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    # the unrolled loop's temporaries (exponent tile, KV tiles) need up to
    # the static footprint again: f32 operands at 4096 tokens pass 16 MiB
    need = 2 * vmem_bytes(meta)
    params = (pltpu.CompilerParams(vmem_limit_bytes=need)
              if need > SCOPED_VMEM_BYTES else None)

    qt = q.transpose(0, 2, 1, 3)  # [B, H, Sq, Dh]
    kt = k.transpose(0, 2, 1, 3)  # [B, KV, Sk, Dh]
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, sk=sk, causal=causal,
                          scale=scale),
        grid=meta.grid,
        in_specs=block_specs(meta.inputs),
        out_specs=block_specs(meta.outputs)[0],
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM(m.shape, m.dtype) for m in meta.scratch],
        compiler_params=params,
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
