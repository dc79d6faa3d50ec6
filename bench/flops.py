"""Operations and bytes, worked out from shapes.

Model FLOPs count the matrix multiplications a forward pass needs (2 per
multiply-add), as model FLOP utilization conventionally does; norms, RoPE,
softmax and other elementwise work are left out, so a utilization from
these counts is a lower bound of what the chip did. Causal attention counts
its lower triangle only.

The kernel functions follow ``benchmarks/kernels.py``'s arithmetic: bytes
are each operand and output read or written once (what a pipelined
``pallas_call`` moves), FLOPs are the kernel's useful work.
"""
from __future__ import annotations

import backbones


def _attention(s: int, heads: int, head_dim: int, causal: bool) -> float:
    pairs = s * (s + 1) / 2 if causal else s * s
    return 2 * 2 * pairs * heads * head_dim  # QK^T and PV


def _mlp(s: int, d: int, f: int) -> float:
    return 3 * 2 * s * d * f  # gate, up, down


def _attn_block(s: int, m: dict, causal: bool) -> float:
    d, h, dh = m["d_model"], m["num_heads"], m["head_dim"]
    kv = m["num_kv_heads"]
    proj = 2 * s * d * (h + 2 * kv) * dh + 2 * s * h * dh * d
    return proj + _attention(s, h, dh, causal)


def mamba2_layer(s: int, m: dict) -> float:
    d, n = m["d_model"], m["ssm_state"]
    din = m["ssm_expand"] * d
    heads = din // m["ssm_head_dim"]
    in_proj = 2 * s * d * (2 * din + 2 * n + heads)
    conv = 2 * s * (din + 2 * n) * m["ssm_conv"]
    # the state recurrence: per token, decay*state + x dt B^T (2 hd n per
    # head) and the read-out C . state (2 hd n per head)
    scan = 4 * s * din * n
    out_proj = 2 * s * din * d
    return in_proj + conv + scan + out_proj


def drift_forward(m: dict, seq: int, latent_dim: int) -> float:
    """FLOPs of one drift evaluation of one latent of ``seq`` tokens: the
    wrapper's in/out projections and time MLP plus the backbone, whose
    count is its family's (``bench/backbones/<family>.py``)."""
    d = m["d_model"]
    wrapper = 2 * seq * latent_dim * d * 2 + 2 * 256 * d + 2 * d * d
    return wrapper + backbones.load(m["family"]).flops(seq, m)


def flash_attention_call(batch: int, heads: int, sq: int, sk: int,
                         head_dim: int, itemsize: int, causal: bool):
    """(FLOPs, bytes) of one flash-attention kernel call: q, k, v read and
    o written once; FLOPs as in ``benchmarks/kernels.py`` (the two matmuls,
    five softmax operations per score, the q pre-scale)."""
    pairs = sq * (sk + 1) / 2 if causal else sq * sk
    flops = (4 * batch * heads * pairs * head_dim
             + 5 * batch * heads * pairs + batch * sq * heads * head_dim)
    nbytes = itemsize * batch * head_dim * heads * (2 * sq + 2 * sk)
    return flops, nbytes


def rectify_accept_call(lanes: int, m: int, itemsize: int = 4):
    """(FLOPs, bytes) of one fused step+rectify+accept kernel call over
    ``lanes`` (slots x cores) latents of ``m`` elements: seven latent
    operands read, one written; 12 FLOPs per element (update 7, the two
    accept sums 5), as in ``benchmarks/kernels.py``."""
    return 12 * lanes * m, 8 * itemsize * lanes * m
