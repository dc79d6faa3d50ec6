"""Nemotron-H backbone (NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type:
nemotron_h``): one pre-norm layer per letter of ``layer_pattern``,
``h <- h + mixer(RMSNorm(h))``, then a final RMSNorm. The mixers:

* ``M``, Mamba2: ``[z | xBC | dt] = x W_in``; ``xBC <- SiLU(causal
  depthwise conv(xBC) + b_conv)``; ``ssm_heads`` heads of ``ssm_head_dim``,
  B and C in ``ssm_groups`` groups of ``ssm_state``, head h reading group
  ``h // (heads / groups)``; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; ``s_t = exp(dt A) s_{t-1} + dt x_t B_t^T``,
  ``y_t = s_t C_t + D x_t``, unrolled over blocks of tokens as
  ``reference.mamba2`` does; ``y <- RMSNorm(y SiLU(z))`` over each group's
  channels, times its weight; out-projection.
* ``E``, MoE: float32 router scores ``sigmoid(x W_r)``; the top
  ``experts_per_tok`` (the score correction bias is zero in the
  configuration: its ``assumed``); weights ``routed_scale * s / sum(s)``;
  experts ``W_down relu(W_up x)^2``, computed for the routed (token,
  expert) pairs only, sorted by expert and put back by a scatter-add; plus
  the ungated shared expert of the same form.
* ``*``, attention: ``num_heads`` query and ``num_kv_heads`` KV heads
  (query head h reads KV head ``h // (num_heads / num_kv_heads)``), no
  positional encoding, scale ``1/sqrt(head_dim)``, causal as the program
  runs recurrent backbones.

The expert matmuls are ``jax.lax.ragged_dot`` on float32 operands at
``HIGHEST`` (a dense form over every expert is ``num_experts /
experts_per_tok`` times the work); their operands are rounded as ``mm``
rounds its own (:func:`_operand`), so the float8 control reaches them too.
Each weight is cast to float32 where it is read. The layers' weights are a
list of dicts (``blocks``), not stacks; the expert stacks sit under
``experts``, their one leading axis the experts.
"""
from __future__ import annotations

from flops import _attn_block
from reference import SCAN_BLOCK, _attention, _rms, _silu

ATTENTION_CAUSAL = True
STACKED = {"experts": 1}  # [num_experts, ...]


def _f32(a):
    import jax.numpy as jnp
    return a.astype(jnp.float32)


def _operand(mm, a):
    """``a`` as ``mm`` has its operands: float32, or float8-rounded with a
    per-tensor scale in the control (``mm`` by a unit scalar)."""
    import jax.numpy as jnp
    return mm("...,->...", a, jnp.ones((), jnp.float32))


def _relu2(x):
    import jax.numpy as jnp
    return jnp.square(jnp.maximum(x, 0.0))


def mamba2(p, m, x, mm):
    import jax
    import jax.numpy as jnp
    b, s, _ = x.shape
    n, hd, w = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    groups, heads = m["ssm_groups"], m["ssm_heads"]
    per = heads // groups
    din, gn = heads * hd, groups * n
    proj = mm("bsd,dk->bsk", x, p["in_proj"])
    z = proj[..., :din]
    conv_in = proj[..., din: 2 * din + 2 * gn]
    dt = proj[..., 2 * din + 2 * gn:]
    padded = jnp.concatenate(
        [jnp.zeros((b, w - 1, conv_in.shape[-1]), conv_in.dtype), conv_in], 1)
    conv_w = _f32(p["conv_w"])
    conv = _silu(sum(padded[:, i: i + s] * conv_w[i] for i in range(w))
                 + _f32(p["conv_b"]))
    xc = conv[..., :din].reshape(b, s, groups, per, hd)
    bmat = conv[..., din: din + gn].reshape(b, s, groups, n)
    cmat = conv[..., din + gn:].reshape(b, s, groups, n)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))          # [B, S, H]
    log_a = (dt * -jnp.exp(_f32(p["a_log"]))).reshape(b, s, groups, per)
    dt = dt.reshape(b, s, groups, per)
    nc = s // SCAN_BLOCK
    blk = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape((b, nc, SCAN_BLOCK) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((SCAN_BLOCK, SCAN_BLOCK), bool))

    def block(state, inp):
        # state [B, G, P, hd, N]: per group g, its heads p; within the block
        #   y_l = C_l . (a_1..a_l) s_in + sum_{m<=l} (a_{m+1}..a_l)
        #         (C_l . B_m) dt_m x_m
        #   s_out = (a_1..a_L) s_in + sum_m (a_{m+1}..a_L) dt_m x_m B_m^T
        x_b, b_b, c_b, dt_b, la_b = inp
        cum = jnp.cumsum(la_b, axis=1)                    # [B, L, G, P]
        xdt = x_b * dt_b[..., None]                       # [B, L, G, P, hd]
        decay = jnp.where(causal[None, :, :, None, None],
                          jnp.exp(cum[:, :, None] - cum[:, None]),
                          0.0)                            # [B, L, M, G, P]
        cb = mm("blgn,bmgn->blmg", c_b, b_b)
        y = mm("blmgp,bmgpe->blgpe", cb[..., None] * decay, xdt)
        y = y + mm("blgn,bgpen->blgpe", c_b, state) * jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[:, -1:] - cum)                 # [B, L, G, P]
        state = (jnp.exp(cum[:, -1])[..., None, None] * state
                 + mm("bmgpe,bmgn->bgpen", xdt * tail[..., None], b_b))
        return state, y

    _, y = jax.lax.scan(block, jnp.zeros((b, groups, per, hd, n), jnp.float32),
                        (blk(xc), blk(bmat), blk(cmat), blk(dt), blk(log_a)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, groups, per, hd)
    y = y + xc * _f32(p["d_skip"]).reshape(groups, per)[..., None]
    y = y.reshape(b, s, groups, din // groups) * _silu(
        z.reshape(b, s, groups, din // groups))
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + m["norm_eps"])
    y = y.reshape(b, s, din) * _f32(p["gate_norm"])
    return mm("bsk,kd->bsd", y, p["out_proj"])


def moe(p, m, x, mm):
    import jax
    import jax.numpy as jnp
    b, s, d = x.shape
    e, k = m["num_experts"], m["experts_per_tok"]
    hi = jax.lax.Precision.HIGHEST
    xf = x.reshape(b * s, d)
    scores = jax.nn.sigmoid(mm("td,de->te", xf, p["router"]))
    top, idx = jax.lax.top_k(scores, k)                   # ties: lower index
    gate = top / jnp.sum(top, -1, keepdims=True) * m["routed_scale"]
    expert = idx.reshape(-1)                              # token-major pairs
    order = jnp.argsort(expert, stable=True)
    token = order // k
    sizes = jnp.bincount(expert, length=e)
    ex = p["experts"]
    hid = _relu2(jax.lax.ragged_dot(_operand(mm, xf[token]),
                                    _operand(mm, ex["w_up"]), sizes,
                                    precision=hi))
    rows = jax.lax.ragged_dot(_operand(mm, hid), _operand(mm, ex["w_down"]),
                              sizes, precision=hi)
    out = jnp.zeros((b * s, d), jnp.float32).at[token].add(
        rows * gate.reshape(-1)[order][:, None])
    sh = p["shared"]
    out = out + mm("tf,fd->td", _relu2(mm("td,df->tf", xf, sh["w_up"])),
                   sh["w_down"])
    return out.reshape(b, s, d)


def attention(p, m, x, mm):
    import jax.numpy as jnp
    rep = m["num_heads"] // m["num_kv_heads"]
    q = mm("bsd,dhk->bshk", x, p["wq"])
    k = jnp.repeat(mm("bsd,dhk->bshk", x, p["wk"]), rep, axis=2)
    v = jnp.repeat(mm("bsd,dhk->bshk", x, p["wv"]), rep, axis=2)
    return mm("bshk,hkd->bsd", _attention(q, k, v, ATTENTION_CAUSAL, mm),
              p["wo"])


MIXERS = {"M": ("mamba", mamba2), "E": ("moe", moe), "*": ("attn", attention)}


def reference(p, m, h, mm):
    eps = m["norm_eps"]
    for kind, lp in zip(m["layer_pattern"], p["blocks"]):
        key, mixer = MIXERS[kind]
        h = h + mixer(lp[key], m, _rms(h, lp["ln"], eps), mm)
    return _rms(h, p["final_norm"], eps)


def mamba2_flops(s: int, m: dict) -> float:
    d, n, heads = m["d_model"], m["ssm_state"], m["ssm_heads"]
    din, gn = heads * m["ssm_head_dim"], m["ssm_groups"] * m["ssm_state"]
    in_proj = 2 * s * d * (2 * din + 2 * gn + heads)
    conv = 2 * s * (din + 2 * gn) * m["ssm_conv"]
    scan = 4 * s * din * n  # decay*state + x dt B^T, and C . state
    return in_proj + conv + scan + 2 * s * din * d


def moe_flops(s: int, m: dict) -> float:
    """The active path: router, ``experts_per_tok`` routed experts and the
    shared expert, two matmuls each."""
    d = m["d_model"]
    return (2 * s * d * m["num_experts"]
            + m["experts_per_tok"] * 4 * s * d * m["d_ff"]
            + 4 * s * d * m["shared_d_ff"])


def flops(s: int, m: dict) -> float:
    per = {"M": mamba2_flops(s, m), "E": moe_flops(s, m),
           "*": _attn_block(s, m, causal=ATTENTION_CAUSAL)}
    return sum(per[kind] for kind in m["layer_pattern"])


def expert_call(lanes: int, s: int, m: dict, itemsize: int = 2):
    """(FLOPs, bytes) of one grouped expert matmul over ``lanes`` latents of
    ``s`` tokens: every token's ``experts_per_tok`` rows, whatever the
    routing (no row is dropped); bytes are the rows in, the rows out and
    the expert stack, each once. Up (d -> f) and down (f -> d) are alike."""
    rows = lanes * s * m["experts_per_tok"]
    d, f = m["d_model"], m["d_ff"]
    return (2 * rows * d * f,
            itemsize * (rows * d + rows * f + m["num_experts"] * d * f))
