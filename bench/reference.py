"""Plain float32 reference: the drift wrapper, the backbones' shared pieces
and CHORDS.

Written from the published descriptions and the configuration file's
numbers, in straightforward ``jax.numpy`` at ``highest`` matmul precision.
Where a configuration departs from its published source (its file's
``assumed``), the reference models the configuration, so it checks the
network that is timed. It imports nothing of the program: weights come in
as a pytree (drawn by ``bench/weights.py``) in their stored dtype and are
cast to float32 where they are read, hyper-parameters from
``bench/configs/<name>.json``.

* Drift (rectified flow, velocity prediction): in-projection of the latent,
  sinusoidal time embedding through a two-layer SiLU MLP added to every
  token, the backbone, RMSNorm, out-projection.
* The backbone: ``bench/backbones/<family>.py`` for the configuration's
  ``model.family``, built from the pieces here: RoPE multi-head attention,
  the SwiGLU MLP, RMSNorm, and Mamba2 layers (in-projection to z, x, B, C,
  dt; causal depthwise convolution and SiLU over x, B, C; the selective
  state recurrence ``s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T``,
  ``y_t = s_t C_t + D x_t``, unrolled over blocks of 64 tokens; SiLU(z)
  gate, RMSNorm, out-projection).
* CHORDS (paper Algorithm 1): K cores start at the init sequence; core k
  jumps ``k`` times along it, then takes unit Euler steps; whenever core
  k-1 stands where core k last took its snapshot, core k is rectified by
  ``dt * (f_{k-1} - f_snap) + x_{k-1} - x_snap``. Outputs stream as cores
  reach t=1; an output is accepted when it agrees with the previous one to
  ``rtol`` (relative L2), and core 0's output (the sequential solve) is
  always accepted.

``quant="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 with a per-tensor scale, the step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import List, NamedTuple, Optional

import numpy as np

import backbones

FP8_MAX = 448.0  # largest finite float8_e4m3fn
# tokens per block of the state recurrence: the recurrence is exact at any
# block length; blocks of 64 turn 4096 sequential steps into 64
SCAN_BLOCK = 64


def make_mm(quant: Optional[str]):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def f32(x):
        return x.astype(jnp.float32)

    if quant is None:
        return lambda spec, a, b: jnp.einsum(spec, f32(a), f32(b),
                                             precision=hi)
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")

    def q(x):
        x = f32(x)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale

    return lambda spec, a, b: jnp.einsum(spec, q(a), q(b), precision=hi)


def _rms(x, w, eps):
    import jax.numpy as jnp
    return (x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
            * w.astype(jnp.float32))


def _silu(x):
    import jax
    return x * jax.nn.sigmoid(x)


def _rope(x, theta):
    """x: [B, S, H, Dh]; rotates the two halves of the head dim."""
    import jax.numpy as jnp
    s, dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, causal, mm):
    """Softmax attention, one head at a time. q/k/v: [B, S, H, Dh]."""
    import jax
    import jax.numpy as jnp
    s, dh = q.shape[1], q.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else None

    def head(qkv):
        qh, kh, vh = qkv  # [B, S, Dh]
        sc = mm("bqd,bkd->bqk", qh, kh) * scale
        if causal:
            sc = jnp.where(mask, sc, -jnp.inf)
        return mm("bqk,bkd->bqd", jax.nn.softmax(sc, -1), vh)

    heads = jax.lax.map(head, tuple(a.transpose(2, 0, 1, 3)
                                    for a in (q, k, v)))
    return heads.transpose(1, 2, 0, 3)


def _attn(p, m, x, causal, mm):
    q = _rope(mm("bsd,dhk->bshk", x, p["wq"]), m["rope_theta"])
    k = _rope(mm("bsd,dhk->bshk", x, p["wk"]), m["rope_theta"])
    v = mm("bsd,dhk->bshk", x, p["wv"])
    return mm("bshk,hkd->bsd", _attention(q, k, v, causal, mm), p["wo"])


def _mlp(p, x, mm):
    g = mm("bsd,df->bsf", x, p["w_gate"])
    u = mm("bsd,df->bsf", x, p["w_up"])
    return mm("bsf,fd->bsd", _silu(g) * u, p["w_down"])


def _layer(tree, i):
    """Layer ``i`` of a stacked subtree, in float32."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: a[i].astype(jnp.float32), tree)


def mamba2(p, m, x, mm):
    import jax
    import jax.numpy as jnp
    b, s, _ = x.shape
    n, hd, w = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    din = m["ssm_expand"] * m["d_model"]
    heads = din // hd
    proj = mm("bsd,dk->bsk", x, p["in_proj"])
    z = proj[..., :din]
    conv_in = proj[..., din: 2 * din + 2 * n]
    dt = proj[..., 2 * din + 2 * n:]
    padded = jnp.concatenate(
        [jnp.zeros((b, w - 1, conv_in.shape[-1]), conv_in.dtype), conv_in], 1)
    conv = _silu(sum(padded[:, i: i + s] * p["conv_w"][i] for i in range(w)))
    xc = conv[..., :din].reshape(b, s, heads, hd)
    bmat, cmat = conv[..., din: din + n], conv[..., din + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])            # [B, S, H]
    log_a = dt * -jnp.exp(p["a_log"])                  # [B, S, H], <= 0
    nc = s // SCAN_BLOCK
    blk = lambda a: jnp.moveaxis(
        a.reshape((b, nc, SCAN_BLOCK) + a.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((SCAN_BLOCK, SCAN_BLOCK), bool))

    def block(state, inp):
        # the recurrence unrolled over one block of tokens l, m:
        #   y_l = C_l . (a_1..a_l) s_in + sum_{m<=l} (a_{m+1}..a_l)
        #         (C_l . B_m) dt_m x_m
        #   s_out = (a_1..a_L) s_in + sum_m (a_{m+1}..a_L) dt_m x_m B_m^T
        x_b, b_b, c_b, dt_b, la_b = inp
        cum = jnp.cumsum(la_b, axis=1)                 # [B, L, H]
        xdt = x_b * dt_b[..., None]                    # [B, L, H, P]
        decay = jnp.where(causal[None, :, :, None],
                          jnp.exp(cum[:, :, None, :] - cum[:, None, :, :]),
                          0.0)                         # [B, L, M, H]
        cb = mm("bln,bmn->blm", c_b, b_b)
        y = mm("blmh,bmhp->blhp", cb[..., None] * decay, xdt)
        y = y + mm("bln,bhpn->blhp", c_b, state) * jnp.exp(cum)[..., None]
        tail = jnp.exp(cum[:, -1:, :] - cum)           # [B, L, H]
        state = (jnp.exp(cum[:, -1, :])[:, :, None, None] * state
                 + mm("bmhp,bmn->bhpn", xdt * tail[..., None], b_b))
        return state, y

    _, y = jax.lax.scan(block, jnp.zeros((b, heads, hd, n), jnp.float32),
                        (blk(xc), blk(bmat), blk(cmat), blk(dt), blk(log_a)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, heads, hd)
    y = y + xc * p["d_skip"][None, None, :, None]
    y = _rms(y.reshape(b, s, din) * _silu(z), p["gate_norm"], m["norm_eps"])
    return mm("bsk,kd->bsd", y, p["out_proj"])


def time_embedding(t, dim=256, max_period=1e4):
    import jax.numpy as jnp
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = (t * 1000.0)[:, None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def drift(p, m, x, t, mm):
    """x: [B, S, L] float32, t: [B]. Returns the velocity [B, S, L]."""
    h = mm("bsl,ld->bsd", x, p["in_proj"])
    te = _silu(mm("bk,kd->bd", time_embedding(t), p["t_mlp1"]))
    h = h + mm("bd,de->be", te, p["t_mlp2"])[:, None, :]
    h = backbones.load(m["family"]).reference(p["backbone"], m, h, mm)
    h = _rms(h, p["out_norm"], m["norm_eps"])
    return mm("bsd,dl->bsl", h, p["out_proj"])


def make_drift(params, model: dict, quant: Optional[str] = None):
    """``f(x [K, *latent], t [K]) -> [K, *latent]``; each core's latent is
    one batch row. ``params`` go into the jitted function as they are
    stored and are cast to float32 where each is read, so no float32 copy
    of the tree is made (``f.args[0] is params``)."""
    import jax

    mm = make_mm(quant)

    @jax.jit
    def f(p, x, t):
        k = x.shape[0]
        flat = x.reshape((k,) + x.shape[-2:])
        return drift(p, model, flat, t, mm).reshape(x.shape)

    return functools.partial(f, params)


class Emission(NamedTuple):
    round: int             # 1-based lockstep round
    core: int              # 0-based core that reached t=1
    out: np.ndarray        # its latent, float32
    ratio: Optional[float]  # ||out - prev|| / ||out||; None for the first


def init_positions(i_seq, r: int):
    """(cur, nxt) of every core in round ``r`` (1-based): core k makes k
    jumps along the init sequence, then unit steps."""
    k_all = len(i_seq)
    cur, nxt = [], []
    for k in range(k_all):
        if r <= k:
            c, x = i_seq[r - 1], i_seq[min(r, k_all - 1)]
        else:
            c = i_seq[k] + r - k - 1
            x = c + 1
        cur.append(c)
        nxt.append(x)
    return cur, nxt


def chords(f, x0: np.ndarray, i_seq, n: int, rtol: float,
           min_rounds: int = 0) -> List[Emission]:
    """Run CHORDS on one request; returns the streamed emissions up to the
    round where the reference accepts, and at least ``min_rounds`` rounds
    (so a later emission that another solver returned can be compared)."""
    import jax.numpy as jnp

    k_all = len(i_seq)
    tgrid = np.linspace(0.0, 1.0, n + 1, dtype=np.float32)
    x = jnp.broadcast_to(jnp.asarray(x0, jnp.float32)[None],
                         (k_all,) + x0.shape)
    xs = [x[k] for k in range(k_all)]
    x_snap = list(xs)
    f_snap = [jnp.zeros_like(xs[0]) for _ in range(k_all)]
    p = list(i_seq)
    emissions: List[Emission] = []
    accepted = False
    for r in range(1, n + 1):
        if accepted and r > min_rounds:
            break
        cur, nxt = init_positions(i_seq, r)
        alive = [c <= n - 1 for c in cur]
        t_cur = [tgrid[min(max(c, 0), n)] for c in cur]
        fx = f(jnp.stack(xs), jnp.asarray(t_cur, jnp.float32))
        fk = [fx[k] for k in range(k_all)]
        for k in range(k_all):
            if alive[k] and cur[k] == p[k]:
                x_snap[k], f_snap[k] = xs[k], fk[k]
        new = []
        for k in range(k_all):
            t_n = tgrid[min(max(nxt[k], 0), n)]
            step = xs[k] + float(t_n - t_cur[k]) * fk[k]
            fire = k > 0 and alive[k] and cur[k - 1] == p[k]
            if fire:
                t_p = tgrid[min(max(p[k], 0), n)]
                step = step + (float(t_n - t_p) * (fk[k - 1] - f_snap[k])
                               + (xs[k - 1] - x_snap[k]))
                x_snap[k] = step
                p[k] = nxt[k]
            new.append(step if alive[k] else xs[k])
        xs = new
        emitting = [k for k in range(k_all) if alive[k] and nxt[k] == n]
        if emitting:
            core = min(emitting)  # the slowest core emitting wins
            out = np.asarray(xs[core], np.float32)
            ratio = None
            if emissions:
                prev = emissions[-1].out.astype(np.float64)
                o64 = out.astype(np.float64)
                ratio = float(np.linalg.norm(o64 - prev)
                              / (np.linalg.norm(o64) + 1e-12))
            emissions.append(Emission(r, core, out, ratio))
            if core == 0 or (ratio is not None and ratio < rtol):
                accepted = True
    return emissions
