"""Zamba2 backbone, as the repo's model has it: Mamba2 layers
(``reference.mamba2``), and after every ``attn_every`` layers one shared
block: ``concat(h, h0)`` projected to ``d_model``, attention and MLP at
that width, projected back and added (published Zamba2 attends at the
concatenated width, with two such blocks). Attention there is causal, as
the program runs hybrid backbones (ROADMAP R5)."""
from __future__ import annotations

from flops import _attn_block, mamba2_layer
from flops import _mlp as _mlp_flops
from reference import _attn, _layer, _mlp, _rms, mamba2

ATTENTION_CAUSAL = True
STACKED = {"mamba": 1}  # [num_layers, ...]; the shared block is one


def reference(p, m, h0, mm):
    import jax.numpy as jnp
    eps = m["norm_eps"]
    sp = p["shared"]
    h = h0
    for i in range(m["num_layers"]):
        lp = _layer(p["mamba"], i)
        h = h + mamba2(lp["ssd"], m, _rms(h, lp["ln"], eps), mm)
        if (i + 1) % m["attn_every"] == 0:
            x = _rms(jnp.concatenate([h, h0], -1), sp["ln_in"], eps)
            x = mm("bse,ed->bsd", x, sp["w_in"])
            x = x + _attn(sp["attn"], m, _rms(x, sp["ln1"], eps),
                          ATTENTION_CAUSAL, mm)
            x = x + _mlp(sp["mlp"], _rms(x, sp["ln2"], eps), mm)
            h = h + mm("bsd,de->bse", x, sp["w_out"])
    return _rms(h, p["final_norm"], eps)


def flops(s: int, m: dict) -> float:
    d = m["d_model"]
    calls = m["num_layers"] // m["attn_every"]
    shared = (2 * s * 2 * d * d                    # w_in on concat(h, h0)
              + _attn_block(s, m, causal=ATTENTION_CAUSAL)
              + _mlp_flops(s, d, m["d_ff"])
              + 2 * s * d * d)                     # w_out
    return m["num_layers"] * mamba2_layer(s, m) + calls * shared
