"""Dense DiT backbone: pre-norm blocks of RoPE multi-head attention over all
tokens and a SwiGLU MLP, final RMSNorm."""
from __future__ import annotations

from flops import _attn_block
from flops import _mlp as _mlp_flops
from reference import _attn, _layer, _mlp, _rms

ATTENTION_CAUSAL = False
STACKED = {"blocks": 1}  # [num_layers, ...]


def reference(p, m, h, mm):
    eps = m["norm_eps"]
    for i in range(m["num_layers"]):
        lp = _layer(p["blocks"], i)
        h = h + _attn(lp["attn"], m, _rms(h, lp["ln1"], eps),
                      ATTENTION_CAUSAL, mm)
        h = h + _mlp(lp["mlp"], _rms(h, lp["ln2"], eps), mm)
    return _rms(h, p["final_norm"], eps)


def flops(s: int, m: dict) -> float:
    per_layer = (_attn_block(s, m, causal=ATTENTION_CAUSAL)
                 + _mlp_flops(s, m["d_model"], m["d_ff"]))
    return m["num_layers"] * per_layer
