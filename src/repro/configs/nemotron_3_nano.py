"""NVIDIA-Nemotron-3-Nano-30B-A3B — hybrid Mamba2 / MoE / GQA (``nemotron_h``).

https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (config.json,
``model_type: nemotron_h``). 52 pre-norm layers in the pattern below: 23
Mamba2 (64 heads of 64, 8 B/C groups of state 128, conv 4 with bias, chunk
128), 23 MoE (128 relu^2 experts of width 1856, top-6 by sigmoid score,
weights renormalised and scaled by 2.5, one shared expert of width 3712)
and 6 GQA attention layers (32 query and 2 KV heads of 128, no positional
encoding). Used as a denoiser trunk (``repro.diffusion``): no vocabulary.
"""
from repro.configs.base import ModelConfig, register

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def full() -> ModelConfig:
    return ModelConfig(
        name="nemotron-3-nano-30b-a3b",
        family="nemotron_h",
        num_layers=len(PATTERN),
        layer_pattern=PATTERN,
        d_model=2688,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        d_ff=1856,  # per routed expert
        vocab_size=131072,  # unused in the denoiser role
        act="relu2",
        norm_eps=1e-5,
        num_experts=128,
        experts_per_tok=6,
        num_shared_experts=1,
        shared_d_ff=3712,
        router="sigmoid",
        routed_scale=2.5,
        ssm_state=128,
        ssm_heads=64,
        ssm_head_dim=64,
        ssm_groups=8,
        ssm_conv=4,
        ssm_conv_bias=True,
        ssm_chunk=128,
        embeds_input=True,
        tie_embeddings=False,
        source="https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
    )


def reduced() -> ModelConfig:
    return full().replace(
        name="nemotron-3-nano-reduced",
        num_layers=7, layer_pattern="MEMEM*E", d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=32, num_experts=8, experts_per_tok=2,
        shared_d_ff=64, ssm_state=16, ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
        ssm_chunk=8, vocab_size=256, param_dtype="float32", compute_dtype="float32",
    )


register("nemotron-3-nano-30b-a3b", full, reduced)
