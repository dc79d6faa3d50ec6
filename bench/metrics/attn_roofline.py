"""Flash-attention kernel: least time its FLOPs need at the bf16 peak over
its summed device time (at 4096 tokens it is bound by compute)."""
from readers import attention_roofline

KERNELS = {"flash_attention": "%flash_attention"}


def read(run):
    return attention_roofline(run, "flash_attention")
