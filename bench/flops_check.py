#!/usr/bin/env python3
"""Cross-check ``bench/flops.py`` against XLA's own count, with no chip.

    JAX_PLATFORMS=cpu python3 bench/flops_check.py

Compiles, for a described v5e (``v5e:2x2``, one chip), one drift forward
of each configuration in ``BENCHMARK.json`` at the cells' batch (S x K = 8
latents of 4096x64, the backbone on its jnp path) and the two kernels at
the cells' shapes, and prints ``compiled.cost_analysis()``'s flops and
bytes accessed beside the analytic ones, with their ratio. Nothing runs,
so there are no times. The analytic count of a drift forward is
``flops.drift_forward``: the wrapper's, plus the backbone's from its
family's file, ``bench/backbones/<family>.py``, where a new family's count
lives.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import flops  # noqa: E402

LANES, SEQ, LAT = 8, 4096, 64


def cost(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import get_config
    from repro.diffusion import init_wrapper
    from repro.diffusion.wrapper import denoise
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.rectify.kernel import fused_step_rectify_accept

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["configs"]
    rows = []
    for entry in entries:
        name = entry["name"]
        with open(os.path.join(ROOT, entry["file"])) as f:
            conf = json.load(f)
        cfg = get_config(conf["arch"]).replace(use_kernels=False,
                                               **conf["changes"])
        params = jax.eval_shape(
            lambda k: init_wrapper(cfg, LAT, k, cfg.param_dtype),
            jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                        params)
        fwd = jax.jit(jax.vmap(lambda p, x, t: denoise(p, cfg, x[None], t)[0],
                               in_axes=(None, 0, 0)))
        compiled = fwd.lower(params, sds((LANES, SEQ, LAT), jnp.float32),
                             sds((LANES,), jnp.float32)).compile()
        xf, xb = cost(compiled)
        af = LANES * flops.drift_forward(conf["model"], SEQ, LAT)
        rows.append({"what": f"{name} drift forward x{LANES}",
                     "xla_flops": xf, "analytic_flops": af,
                     "flops_ratio_xla_over_analytic": xf / af,
                     "xla_bytes": xb})

    q = sds((LANES, SEQ, 24, 128), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=False,
                                                 interpret=False))
    xf, xb = cost(fa.lower(q, q, q).compile())
    af, ab = flops.flash_attention_call(LANES, 24, SEQ, SEQ, 128, 2, False)
    rows.append({"what": "flash_attention call (8, 4096, 24, 128) bf16",
                 "xla_flops": xf, "analytic_flops": af, "xla_bytes": xb,
                 "analytic_bytes": ab})

    m = SEQ * LAT
    lat = sds((LANES, m), jnp.float32)
    vec = sds((LANES,), jnp.float32)
    ra = jax.jit(lambda *a: fused_step_rectify_accept(*a, interpret=False))
    xf, xb = cost(ra.lower(lat, lat, lat, lat, lat, lat, lat, vec, vec,
                           sds((LANES,), jnp.bool_)).compile())
    af, ab = flops.rectify_accept_call(LANES, m)
    rows.append({"what": "fused step+rectify+accept call 8 x 262144 f32",
                 "xla_flops": xf, "analytic_flops": af, "xla_bytes": xb,
                 "analytic_bytes": ab})
    for r in rows:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
