"""The serve path's Pallas kernels compile for a TPU v5e at serving width.

Nothing runs: each test lowers one kernel for a *described* v5e chip (no
chip attached) with the real Mosaic lowering and asserts the compiled HLO
launches it as a ``tpu_custom_call``. The TPU compiler refuses here what
the chip would refuse — a block shape off the (8, 128) tiling, a VMEM
overflow — which interpret-mode tests cannot see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, SEQ, HEADS, HEAD_DIM, D_MODEL = 8, 4096, 24, 128, 3072  # chords-dit-xl
Z_HEADS, Z_HEAD_DIM = 32, 80  # zamba2-2.7b's shared-block attention
K, LATENT = 8, 4096 * 64  # cores x one (4096, 64) latent


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.kernel import flash_attention

    qkv = ((B, SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
    text = _compiled_text(
        functools.partial(flash_attention, causal=False, interpret=False),
        qkv, qkv, qkv, sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,head_dim,dtype,causal", [
    (Z_HEADS, Z_HEAD_DIM, jnp.bfloat16, True),  # lanes sliced, two loops
    (HEADS, HEAD_DIM, jnp.float32, False),  # raises the scoped VMEM limit
], ids=["zamba2-causal-bf16", "dit-f32"])
def test_flash_attention_compiles_default_tiles(one_chip, heads, head_dim,
                                                dtype, causal):
    from repro.kernels.flash_attention.kernel import flash_attention

    qkv = ((B, SEQ, heads, head_dim), dtype)
    text = _compiled_text(
        functools.partial(flash_attention, causal=causal, interpret=False),
        qkv, qkv, qkv, sharding=one_chip)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm.kernel import rmsnorm

    text = _compiled_text(
        functools.partial(rmsnorm, interpret=False),
        ((B * SEQ, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.bfloat16),
        sharding=one_chip)
    assert "tpu_custom_call" in text


def _rectify_shapes(n_latents):
    lat = ((K, LATENT), jnp.float32)
    return ([lat] * n_latents
            + [((K,), jnp.float32), ((K,), jnp.float32), ((K,), jnp.bool_)])


def test_fused_step_rectify_compiles(one_chip):
    from repro.kernels.rectify.kernel import fused_step_rectify

    text = _compiled_text(
        functools.partial(fused_step_rectify, interpret=False),
        *_rectify_shapes(6), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_fused_step_rectify_accept_compiles(one_chip):
    from repro.kernels.rectify.kernel import fused_step_rectify_accept

    text = _compiled_text(
        functools.partial(fused_step_rectify_accept, interpret=False),
        *_rectify_shapes(7), sharding=one_chip)
    assert "tpu_custom_call" in text


def test_nemotron_round_fits_one_chip(one_chip):
    # one CHORDS round of nemotron-3-nano-7l (the benchmark's first 7 layers,
    # MEMEM*E, every width and all 128 experts) over S*K = 8 latents of
    # 4096 tokens: its weights and temporaries fit a v5e's 16 GB, and the
    # grouped expert matmuls lower to XLA's ragged-dot kernel, the ops
    # bench/metrics/expert_roofline.py reads
    from repro.configs import get_config
    from repro.core.ode import uniform_tgrid
    from repro.diffusion import init_wrapper, make_drift
    from repro.serve.executor import GridSpec, _grid_fns, _slot_state_structs

    cfg = get_config("nemotron-3-nano-30b-a3b").replace(
        num_layers=7, layer_pattern="MEMEM*E")
    spec = GridSpec(num_slots=2, num_cores=4, latent_shape=(1, 4096, 64),
                    donate=True)
    fn = _grid_fns(make_drift(None, cfg), uniform_tgrid(20), 20, spec,
                   True)["round"]
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(
        lambda k: init_wrapper(cfg, 64, k, cfg.param_dtype),
        jax.random.PRNGKey(0))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(_slot_state_structs(spec))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 8e9  # 4.03 B bf16 parameters
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, mem
    assert "%ragged-dot-none" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(2688, 1856), (1856, 2688)], ids=["up", "down"])
def test_nemotron_grouped_matmul_compiles_with_picked_tiles(one_chip, k, n):
    # a round's 196,608 expert rows over 128 experts, as in
    # nemotron3-nano.img-backlog: the compiler takes the tiles _ragged_tiles
    # picks (a tiling over the scoped VMEM is refused here), and the op
    # keeps the name bench/metrics/expert_roofline.py reads
    from repro.models import moe

    rows, experts = 196608, 128
    tm, tk, tn = moe._ragged_tiles(rows, k, n, experts, 2)
    text = _compiled_text(
        moe._tiled_ragged_dot, ((rows, k), jnp.bfloat16),
        ((experts, k, n), jnp.bfloat16), ((experts,), jnp.int32),
        sharding=one_chip)
    assert f'ragged_dot_tiling="{tm},{tk},{tn}"' in text
    assert "%ragged-dot-none" in text
