"""The serve surface the analyzer lints: executor programs + kernel launches.

One place defines WHAT gets checked so the CLI, the tests, and CI all lint
the same thing: the full bucket ladder a ``ContinuousEngine`` walks
(``engine.bucket_ladder``), the batch streaming program, lane migration
between adjacent buckets, and the five Pallas kernel launches at
representative shapes. The drift is the analytic ``-x * t`` used across
the test suite — program *structure* (what the passes inspect) does not
depend on the drift's weights, so linting the analytic surface covers the
control flow every model-backed engine runs.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

N_STEPS = 20
NUM_CORES = 4
MIN_SLOTS = 4
MAX_SLOTS = 16
LATENT_SHAPE = (8,)
RTOL = 0.05


def drift(x, t):
    return -x * t


def make_executor():
    import jax.numpy as jnp  # noqa: F401 - jax import gated to call time

    from repro.core.ode import uniform_tgrid
    from repro.serve.executor import RoundExecutor

    return RoundExecutor(drift, uniform_tgrid(N_STEPS), N_STEPS)


def grid_ladder(min_slots: int = MIN_SLOTS, max_slots: int = MAX_SLOTS
                ) -> List:
    """One GridSpec per capacity bucket an elastic engine can visit."""
    from repro.serve.engine import bucket_ladder
    from repro.serve.executor import GridSpec

    return [GridSpec(num_slots=s, num_cores=NUM_CORES,
                     latent_shape=LATENT_SHAPE)
            for s in bucket_ladder(min_slots, max_slots)]


def stream_specs() -> List:
    from repro.core.init_sequence import make_sequence
    from repro.serve.executor import StreamSpec

    i_seq = tuple(make_sequence(NUM_CORES, N_STEPS))
    return [StreamSpec(num_cores=NUM_CORES, i_seq=i_seq, rtol=RTOL,
                      batched=b) for b in (False, True)]


def lane_grid_ladder(min_slots: int = MIN_SLOTS, max_slots: int = MAX_SLOTS
                     ) -> List:
    """The heterogeneous-lane variant of :func:`grid_ladder`: every bucket
    with the default draft/refine lane profile for ``NUM_CORES``. Kept as a
    SEPARATE ladder — a homogeneous grid carries no ``LaneState`` pytree, so
    migrate pairs must never mix the two families."""
    from repro.core.chords import default_lane_profile
    from repro.serve.engine import bucket_ladder
    from repro.serve.executor import GridSpec

    profile = default_lane_profile(NUM_CORES)
    return [GridSpec(num_slots=s, num_cores=NUM_CORES,
                     latent_shape=LATENT_SHAPE, lane_profile=profile)
            for s in bucket_ladder(min_slots, max_slots)]


def migrate_pairs(ladder=None) -> List[Tuple]:
    """Adjacent-bucket (src, dst) GridSpec pairs, both directions
    (grow + shrink)."""
    ladder = grid_ladder() if ladder is None else ladder
    pairs = []
    for a, b in zip(ladder, ladder[1:]):
        pairs += [(a, b), (b, a)]
    return pairs


def enumerate_serve_programs(executor=None) -> List:
    ex = make_executor() if executor is None else executor
    return ex.enumerate_programs(
        grid_specs=grid_ladder() + lane_grid_ladder(),
        stream_specs=stream_specs(),
        stream_latent_shape=LATENT_SHAPE,
        migrate_pairs=migrate_pairs() + migrate_pairs(lane_grid_ladder()))


class KernelCase(NamedTuple):
    """One kernel at a representative shape: its static launch description
    plus (op, oracle, abstract args) for the shape/dtype agreement check."""

    name: str
    launch: object
    op: object
    ref: object
    op_args: Tuple
    ref_args: Tuple


def kernel_cases() -> List[KernelCase]:
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.kernel import (
        launch_meta as flash_meta)
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rectify.kernel import (fused_step_rectify,
                                              fused_step_rectify_accept,
                                              launch_meta as rect_meta,
                                              launch_meta_accept)
    from repro.kernels.rectify.ref import (fused_step_rectify_accept_ref,
                                           fused_step_rectify_ref)
    from repro.kernels.rmsnorm.kernel import (launch_meta as rms_meta,
                                              rmsnorm)
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.ssd_scan.kernel import (launch_meta as ssd_meta,
                                               ssd_chunk)
    from repro.kernels.ssd_scan.ref import ssd_chunk_ref

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    cases = []

    b, sq, h, dh, sk, kvh = 2, 256, 4, 64, 256, 2  # default (shape) tiles
    cases.append(KernelCase(
        "flash_attention", flash_meta(b, sq, h, dh, sk, kvh),
        functools.partial(flash_attention, causal=True),
        functools.partial(attention_ref, causal=True),
        (f32(b, sq, h, dh), f32(b, sk, kvh, dh), f32(b, sk, kvh, dh)),
        (f32(b, sq, h, dh), f32(b, sk, kvh, dh), f32(b, sk, kvh, dh))))

    rows, d = 512, 128
    cases.append(KernelCase(
        "rmsnorm", rms_meta(rows, d),
        rmsnorm, rmsnorm_ref,
        (f32(rows, d), f32(d)), (f32(rows, d), f32(d))))

    g, hh, lc, n, hd = 4, 2, 256, 64, 64
    ref_b = jax.vmap(jax.vmap(ssd_chunk_ref, in_axes=(None, None, 0, 0)),
                     in_axes=(0, 0, 0, 0))
    cases.append(KernelCase(
        "ssd_scan", ssd_meta(g, hh, lc, n, hd),
        ssd_chunk, ref_b,
        (f32(g, lc, n), f32(g, lc, n), f32(g, hh, lc, hd), f32(g, hh, lc)),
        (f32(g, lc, n), f32(g, lc, n), f32(g, hh, lc, hd), f32(g, hh, lc))))

    k, m = NUM_CORES, 8192
    rect_args = tuple([f32(k, m)] * 6) + (
        f32(k), f32(k), jax.ShapeDtypeStruct((k,), jnp.bool_))
    cases.append(KernelCase(
        "rectify", rect_meta(k, m),
        fused_step_rectify, fused_step_rectify_ref, rect_args, rect_args))

    acc_args = tuple([f32(k, m)] * 7) + (
        f32(k), f32(k), jax.ShapeDtypeStruct((k,), jnp.bool_))
    cases.append(KernelCase(
        "rectify_accept", launch_meta_accept(k, m),
        fused_step_rectify_accept, fused_step_rectify_accept_ref,
        acc_args, acc_args))
    return cases
