"""Static launch metadata shared by every Pallas kernel in this package.

Each kernel module exposes a ``launch_meta(...)`` function returning a
:class:`KernelLaunch` — the grid plus one :class:`BlockMeta` per operand —
and builds its actual ``pl.pallas_call`` block specs FROM that metadata via
:func:`block_specs`. The kernel and the static checker
(``repro.analysis.pallas_check``) therefore read the *same* index maps and
block shapes by construction: the checker can enumerate the grid, evaluate
every ``index_map`` concretely, and prove write-write-race freedom /
in-bounds origins / VMEM budgets without ever executing the kernel — and a
kernel cannot silently change its tiling out from under the analysis.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

DOUBLE_BUFFER = 2  # a pipelined pallas_call keeps two copies of each block


class BlockMeta(NamedTuple):
    """One operand's BlockSpec, plus the facts Pallas itself never needs but
    a static checker does: the full array shape and dtype.

    ``block_shape`` follows Pallas conventions — an int entry is a block
    size along that dim (``index_map`` returns a *block* index there, so the
    element origin is ``index * size``); a ``None`` entry is a squeezed
    unit dim (``index_map`` returns an *element* index there).
    """

    name: str
    block_shape: Tuple[Optional[int], ...]
    index_map: Callable
    array_shape: Tuple[int, ...]
    dtype: str


class ScratchMeta(NamedTuple):
    """A block-shaped buffer one program keeps in VMEM besides its operand
    blocks (a score tile, running statistics, an accumulator): held once per
    program, not double-buffered."""

    name: str
    shape: Tuple[int, ...]
    dtype: str


class KernelLaunch(NamedTuple):
    """A kernel's complete static launch description."""

    kernel: str                       # e.g. "rectify.fused_step_rectify"
    grid: Tuple[int, ...]
    inputs: Tuple[BlockMeta, ...]
    outputs: Tuple[BlockMeta, ...]
    scratch: Tuple[ScratchMeta, ...] = ()


def vmem_bytes(launch: KernelLaunch) -> int:
    """One program's static VMEM footprint: every operand block
    double-buffered, plus the scratch once."""
    blocks = sum(math.prod(1 if b is None else b for b in m.block_shape)
                 * np.dtype(m.dtype).itemsize
                 for m in launch.inputs + launch.outputs)
    scratch = sum(math.prod(m.shape) * np.dtype(m.dtype).itemsize
                  for m in launch.scratch)
    return DOUBLE_BUFFER * blocks + scratch


def block_specs(metas):
    """The ``pl.BlockSpec`` list a ``pallas_call`` consumes, built from the
    metadata the checker consumes — single source of truth for the tiling."""
    from jax.experimental import pallas as pl

    return [pl.BlockSpec(m.block_shape, m.index_map) for m in metas]
