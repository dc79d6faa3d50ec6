"""Pallas kernel contract checker.

Each kernel package exposes ``launch_meta(...)`` (``repro.kernels.meta``)
— the *same* static description its ``pl.pallas_call`` is built from — so
this pass can concretely enumerate the grid and evaluate every
``BlockSpec.index_map`` without tracing the kernel body:

* ``index-map``       — index_map arity / return-rank mismatch vs the
                        block shape (error).
* ``oob-block``       — a block origin outside the backing array: Pallas
                        silently clamps/pads these, masking logic bugs
                        (error).
* ``ww-race``         — two grid programs whose *output* blocks overlap:
                        on TPU the grid is a sequential megacore loop but
                        on GPU/interpret it is parallel, so overlapping
                        writes are nondeterministic (error).
* ``vmem``            — per-program footprint (all input+output blocks,
                        x2 for double buffering, plus the launch's scratch
                        once) over the VMEM budget (error), or over half of
                        it (info).
* ``oracle-mismatch`` — kernel op and its ``ref.py`` oracle disagree on
                        abstract output shapes/dtypes (error).

Block semantics follow Pallas: an ``int`` entry in ``block_shape`` means
the index_map returns a *block* index for that dim (origin = idx * size);
a ``None`` entry is a squeezed unit dim addressed by *element* index.
"""
from __future__ import annotations

import itertools
from typing import Iterable, List, Sequence, Tuple

from repro.analysis.report import Finding
from repro.kernels.meta import BlockMeta, KernelLaunch, vmem_bytes

PASS = "pallas"

VMEM_BUDGET_BYTES = 16 * 1024 * 1024  # per-core VMEM on current TPUs

Region = Tuple[Tuple[int, int], ...]  # ((origin, extent), ...) per array dim


def grid_points(grid: Sequence[int]) -> List[Tuple[int, ...]]:
    return list(itertools.product(*(range(g) for g in grid)))


def region(meta: BlockMeta, idx: Tuple[int, ...]) -> Region:
    """Concrete (origin, extent) per array dim for one grid point."""
    ret = meta.index_map(*idx)
    if not isinstance(ret, tuple):
        ret = (ret,)
    if len(ret) != len(meta.block_shape):
        raise ValueError(
            f"index_map returned {len(ret)} indices for block_shape of "
            f"rank {len(meta.block_shape)}")
    out = []
    for b, r in zip(meta.block_shape, ret):
        r = int(r)
        if b is None:
            out.append((r, 1))
        else:
            out.append((r * int(b), int(b)))
    return tuple(out)


def _overlaps(a: Region, b: Region) -> bool:
    return all(ao < bo + be and bo < ao + ae
               for (ao, ae), (bo, be) in zip(a, b))


def find_races(meta: BlockMeta, points: Iterable[Tuple[int, ...]]):
    """All pairs of grid points whose blocks of ``meta`` overlap.

    Result is canonically sorted, so it is invariant under any
    permutation of ``points`` (property-tested in test_analysis.py).
    """
    regs = sorted((region(meta, p), tuple(p)) for p in points)
    races = set()
    for i, (ra, pa) in enumerate(regs):
        for rb, pb in regs[i + 1:]:
            # sorted by origin tuple: once first dims stop overlapping
            # nothing later can overlap either
            if rb[0][0] >= ra[0][0] + ra[0][1]:
                break
            if pa != pb and _overlaps(ra, rb):
                races.add(tuple(sorted((pa, pb))))
    return sorted(races)


def check_launch(launch: KernelLaunch,
                 vmem_budget_bytes: int = VMEM_BUDGET_BYTES
                 ) -> List[Finding]:
    """Statically verify one kernel launch description."""
    findings: List[Finding] = []
    points = grid_points(launch.grid)

    for role, metas in (("in", launch.inputs), ("out", launch.outputs)):
        for meta in metas:
            loc = f"{launch.kernel}:{meta.name}"

            # arity: index_map must accept exactly one index per grid dim
            try:
                first = region(meta, points[0]) if points else None
            except TypeError as e:
                findings.append(Finding(
                    PASS, "index-map", "error", loc,
                    f"{loc}: index_map does not accept {len(launch.grid)} "
                    f"grid indices: {e}"))
                continue
            except ValueError as e:
                findings.append(Finding(
                    PASS, "index-map", "error", loc, f"{loc}: {e}"))
                continue
            del first

            oob = []
            for p in points:
                for d, (o, e) in enumerate(region(meta, p)):
                    if o < 0 or o + e > meta.array_shape[d]:
                        oob.append((p, d, o, e))
            if oob:
                p, d, o, e = oob[0]
                findings.append(Finding(
                    PASS, "oob-block", "error", loc,
                    f"{loc}: {len(oob)} grid point(s) address blocks "
                    f"outside the {meta.array_shape} array, e.g. grid "
                    f"{p}: dim {d} spans [{o}, {o + e}) — Pallas pads "
                    f"these silently"))

            if role == "out":
                races = find_races(meta, points)
                if races:
                    pa, pb = races[0]
                    findings.append(Finding(
                        PASS, "ww-race", "error", loc,
                        f"{loc}: {len(races)} grid program pair(s) write "
                        f"overlapping output blocks, e.g. {pa} vs {pb} — "
                        f"nondeterministic on parallel backends"))

    vmem = vmem_bytes(launch)
    vloc = f"{launch.kernel}:grid{tuple(launch.grid)}"
    if vmem > vmem_budget_bytes:
        findings.append(Finding(
            PASS, "vmem", "error", vloc,
            f"{vloc}: per-program footprint {vmem} B (double-buffered "
            f"blocks and scratch) exceeds the {vmem_budget_bytes} B VMEM "
            f"budget — shrink the block shapes"))
    elif vmem > vmem_budget_bytes // 2:
        findings.append(Finding(
            PASS, "vmem", "info", vloc,
            f"{vloc}: per-program footprint {vmem} B is over half the "
            f"{vmem_budget_bytes} B VMEM budget; headroom is thin"))
    return findings


def check_oracle(kernel: str, op, ref, op_args, ref_args=None
                 ) -> List[Finding]:
    """Abstractly run kernel op and ref oracle; compare output avals."""
    import jax

    ref_args = op_args if ref_args is None else ref_args
    loc = kernel
    try:
        got = jax.eval_shape(op, *op_args)
        want = jax.eval_shape(ref, *ref_args)
    except Exception as e:  # noqa: BLE001 - report, don't crash the run
        return [Finding(PASS, "oracle-mismatch", "error", loc,
                        f"{loc}: abstract evaluation failed: {e!r}")]
    got_t = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), got)
    want_t = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), want)
    if got_t != want_t:
        return [Finding(PASS, "oracle-mismatch", "error", loc,
                        f"{loc}: kernel outputs {got_t} but ref.py oracle "
                        f"outputs {want_t}")]
    return []
