"""The comparison that decides ``correct``.

Each checked request's returned latent is held against the reference's
emission for the same request (same round, same core). The program may
accept that emission only where the reference could have accepted it
within the program's own rounding: its agreement ratio under
``rtol + band`` (or it is core 0's, which is always accepted), and every
earlier ratio at or over ``rtol - band``, with ``band`` twice the gap of
the returned latent (an error of ``g`` in each of two latents moves their
ratio by up to ``2 g``). Such an emission is the one compared; any other
answer is compared with the reference's own accepted emission, so a wrong
accept decision reads as a large gap.

The number compared is ``latent_gap``: the largest ``||y - y_ref|| /
||y_ref||`` over the checked requests. A request the reference cannot match
to an emission, or a non-finite latent, reads as infinite.
"""
from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np

from reference import Emission


def rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    if a.shape != b.shape or not np.isfinite(a).all():
        return math.inf
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def reference_choice(emissions: Sequence[Emission], rtol: float) -> int:
    for j, e in enumerate(emissions):
        if e.core == 0 or (e.ratio is not None and e.ratio < rtol):
            return j
    return len(emissions) - 1


def admissible(emissions: Sequence[Emission], j: int, rtol: float,
               band: float) -> bool:
    e = emissions[j]
    if not (e.core == 0 or (e.ratio is not None and e.ratio < rtol + band)):
        return False
    return all(x.ratio is None or x.ratio >= rtol - band
               for x in emissions[:j])


def judge(latent, rounds_used: int, core: int,
          emissions: List[Emission], rtol: float) -> dict:
    """Gap of one returned latent, with what it was compared against."""
    match = [j for j, e in enumerate(emissions)
             if e.round == rounds_used and e.core == core]
    ref_j = reference_choice(emissions, rtol)
    j = ref_j
    if match:
        band = 2.0 * rel_gap(latent, emissions[match[0]].out)
        if admissible(emissions, match[0], rtol, band):
            j = match[0]
    return {"gap": rel_gap(latent, emissions[j].out),
            "program": [int(rounds_used), int(core)],
            "reference": [emissions[ref_j].round, emissions[ref_j].core],
            "compared_with": [emissions[j].round, emissions[j].core],
            "ratios": [e.ratio for e in emissions]}
