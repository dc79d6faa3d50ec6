"""The one traffic generator: arrival schedules from a traffic file.

``poisson``: open-loop arrivals at ``rate_per_s`` whose gaps are the
quantiles of the exponential distribution, scrambled in an order fixed by
the file's ``order_seed``: a Poisson stream's bursts and lulls, but the
same schedule for every run seed. The run seed draws the requests (their
noise) and the weights; were it to reorder the gaps as well, which request
waits would change from seed to seed, and with some twenty requests in a
window the tail would swing by tens of percent between seeds. Three
stretches are laid out alike: a pre-roll of ``preroll_s`` before the
window (so it opens on a loaded grid), the window itself (exactly
``round(rate * seconds)`` arrivals), and a tail that keeps the load on
while the window's requests finish.

``backlog``: a closed backlog of ``depth_per_slot * num_slots`` requests;
each completion submits one more, so the queue never empties.
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class Arrival(NamedTuple):
    due: float      # seconds after the schedule's origin
    in_window: bool


def _gaps(n: int, rate: float, span: float, rng) -> np.ndarray:
    """``n`` exponential-quantile gaps scaled to sum to ``span``, in
    ``rng``'s order."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    q *= span / q.sum()
    return rng.permutation(q)


def poisson(spec: dict, seconds: float, tail_s: float) -> List[Arrival]:
    rate = float(spec["rate_per_s"])
    rng = np.random.default_rng(int(spec["order_seed"]))
    pre = float(spec.get("preroll_s", 0.0))
    out: List[Arrival] = []
    start = 0.0
    for span, inside in ((pre, False), (seconds, True), (tail_s, False)):
        n = int(round(rate * span))
        if n:
            gaps = _gaps(n, rate, span, rng)
            # the first arrival opens the stretch; each gap follows one
            due = start + np.cumsum(gaps) - gaps
            out.extend(Arrival(float(d), inside) for d in due)
        start += span
    return out


def window_bounds(spec: dict, seconds: float):
    """(start, end) of the window, in seconds after the origin."""
    pre = float(spec.get("preroll_s", 0.0))
    return pre, pre + seconds
