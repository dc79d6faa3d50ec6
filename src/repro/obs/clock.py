"""Fit the tracer's clock onto a ``jax.profiler`` capture's.

Every :meth:`repro.obs.Tracer.phase` span is also a profiler annotation
whose ``t_ns`` argument is the tracer's reading at entry (ns since tracer
birth). In the capture's ``.xplane.pb`` the same annotation starts at
``start_ns`` on the profiler's clock, which counts from the session's
start. The two clocks differ by one constant, so

    offset_ns = median over phase spans of (start_ns - t_ns)

maps any ring event onto the capture, retrospective spans such as
``request/queued`` included: ``profiler_ns = ts * 1e9 + offset_ns``. The
residuals of that fit (each pair's difference minus the median) say how
far to trust it: they are the host's jitter between reading the clock and
entering the annotation.
"""
from __future__ import annotations

import statistics
from typing import Iterable, List, NamedTuple, Tuple


class ClockFit(NamedTuple):
    offset_ns: float     # profiler ns - tracer ns
    spread_ns: float     # largest minus smallest residual
    n: int               # spans the fit used


def fit_offset(pairs: Iterable[Tuple[float, float]]) -> ClockFit:
    """``pairs`` holds (profiler start_ns, ``t_ns`` argument) per span."""
    diffs = [float(s) - float(t) for s, t in pairs]
    if not diffs:
        raise ValueError("no spans with a t_ns argument to fit the clock on")
    off = statistics.median(diffs)
    res = [d - off for d in diffs]
    return ClockFit(off, max(res) - min(res), len(diffs))


def profiler_phases(xspace_path: str) -> List[tuple]:
    """(name, start_ns, end_ns, args) of every host event in a capture that
    carries a ``t_ns`` argument, on the profiler's clock; ``args`` holds
    the annotation's arguments, ``t_ns`` among them."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xspace_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                args = dict(e.stats)
                if "t_ns" in args:
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns), args))
    return out
