"""Reduce a profiler trace to device busy and idle time, kernel time by
name, and idle gaps labelled by what the host was doing.

Two stages, so the second can be checked on a small recorded trace
(``bench/testdata/trace_small.json``, ``bench/tests/test_trace.py``):

1. :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
   keeps, as plain lists: every op on each TPU device's "XLA Ops" line
   (its HLO text, start and duration in ns), and the host spans the
   benchmark and the engine annotate (``bench/...``, ``dispatch/...``).
2. :func:`reduce` takes that record and the traced window (the host span
   ``bench/window``) and returns: the window's length; the seconds in which
   an op ran (the union of op intervals), averaged over the devices; the
   seconds of each op name and of each kernel pattern; and the idle gaps,
   each labelled by the innermost host span that covers its middle.

Ops that contain other ops on the same line (a ``while`` around a layer
scan) count towards busy time but not towards per-op seconds, so no time is
counted twice.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

HOST_PREFIXES = ("bench/", "dispatch/")
WINDOW_SPAN = "bench/window"


def find_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def extract(xspace_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xspace_path)
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        host.append([e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(ops):
    """Ops that contain no other op (sorted by start)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    keep = []
    for i, o in enumerate(ops):
        end = o[1] + o[2]
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[1] < end and nxt[1] + nxt[2] <= end:
            continue
        keep.append(o)
    return keep


def window_of(record: dict):
    spans = [h for h in record["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    return spans[0][1], spans[0][2]


def _label(t: float, host) -> str:
    best = None
    for name, s, e in host:
        if name != WINDOW_SPAN and s <= t <= e:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0] if best else "no host span"


def reduce(record: dict, kernels: Optional[Dict[str, str]] = None) -> dict:
    """Busy/idle seconds, per-op and per-kernel seconds, labelled idle gaps.

    ``kernels`` maps a label to a substring of an op's own name, the part
    of its HLO text before `` = `` (on the v5e a Pallas kernel's op is
    named after its jitted wrapper, such as ``%flash_attention.6``). The
    rest of the text lists the operands, so an op that consumes the
    kernel's result names it there too and must not count as a call."""
    w0, w1 = window_of(record)
    window_ns = w1 - w0
    kernels = kernels or {}
    busy, op_s, kern_s, kern_n, gaps = [], {}, {}, {}, []
    for ops in record["devices"].values():
        inside = [o for o in ops if o[1] < w1 and o[1] + o[2] > w0]
        merged = _union([[max(o[1], w0), min(o[1] + o[2], w1)]
                         for o in inside])
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in _leaves(inside):
            dur = min(s + d, w1) - max(s, w0)
            op_s[name] = op_s.get(name, 0.0) + dur * 1e-9
            own = op_name(name)
            for label, pat in kernels.items():
                if pat in own:
                    kern_s[label] = kern_s.get(label, 0.0) + dur * 1e-9
                    kern_n[label] = kern_n.get(label, 0) + 1
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                gaps.append((s - prev, _label((s + prev) / 2,
                                              record["host"])))
            prev = max(prev, e)
    n_dev = max(1, len(record["devices"]))
    gap_by_label: Dict[str, float] = {}
    for d, label in gaps:
        gap_by_label[label] = gap_by_label.get(label, 0.0) + d * 1e-9
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "devices": n_dev,
        "op_s": op_s,
        "kernel_s": kern_s,
        "kernel_calls": kern_n,
        "idle_by_label_s": gap_by_label,
        "longest_gaps": [[label, d * 1e-9] for d, label
                         in sorted(gaps, reverse=True)[:10]],
    }


def op_name(op: str) -> str:
    """An HLO op's own name, such as ``%fusion.138``."""
    return op.partition(" = ")[0]


def short_name(op: str) -> str:
    """An HLO op's name and (first) result type, without layouts and
    operands."""
    name, _, rest = op.partition(" = ")
    return f"{name} = {rest.lstrip('(').split('{')[0]}" if rest else name


def breakdown(reduced: dict) -> dict:
    top = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[short_name(k), v] for k, v in top],
            "idle_gaps": reduced["longest_gaps"]}
