"""Drive the engine through set-up, the measured window and the drain.

The harness steps ``ContinuousEngine.step()`` itself, in one thread: it
submits the requests that are due, steps, and collects what each step
drains (the final latent is on the host when ``step()`` returns). Every
step is timed on the host clock; a step's rounds are the engine's
``round_count`` delta, and a request's rounds are the last ``rounds_used``
rounds up to the step that returned it (every live lane advances one round
per engine round).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Backend compiles, with the host time at which each was reported."""

    def __init__(self):
        import jax

        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), kw.get("fun_name", "?"),
                                float(duration)))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class GcLog:
    """Python garbage collections (start time, seconds, generation)."""

    def __init__(self):
        import gc

        self.events: List[tuple] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.events.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))

    def between(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


class Annotation:
    """A profiler host span entered and left at arbitrary points."""

    def __init__(self, name: str, on: bool):
        self.name, self.on, self._ann = name, on, None

    def __enter__(self):
        if self.on:
            import jax.profiler
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None


class EngineLoop:
    """One engine, its requests and the record of what happened."""

    def __init__(self, engine, make_request: Callable, annotate: bool):
        self.engine = engine
        self.make_request = make_request
        self.annotate = annotate
        self.origin = time.perf_counter()
        self.steps: List[tuple] = []          # (t0, t1, rounds, round_end)
        self.req: Dict[int, dict] = {}
        self.order: List[int] = []            # submission order
        self.outstanding = 0

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def submit(self, rid: int, due: Optional[float] = None,
               in_window: bool = False):
        t = self.now()
        self.engine.submit(self.make_request(rid))
        self.req[rid] = {"rid": rid, "due": t if due is None else due,
                         "submitted": t, "in_window": in_window}
        self.order.append(rid)
        self.outstanding += 1

    def step(self) -> List[int]:
        eng = self.engine
        rc0 = eng.round_count
        t0 = self.now()
        with Annotation("bench/step", self.annotate):
            outs = eng.step()
        t1 = self.now()
        ran = eng.round_count - rc0
        if ran:
            self.steps.append((t0, t1, ran, eng.round_count))
        done = []
        for rid, out in outs:
            r = self.req[rid]
            r.update(finished=t1, round_end=eng.round_count,
                     rounds_used=int(out.rounds_used),
                     core=int(out.accepted_core),
                     latent=np.asarray(out.sample))
            self.outstanding -= 1
            done.append(rid)
        return done

    def run_until_done(self, rids, limit_s: float) -> bool:
        """Step until ``rids`` are all done; False if ``limit_s`` passes
        first."""
        end = self.now() + limit_s
        while any("finished" not in self.req[r] for r in rids):
            if self.now() > end:
                return False
            self.step()
        return True

    def wait_until(self, t: float):
        dt = t - self.now()
        if dt > 0:
            with Annotation("bench/wait", self.annotate):
                time.sleep(dt)

    # -- accounting after the run ----------------------------------------

    def round_intervals(self) -> Dict[int, tuple]:
        """Global round index (1-based) -> (start, end) on the host clock;
        a step's time is split evenly over the rounds it ran."""
        out = {}
        for t0, t1, ran, end in self.steps:
            for j in range(ran):
                g = end - ran + 1 + j
                out[g] = (t0 + (t1 - t0) * j / ran,
                          t0 + (t1 - t0) * (j + 1) / ran)
        return out

    def rounds_of(self, rid: int) -> List[int]:
        r = self.req[rid]
        if "finished" not in r:
            return []
        return list(range(r["round_end"] - r["rounds_used"] + 1,
                          r["round_end"] + 1))


def overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))
