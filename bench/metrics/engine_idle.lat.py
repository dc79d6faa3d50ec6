"""Share of the traced window in which the device idled inside an engine
step: idle gaps whose innermost host span is the step (``bench/step``,
which brackets ``ContinuousEngine.step()``; ``serve/step`` where the trace
keeps it) or one of its phases (``serve/``, ``dispatch/``, ``verify/``).
Gaps with no request in flight (``bench/wait``) are not the engine's."""

ENGINE_LABELS = ("bench/step", "serve/", "dispatch/", "verify/")


def read(run):
    red = run["reduced_trace"]
    if red is None or not red["window_s"]:
        return None
    idle = sum(s for label, s in red["idle_by_label_s"].items()
               if label.startswith(ENGINE_LABELS))
    return 100.0 * idle / red["window_s"]
