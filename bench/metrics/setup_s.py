"""Set-up seconds: process start to the end of warm-up (JAX and TPU start,
weights drawn, engine built, programs compiled or loaded, warm-up served)."""


def read(run):
    return run["setup_s"]
