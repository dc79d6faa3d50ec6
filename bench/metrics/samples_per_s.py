"""Samples per second over the window: each request adds the share of its
rounds that ran inside the window."""


def read(run):
    return run["samples_in_window"] / run["window_s"]
