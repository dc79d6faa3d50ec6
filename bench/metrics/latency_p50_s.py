"""Median seconds from a window request's due time to its final latent on
the host, over every request due in the window."""
from readers import latency_percentile


def read(run):
    return latency_percentile(run, 50)
