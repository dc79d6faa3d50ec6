"""90th percentile of the window requests' due-to-latent seconds."""
from readers import latency_percentile


def read(run):
    return latency_percentile(run, 90)
