"""Mean lockstep rounds a window request took before its accept."""
from readers import mean_rounds


def read(run):
    return mean_rounds(run)
