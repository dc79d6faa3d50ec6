"""Share of the traced window in which no op ran on the device."""
from readers import device_idle


def read(run):
    return device_idle(run)
