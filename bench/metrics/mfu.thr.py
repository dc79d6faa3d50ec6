"""Useful model FLOPs of the window's rounds over the rounds' host wall
time (step start to step end) and the chip's bf16 peak. Useful: live
slots' cores that have not yet reached t=1."""
from readers import step_mfu


def read(run):
    return step_mfu(run)
