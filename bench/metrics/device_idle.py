"""Idle share of the chip over the traced window (closed-loop cells)."""
from bench import readings


def read(ctx):
    return readings.device_idle(ctx)
