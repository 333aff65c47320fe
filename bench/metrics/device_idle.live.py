"""Idle share of the chip over the traced window (open-loop cell)."""
from bench import readings


def read(ctx):
    return readings.device_idle(ctx)
