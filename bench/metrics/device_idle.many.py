"""Idle share of the chip over the traced window (the many-tenant cell)."""
from bench import readings


def read(ctx):
    return readings.device_idle(ctx)
