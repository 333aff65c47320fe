"""Host ms of SummarizerPod.readout and its copy to the host."""
from bench import readings


def read(ctx):
    return readings.readout_ms(ctx)
