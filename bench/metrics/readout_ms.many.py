"""Host ms of SummarizerPod.readout and its copy to the host, once a round
(the many-tenant cell: (4096, K, d) features)."""
from bench import readings


def read(ctx):
    return readings.readout_ms(ctx)
