"""Mean host time (ms) of the stream scanner's host_prep span: reading the
source and assembling one chunk's window (Recorder span, core/stream.py)."""

from bench.roofline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "host_prep")
