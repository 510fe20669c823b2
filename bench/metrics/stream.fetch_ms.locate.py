"""Mean host time (ms) per chunk of the stream scanner's fetch span: the
copy of one chunk's (P, N) match mask from the device to the host
(Recorder span ``fetch``, core/stream.py).  None where the program has no
such span."""

from bench.roofline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "fetch")
