"""Mean host time (ms) per chunk of the stream scanner's wait span: the
host blocked on one chunk's mask step, from the step's dispatch returning
to the device finishing it (Recorder span ``wait``, core/stream.py).
None where the program has no such span."""

from bench.roofline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "wait")
