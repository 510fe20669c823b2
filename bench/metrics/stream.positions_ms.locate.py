"""Mean host time (ms) per chunk of the stream scanner's positions span:
one chunk's match mask turned into positions on the host, np.nonzero per
pattern row offset by the chunk's base (Recorder span ``positions``,
core/stream.py).  None where the program has no such span."""

from bench.roofline import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx, "positions")
