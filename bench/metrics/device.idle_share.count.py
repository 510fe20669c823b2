"""Idle share (%) of the chip over the traced window: 1 - the union of
device-operation intervals over the window (profiler trace)."""

from bench.roofline import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx)
