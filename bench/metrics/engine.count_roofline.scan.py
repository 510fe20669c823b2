"""HBM roofline share (%) of the stream's count step: one read of each new
stream byte, over the device time of the step's XLA module in the trace.
The step is jitted from a functools.partial of _fused_count_step
(core/stream.py), so XLA names its module jit__unknown; it runs once per
chunk.  A program change that names the step, or that runs another
unnamed partial per chunk, leaves this metric out of the result line (the
module then runs a number of times other than the chunks dispatched)."""

from bench.roofline import count_roofline_pct

MODULE = "jit__unknown"


def read(ctx):
    if not ctx.get("dispatches"):
        return None
    return count_roofline_pct(ctx, MODULE, ctx["bytes"] / ctx["dispatches"],
                              ctx["dispatches"])
