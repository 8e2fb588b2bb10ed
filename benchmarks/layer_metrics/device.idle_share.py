"""Share of the traced span in which no operation ran on the chip (%):
1 - (union of device-op intervals / traced span), from the profiler's
trace (benchmarks/trace_reduce.py)."""


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]) * 100.0
