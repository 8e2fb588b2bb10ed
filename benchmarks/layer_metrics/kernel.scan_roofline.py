"""Share of the chip's roofline the scans reached in the traced span (%):
the least time of every request completed inside the profiler's capture
(bytes of the matched rows' attributes and of the result over peak HBM
bandwidth, or operations over peak arithmetic, whichever is larger; see
benchmarks/roofline.py) over the device busy time of the capture."""


def read(ctx):
    if ctx.trace is None or ctx.trace_t0 is None or ctx.trace["busy_s"] <= 0:
        return None
    peak = ctx.roofline.peaks(ctx.device_kind)
    least, n = 0.0, 0
    for r in ctx.ok_records():
        if not (ctx.trace_t0 <= r["t_done"] <= ctx.trace_t1):
            continue
        req = ctx.pool[r["pool"]]
        b, ops = ctx.roofline.request_cost(req, ctx.matched[r["pool"]],
                                           ctx.types, ctx.geom, ctx.dtg)
        least += ctx.roofline.least_time_s(b, ops, peak)
        n += 1
    if n == 0 or least <= 0:
        return None
    return least / ctx.trace["busy_s"] * 100.0
