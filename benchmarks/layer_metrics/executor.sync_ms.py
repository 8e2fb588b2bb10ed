"""Mean time per request the host waits on the device and the transfer
back, in the program's ``scan.sync`` spans (ms)."""

from benchmarks.harness import per_request, span_sum


def read(ctx):
    return per_request(span_sum(ctx, {"scan.sync"}), ctx)
