"""Mean time per request in the program's ``plan`` spans (ms)."""

from benchmarks.harness import per_request, span_sum


def read(ctx):
    return per_request(span_sum(ctx, {"plan"}), ctx)
