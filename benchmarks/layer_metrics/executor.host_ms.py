"""Mean host time per request in the executor (ms): every
``scan.device_put`` span (columns and slab gathers staged for the scan)
plus the SELF time of each dataset-operation span (``density``,
``stats``, ``count``, ``query`` and their fused batch forms): the span's
duration less its children's, which is where window resolution, layout
compaction and the density schedule build run today, since they have no
span of their own."""

from benchmarks.harness import per_request, self_ms, span_sum, walk

OPS = {"density", "stats", "count", "query", "query_batches",
       "density_batch", "count_batch", "stats_batch"}


def read(ctx):
    total = span_sum(ctx, {"scan.device_put"})
    for trees in ctx.spans.values():
        for t in trees:
            total += sum(self_ms(s) for s in walk(t) if s["name"] in OPS)
    return per_request(total, ctx)
