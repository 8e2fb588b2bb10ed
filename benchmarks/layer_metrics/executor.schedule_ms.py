"""Mean time per request spent building the density kernel's pair
schedule and placing it on the device (ms): the program's
``scan.schedule`` span, which opens on a schedule-cache miss only, so a
request that hit the cache, or ran no density kernel, adds 0. None for a
program without the span, whose ``scan.kernel`` spans carry no ``rows``
either."""

from benchmarks.harness import per_request, walk


def read(ctx):
    spans = [s for trees in ctx.spans.values() for t in trees
             for s in walk(t)]
    if not any(s["name"] == "scan.kernel" and "rows" in s.get("attrs", {})
               for s in spans):
        return None
    return per_request(sum(s["ms"] for s in spans
                           if s["name"] == "scan.schedule"), ctx)
