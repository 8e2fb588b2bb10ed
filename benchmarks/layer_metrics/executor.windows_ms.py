"""Mean time per request spent resolving scan windows (ms): the
program's ``scan.windows`` span (the planner's coarse windows) plus its
``scan.windows.fine`` span (the re-covered fine windows of the compact
layout). Each opens on a cache miss only, so a request whose windows were
cached adds 0. None for a program without these spans, whose
``scan.kernel`` spans carry no ``rows`` either."""

from benchmarks.harness import per_request, walk

NAMES = {"scan.windows", "scan.windows.fine"}


def read(ctx):
    spans = [s for trees in ctx.spans.values() for t in trees
             for s in walk(t)]
    if not any(s["name"] == "scan.kernel" and "rows" in s.get("attrs", {})
               for s in spans):
        return None
    return per_request(sum(s["ms"] for s in spans if s["name"] in NAMES),
                       ctx)
