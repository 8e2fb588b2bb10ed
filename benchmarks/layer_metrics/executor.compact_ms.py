"""Mean time per request spent building the compact layout's chunk
descriptor (ms): the SELF time of the program's ``scan.compact`` span
(candidate choice, shared-descriptor lookup, argsort/repeat build), that
is less the ``scan.windows.fine`` span nested in it, which
``executor.windows_ms`` counts. A request whose descriptor was cached, or
that ran the padded path, adds 0. None for a program without the span,
whose ``scan.kernel`` spans carry no ``rows`` either."""

from benchmarks.harness import per_request, self_ms, walk


def read(ctx):
    spans = [s for trees in ctx.spans.values() for t in trees
             for s in walk(t)]
    if not any(s["name"] == "scan.kernel" and "rows" in s.get("attrs", {})
               for s in spans):
        return None
    return per_request(sum(self_ms(s) for s in spans
                           if s["name"] == "scan.compact"), ctx)
