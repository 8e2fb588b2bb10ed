"""Mean time per request spent covering a cold view with z-ranges for its
fine windows (ms): the program's ``scan.cover`` span, nested in
``scan.windows.fine`` around the re-cover ``keyspace.plan`` (attribute
``ranges``), so ``executor.windows_ms`` holds it. It opens on a
window-cache miss only: a request whose fine windows were cached adds 0.
None when the window holds no ``scan.cover`` span, as on a program
without it."""

from benchmarks.harness import per_request, walk


def read(ctx):
    ms = [s["ms"] for trees in ctx.spans.values() for t in trees
          for s in walk(t) if s["name"] == "scan.cover"]
    if not ms:
        return None
    return per_request(sum(ms), ctx)
