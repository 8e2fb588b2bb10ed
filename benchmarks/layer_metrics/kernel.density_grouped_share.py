"""Share of the window's traced density dispatches that the pallas
grouped kernel served (%): ``scan.kernel`` spans whose ``density_kernel``
is ``grouped`` over all spans that name one (``grouped``, ``mxu`` or
``scatter``). Each such span is one increment of the program's
``exec.density.kernel.<kernel>`` counter, so this is the grouped
counter's share of the three counters' increments over the window's
requests. None for a program whose spans carry no ``density_kernel``,
or when the window ran no density dispatch."""

from benchmarks.harness import walk


def read(ctx):
    kinds = [s["attrs"]["density_kernel"]
             for trees in ctx.spans.values() for t in trees
             for s in walk(t)
             if s["name"] == "scan.kernel"
             and "density_kernel" in (s.get("attrs") or {})]
    if not kinds:
        return None
    return 100.0 * kinds.count("grouped") / len(kinds)
