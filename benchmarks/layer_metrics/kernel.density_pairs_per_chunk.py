"""Mean, over the window's traced density dispatches of the compact
layout, of the grouped kernel's (chunk, tile) candidate pairs per real
chunk (pairs/chunk): the ``pairs`` and ``chunks`` attributes of each
``scan.kernel`` span that names a ``density_kernel``. It is the ratio the
density ladder tests against ``geomesa.density.pallas.max.dup`` (4.0)
before it leaves the pallas grouped kernel for the einsum or scatter
rung. None for a program whose spans carry no ``density_kernel``, or
when no density dispatch ran on the compact layout (``chunks`` 0)."""

from benchmarks.harness import walk


def read(ctx):
    ratios = [s["attrs"]["pairs"] / s["attrs"]["chunks"]
              for trees in ctx.spans.values() for t in trees
              for s in walk(t)
              if s["name"] == "scan.kernel"
              and (s.get("attrs") or {}).get("chunks")]
    return sum(ratios) / len(ratios) if ratios else None
