"""Rows the device kernels read per row that matched (rows/row): the
``rows`` attribute of every ``scan.kernel`` span of the window's traced
requests (padded chunk rows on the compact path, every stored row on the
padded path), over the rows the reference matched for the same requests.
1 would read exactly the matches; the excess is window over-cover and
chunk padding. None when no traced request matched a row, or no
``scan.kernel`` span carries ``rows`` (a program without the attribute)."""

from benchmarks.harness import walk


def read(ctx):
    pool_of = {r["seq"]: r["pool"] for r in ctx.records}
    rows = matched = 0
    seen = False
    for seq, trees in ctx.spans.items():
        for t in trees:
            for s in walk(t):
                attrs = s.get("attrs") or {}
                if s["name"] == "scan.kernel" and "rows" in attrs:
                    rows += int(attrs["rows"])
                    seen = True
        matched += ctx.matched[pool_of[seq]]
    return rows / matched if seen and matched else None
