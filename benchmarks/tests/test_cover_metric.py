"""The reader of the fine windows' re-cover span, on hand-built span trees."""

import pytest

from benchmarks.tests.test_phase_metrics import (COLD, WARM, context, reader,
                                                 span)


def test_cover_reader_on_known_trees():
    # the re-cover nested in the fine windows: 90 of its 120 ms
    covered = span(
        "density", 400.0,
        span("scan.compact", 150.0,
             span("scan.windows.fine", 120.0,
                  span("scan.cover", 90.0, ranges=4000),
                  rows=3000, ranges=4000),
             B=128, C=30, rows=3840),
        span("scan.kernel", 1.0, compact=True, site="density", rows=4096),
    )
    cover = reader("executor.cover_ms")
    ctx = context({0: [covered], 1: [WARM]}, {7: 2048, 8: 2048},
                  {0: 7, 1: 8})
    assert cover.read(ctx) == pytest.approx(45.0)
    # the span is nested: the windows and compaction readers are unmoved
    assert reader("executor.windows_ms").read(ctx) == pytest.approx(60.0)
    assert reader("executor.compact_ms").read(ctx) == pytest.approx(15.0)
    # a window without the span, as on a program that has none
    for trees in ({0: [COLD], 1: [WARM]}, {0: [WARM]}, {}):
        assert cover.read(context(trees, {7: 1}, {k: 7 for k in trees})) \
            is None
