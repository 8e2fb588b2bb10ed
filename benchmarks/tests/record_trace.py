"""Record the small device trace the trace-reduction test reads.

    python benchmarks/tests/record_trace.py   # on a machine with a TPU

Loads 1M GDELT-like rows, traces a few density queries through
``GeoDataset`` with the program's ``geomesa:<span>`` annotations on, and
writes the trace, gzipped, to ``benchmarks/tests/data/small.xplane.pb.gz``
with the reduction it gives to ``small.reduce.json``.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    sys.path.insert(0, ROOT)
    os.environ["GEOMESA_TRACE_ENABLED"] = "true"
    os.environ["GEOMESA_TRACE_JAX_PROFILER"] = "true"
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from benchmarks import harness, trace_reduce
    from geomesa_tpu import GeoDataset

    cell = harness.Cell("gdelt.heatmap_pow2")
    cfg = dict(cell.cfg, rows=1_000_000)
    columns, fids, _ = cell.cfg_mod.generate(cfg, 5)
    ds = GeoDataset()
    ds.create_schema(cfg["schema"], cfg["spec"])
    ds.insert(cfg["schema"], columns, fids=fids)
    ds.flush(cfg["schema"])
    ecql = ("BBOX(geom, -20.0, 20.0, 40.0, 60.0) AND dtg DURING "
            "2020-01-04T12:00:00Z/2020-01-20T12:00:00Z")
    box = (-20.0, 20.0, 40.0, 60.0)
    ds.density(cfg["schema"], ecql, bbox=box, width=512, height=512)
    ds.count(cfg["schema"], ecql)
    out = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        ds.density(cfg["schema"], ecql, bbox=box, width=512, height=512)
        ds.count(cfg["schema"], ecql)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(out)
    dst = os.path.join(HERE, "data", "small.xplane.pb.gz")
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    with open(src, "rb") as fi, gzip.open(dst, "wb") as fo:
        shutil.copyfileobj(fi, fo)
    shutil.rmtree(out, ignore_errors=True)
    red = trace_reduce.reduce(dst)
    with open(os.path.join(HERE, "data", "small.reduce.json"), "w") as f:
        json.dump(red, f, indent=1)
    print(json.dumps(red), os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
