"""One run of one benchmark cell, driven by the data under ``benchmarks/``.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. Everything that belongs to one of them is found by name:

* ``benchmarks/configs/<config>.json`` (sizes) and ``<config>.py``
  (generator and traffic anchors);
* ``benchmarks/traffic/<mix>.json`` (parameters of the one generator in
  ``loadgen.py``);
* ``benchmarks/layer_metrics/<metric>.py`` (a ``read(ctx)`` that returns
  the metric, or None when the run holds nothing to read);
* ``benchmarks/limits/<cell>.json`` (the limit of each number compared).

The run: data from the seed, ingest through ``GeoDataset.insert``, an
in-process ``GeoFlightServer`` at its defaults, a client process that
warms the pool and then drives the window, the device trace (``--trace
1``), the plain reference, and one result line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 10.0
INGEST_CHUNK = 5_000_000


class BenchmarkError(RuntimeError):
    """The run cannot produce a result (wrong device, broken client)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything one workload of ``BENCHMARK.json`` names, found by name."""

    def __init__(self, name: str, bench: str = ""):
        root = ROOT
        bench = load_json(bench or os.path.join(root, "BENCHMARK.json"))
        wl = [w for w in bench["workloads"] if w["name"] == name]
        if not wl:
            raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json")
        self.workload = wl[0]
        self.name = name
        entry = [c for c in bench["configs"]
                 if c["name"] == self.workload["config"]][0]
        self.cfg = load_json(os.path.join(root, entry["file"]))
        self.cfg_mod = load_module(
            os.path.join(root, entry["file"][:-len(".json")] + ".py"),
            f"bench_config_{entry['name']}")
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.workload["traffic"] + ".json"))
        self.limits = load_json(os.path.join(HERE, "limits", name + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def reader(self, metric: str):
        return load_module(os.path.join(HERE, "layer_metrics", metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))


# ---------------------------------------------------------------------------
# the program's counters and spans
# ---------------------------------------------------------------------------
def counters():
    """The program's counters and histograms the layer metrics read."""
    from geomesa_tpu import metrics

    reg = metrics.registry()
    qw = reg.histogram(metrics.SERVING_QUEUE_WAIT).snapshot()
    return {
        "dispatch": reg.counter(metrics.EXEC_DEVICE_DISPATCH).value,
        "recompiles": reg.counter(metrics.KERNEL_RECOMPILES).value,
        "queue_wait_n": qw["count"],
        "queue_wait_s": qw["sum_s"],
    }


def span_trees(records):
    """Each window request's finished server span trees, by its trace id."""
    from geomesa_tpu import tracing

    out = {}
    for r in records:
        tid = r.get("trace_id")
        if tid:
            out[r["seq"]] = [t["tree"] for t in tracing.finished_traces(tid)]
    return out


def exec_paths(ds, t0: float, t1: float):
    """Counts of the scan path and density kernel that served the
    window's queries (audit events of the window)."""
    scans, kernels = {}, {}
    for ev in ds.audit.recent(ds.audit.events.maxlen):
        if not (t0 <= ev.date <= t1):
            continue
        path = ev.hints.get("exec_path") or {}
        s = str(path.get("scan", "none"))
        scans[s] = scans.get(s, 0) + 1
        k = path.get("density_kernel")
        if k:
            kernels[k] = kernels.get(k, 0) + 1
    return scans, kernels


# ---------------------------------------------------------------------------
# the client process
# ---------------------------------------------------------------------------
class Client:
    """The load-generator process and its line protocol."""

    def __init__(self, job: dict, workdir: str, trace: bool):
        path = os.path.join(workdir, "job.json")
        job["out"] = os.path.join(workdir, "results")
        with open(path, "w") as f:
            json.dump(job, f)
        self.out = job["out"]
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env["GEOMESA_TRACE_ENABLED"] = "true" if trace else "false"
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=ROOT, env=env)
        self.lines = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            self.lines.put(line.strip())
        self.lines.put(None)

    def expect(self, word: str, timeout: float) -> str:
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=max(deadline - time.monotonic(),
                                                  0.01))
            except queue.Empty:
                raise BenchmarkError(f"client sent no {word!r} in "
                                     f"{timeout:.0f} s") from None
            if line is None:
                raise BenchmarkError(f"client exited before {word!r} "
                                     f"(code {self.proc.wait()})")
            if line.split(" ")[0] == word:
                return line

    def send(self, text: str):
        self.proc.stdin.write(text + "\n")
        self.proc.stdin.flush()

    def results(self):
        data = load_json(self.out + ".json")
        with np.load(self.out + ".npz") as z:
            kept = {k: z[k] for k in z.files}
        return data, kept

    def stop(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()  # a client waiting for "go" exits
            except OSError:
                pass
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def compare(pool, records, kept, answer_of):
    """The numbers compared: answers that differ from the reference (every
    answer is exact: counts, stats, and grids, whose integer weights sum
    exactly in f32), and failed requests."""
    from benchmarks import answers

    off, failed = 0, 0
    worst = None
    verdicts = {}

    def judge(r):
        """Whether one kept answer (grid or stats) is off the reference."""
        req, ref, seq = pool[r["pool"]], answer_of(r["pool"]), r["kept"]
        want = np.asarray(ref, np.float64)
        if req["op"] == "stats":
            got = np.asarray(kept[f"{seq}_stat"], np.float64)
            return not np.array_equal(got, want, equal_nan=True)
        want = want.reshape(-1)
        grid = np.zeros_like(want)
        grid[kept[f"{seq}_idx"]] = kept[f"{seq}_val"]
        return not np.array_equal(grid, want)

    for r in records:
        if not r["ok"]:
            failed += 1
            continue
        req = pool[r["pool"]]
        if "kept" in r:
            if r["kept"] not in verdicts:
                verdicts[r["kept"]] = judge(r)
            bad = verdicts[r["kept"]]
        else:
            bad = r["digest"] != answers.digest(req["op"],
                                                answer_of(r["pool"]))
        if bad:
            off += 1
            worst = worst or (r["pool"], req["ecql"])
    return {"answers_off": off, "failed": failed}, worst


def control_records(pool, records, data):
    """The control: every answer replaced by the bf16 reference's answer
    to the same request (the reference put in the program's place)."""
    from benchmarks import answers, reference

    cache, kept = {}, {}
    out = []
    for r in records:
        r = dict(r)
        if r["ok"]:
            i = r["pool"]
            if i not in cache:
                cache[i] = reference.answer(data, pool[i], "bf16")
            req = pool[i]
            keep = answers.keep(req["op"], cache[i])
            if keep is None:
                r["digest"] = answers.digest(req["op"], cache[i])
            else:
                r["kept"] = r["seq"]
                for k, v in keep.items():
                    kept[f"{r['seq']}_{k}"] = v
        out.append(r)
    return out, kept


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def check_device(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise BenchmarkError(
            f"needs {chips} TPU chip(s); jax found {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs


def _pct(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def setup(cell: Cell, seed: int, trace: bool):
    """Data from the seed, ingested through ``GeoDataset.insert``, and an
    in-process Flight server at its defaults. Returns (server, dataset,
    reference arrays, seconds of data and of ingest)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ.setdefault("GEOMESA_COMPILE_CACHE_DIR",
                              os.path.join(HERE, ".jax_cache"))
    if trace:
        os.environ["GEOMESA_TRACE_ENABLED"] = "true"
        os.environ["GEOMESA_TRACE_JAX_PROFILER"] = "true"
        os.environ["GEOMESA_TRACE_RETAIN"] = "1000000"
    from geomesa_tpu import GeoDataset
    from geomesa_tpu.sidecar.service import GeoFlightServer

    cfg = cell.cfg
    t0 = time.monotonic()
    columns, fids, data = cell.cfg_mod.generate(cfg, seed)
    t1 = time.monotonic()
    ds = GeoDataset()
    ds.create_schema(cfg["schema"], cfg["spec"])
    n = len(fids)
    for lo in range(0, n, INGEST_CHUNK):
        hi = min(lo + INGEST_CHUNK, n)
        ds.insert(cfg["schema"], {k: v[lo:hi] for k, v in columns.items()},
                  fids=fids[lo:hi])
    ds.flush(cfg["schema"])
    del columns, fids
    secs = {"data": t1 - t0, "ingest": time.monotonic() - t1}
    return GeoFlightServer(ds, "grpc+tcp://127.0.0.1:0"), ds, data, secs


class Window:
    """One timed window: the client visits the pool, the window runs, and
    the program's counters, spans and (``trace``) the device trace of its
    first seconds are read."""

    def __init__(self, cell, srv, ds, devs, pool, seed, seconds, trace):
        traffic = cell.traffic
        job = {
            "location": f"grpc+tcp://127.0.0.1:{srv.port}",
            "schema": cell.cfg["schema"], "requests": pool,
            "clients": int(traffic["clients"]), "seed": seed,
            "seconds": seconds,
            "warm_seconds": float(traffic.get("warm_seconds", 5)),
            "trace": trace,
        }
        self.workdir = tempfile.mkdtemp(prefix="geomesa-bench-")
        self.prof = prof = {"lock": threading.Lock()}
        client = Client(job, self.workdir, trace)
        try:
            self.warm = client.expect("warm", 1100)
            self.before = counters()
            self.wall0 = time.time()
            if trace and devs[0].platform == "tpu":
                import jax

                prof["dir"] = os.path.join(self.workdir, "profile")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # keep the trace small
                opts.host_tracer_level = 2     # the geomesa: annotations
                jax.profiler.start_trace(prof["dir"], profiler_options=opts)
                prof["t0"] = time.monotonic()
                timer = threading.Timer(TRACE_SECONDS, _stop_profile, (prof,))
                timer.start()
            client.send("go")
            client.expect("closed", seconds + 120)
            self.after = counters()
            self.wall1 = time.time()
            if prof.get("dir"):
                timer.cancel()
                _stop_profile(prof)
            client.expect("done", 120)
            self.results, self.kept = client.results()
            stats = devs[0].memory_stats() or {}
            self.peak = int(stats.get("peak_bytes_in_use", 0))
            self.scans, self.kernels = exec_paths(ds, self.wall0, self.wall1)
            self.spans = (span_trees(self.results["records"]) if trace
                          else {})
        finally:
            if prof.get("dir") and prof.get("t1") is None:
                _stop_profile(prof)
            client.stop()

    def close(self):
        shutil.rmtree(self.workdir, ignore_errors=True)


def judge(checks: dict, limits: dict):
    """(correct, each number with its limit)."""
    shown = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown


def run(name: str, seed: int, seconds: float, trace: bool, t_proc0: float,
        require_tpu: bool = True, control: bool = False, out=sys.stdout,
        err=sys.stderr, cell: "Cell | None" = None) -> dict:
    """Run one cell; print the info lines, the compared numbers and the
    result line; return the result. With ``control`` the bf16 control's
    numbers, compared against the same limits, are printed on info lines
    and returned under ``control``."""
    cell = cell or Cell(name)
    devs = check_device(cell.workload["chips"], require_tpu)
    from benchmarks import loadgen, reference, roofline, trace_reduce

    cfg = cell.cfg
    srv, ds, data, secs = setup(cell, seed, trace)
    try:
        pool = loadgen.build_pool(cell.traffic, cfg, cell.cfg_mod)
        win = Window(cell, srv, ds, devs, pool, seed, seconds, trace)
    finally:
        srv.shutdown()
    del srv, ds
    results, kept, prof = win.results, win.kept, win.prof
    before, after = win.before, win.after

    records = results["records"]
    refs = {}

    def answer_of(i):
        if i not in refs:
            refs[i] = reference.answer(data, pool[i])
        return refs[i]

    checks, worst = compare(pool, records, kept, answer_of)
    ctl = None
    if control:
        c_records, c_kept = control_records(pool, records, data)
        c_correct, c_shown = judge(compare(pool, c_records, c_kept,
                                           answer_of)[0], cell.limits)
        ctl = {"correct": c_correct, "checks": c_shown}
    matched = {i: reference.matched_rows(data, pool[i])
               for i in sorted({r["pool"] for r in records})}
    geom, dtg, types = loadgen.spec_fields(cfg["spec"])
    t0, t1 = results["t_start"], results["t_end"]
    ok = [r for r in records if r["ok"]]
    lat = [(r["t_done"] - r["t_due"]) * 1e3 for r in ok]
    late = [r["late"] * 1e3 for r in records]
    mrows = [matched[r["pool"]] for r in records]
    print(f"# set-up (s): data {secs['data']:.2f}, ingest "
          f"{secs['ingest']:.2f}, total {t0 - t_proc0:.2f}", file=out)
    print(f"# warm-up: {win.warm}; compiles in window: "
          f"{after['recompiles'] - before['recompiles']}", file=out)
    print(f"# scan paths in window: {json.dumps(win.scans)}; density "
          f"kernels: {json.dumps(win.kernels)}", file=out)
    print(f"# matched rows per request: mean {np.mean(mrows or [0]):.1f} "
          f"min {min(mrows or [0])} max {max(mrows or [0])} over "
          f"{len(records)} requests", file=out)
    print(f"# generator late (ms): mean {np.mean(late or [0]):.3f} "
          f"max {max(late or [0]):.3f}", file=out)
    if worst:
        print(f"# first answer off: pool {worst[0]}: {worst[1]}", file=out)
    bad = [r for r in records if not r["ok"]]
    if bad:
        print(f"# first failed request: pool {bad[0]['pool']}: "
              f"{bad[0]['err']}", file=out)
    if ctl:
        print(f"# control (bf16 reference in the program's place): correct "
              f"{str(ctl['correct']).lower()}; " + "; ".join(
                  f"{k} {v['value']} limit {v['limit']}"
                  for k, v in ctl["checks"].items()), file=out)

    result = {"correct": True, "attempted": len(records),
              "failed": checks["failed"], "metrics": {}}
    if not trace:
        e2e = {
            "query_qps": sum(1 for r in ok if r["t_done"] <= t1) / (t1 - t0),
            "latency_p50_ms": _pct(lat, 50) if lat else None,
            "latency_p95_ms": _pct(lat, 95) if lat else None,
            "setup_s": t0 - t_proc0,
        }
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": win.peak}
    if trace:
        red = None
        if prof.get("dir"):
            red = trace_reduce.reduce(trace_reduce.find_xplane(prof["dir"]))
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
        ctx = Context(cell, pool, records, before, after, win.spans, red,
                      prof, matched, types, geom, dtg, devs[0].device_kind,
                      roofline)
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    win.close()
    result["device"] = device
    result["correct"], result["checks"] = judge(checks, cell.limits)
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    if ctl:
        result["control"] = ctl
    return result


def _stop_profile(prof):
    import jax

    with prof["lock"]:
        if prof.get("t1") is None:
            prof["t1"] = time.monotonic()
            jax.profiler.stop_trace()


class Context:
    """What a per-layer metric reads: the window's requests and their
    server span trees, the program's counters before and after the
    window, the device-trace reduction and its capture times, and the
    reference's matched rows per pool request."""

    def __init__(self, cell, pool, records, before, after, spans, trace,
                 prof, matched, types, geom, dtg, device_kind, roofline):
        self.cell = cell
        self.pool = pool
        self.records = records
        self.before, self.after = before, after
        self.spans = spans
        self.trace = trace
        self.trace_t0, self.trace_t1 = prof.get("t0"), prof.get("t1")
        self.matched = matched
        self.types, self.geom, self.dtg = types, geom, dtg
        self.device_kind = device_kind
        self.roofline = roofline

    def delta(self, key: str) -> float:
        return self.after[key] - self.before[key]

    def ok_records(self):
        return [r for r in self.records if r["ok"]]


def walk(tree):
    """Every span of a span-tree dict, depth first."""
    yield tree
    for c in tree.get("children", ()):
        yield from walk(c)


def span_sum(ctx: Context, names) -> float:
    """Total ms of the named spans over the window's requests."""
    total = 0.0
    for trees in ctx.spans.values():
        for t in trees:
            total += sum(s["ms"] for s in walk(t) if s["name"] in names)
    return total


def self_ms(span) -> float:
    return span["ms"] - sum(c["ms"] for c in span.get("children", ()))


def per_request(total: float, ctx: Context):
    n = len(ctx.spans)
    return total / n if n else None
