"""The one traffic generator, and the client process that drives it.

A traffic mix (``benchmarks/traffic/<mix>.json``) is data that this module
reads: the ops and their shares, viewport sizes around the configuration's
anchors, time-window lengths, the request pool, and the closed loop of N
clients.

The program under test compiles one kernel per distinct query text, so a
run cannot send requests it has never compiled without compiling inside
the window. The mix therefore draws a POOL of distinct requests, fixed by
its ``pool_seed`` (the same for every run), which set-up visits once each;
a run's ``--seed`` draws the data and the order in which the pool is sent
(cyclic permutations, so a request returns only after every other one has
been sent). A pool larger than the store's 64-entry window, layout and
schedule caches makes every visit of the window resolve its view anew, as
a new viewport would; only the plan cache and the compiled kernels hit.

Run as a script (``python benchmarks/loadgen.py <job.json>``) it is the
client process: it never touches the chip (``JAX_PLATFORMS=cpu``), visits
the pool through the Flight client, then, told ``go`` on stdin, drives the
timed window and writes every request's record and kept answer.
"""

from __future__ import annotations

import json
import math
import os
import sys
import threading
import time

import numpy as np

DAY_MS = 86_400_000


# ---------------------------------------------------------------------------
# the pool (parent side; pure numpy)
# ---------------------------------------------------------------------------
def spec_fields(spec: str):
    """(geometry attribute, date attribute, {attribute: type}) of a
    schema spec string."""
    types, geom, dtg = {}, None, None
    for part in spec.split(","):
        bits = part.split(":")
        name, typ = bits[0], bits[1]
        if name.startswith("*"):
            name = name[1:]
            geom = name
        types[name] = typ
        if typ == "Date" and dtg is None:
            dtg = name
        if typ == "Point" and geom is None:
            geom = name
    return geom, dtg, types


def _draw_len(rng, lo: float, hi: float, dist: str) -> float:
    """A length in [lo, hi]: ``uniform``, ``log_uniform``, or ``pow2`` (a
    power of two, each exponent in the range equally likely)."""
    if lo == hi:
        return lo
    if dist == "log_uniform":
        return math.exp(rng.uniform(math.log(lo), math.log(hi)))
    if dist == "pow2":
        k = rng.integers(math.ceil(math.log2(lo)),
                         math.floor(math.log2(hi)) + 1)
        return float(2.0 ** k)
    return rng.uniform(lo, hi)


def _window(rng, cfg, days, dist):
    """(t0, t1) epoch ms: a window of the drawn length whose ends sit on
    the configuration's grain plus offset, so no stored timestamp falls on
    an end."""
    from benchmarks.configs_common import iso_ms

    g, off = cfg["window_grain_ms"], cfg["window_offset_ms"]
    start = iso_ms(cfg["t_start"])
    span = cfg["days"] * DAY_MS
    length = max(g, int(round(_draw_len(rng, *days, dist) * DAY_MS / g)) * g)
    k_lo = max(0, -(off // g))
    k_hi = max(k_lo, (span - length - off) // g)
    t0 = start + int(rng.integers(k_lo, k_hi + 1)) * g + off
    return t0, t0 + length


def _viewport(rng, anchors, vp):
    """A [x0, y0, x1, y1] box of drawn width around a drawn anchor, moved
    inside the world so that its size is kept."""
    from benchmarks.configs_common import draw

    i = int(draw(rng, anchors["xy_p"], 1)[0])
    cx, cy = anchors["xy"][i]
    w = _draw_len(rng, *vp["width_deg"], vp.get("dist", "uniform"))
    h = min(w * vp.get("aspect", 1.0), 180.0)
    w = min(w, 360.0)
    x0 = min(max(cx - w / 2, -180.0), 180.0 - w)
    y0 = min(max(cy - h / 2, -90.0), 90.0 - h)
    return [round(float(v), 4) for v in (x0, y0, x0 + w, y0 + h)]


def _finish(req, geom, dtg):
    """Fill the request's ECQL from its structured filter."""
    from benchmarks.configs_common import ms_iso

    req["ecql"] = (f"BBOX({geom}, " + ", ".join(repr(v) for v in req["bbox"])
                   + f") AND {dtg} DURING {ms_iso(req['t0'])}/"
                   f"{ms_iso(req['t1'])}")
    return req


def _op_fields(op, req):
    req["op"] = op["op"]
    if op["op"] == "density":
        req["grid"] = list(op["grid"])
        req["weight"] = op.get("weight")
    elif op["op"] == "stats":
        req["stat"] = op["stat"]
    return req


def _pick_op(rng, ops):
    shares = np.asarray([o["share"] for o in ops], np.float64)
    return ops[int(rng.choice(len(ops), p=shares / shares.sum()))]


def build_pool(traffic, cfg, cfg_mod):
    """The pool's distinct requests, fixed by the mix's ``pool_seed``; no
    run seed enters."""
    rng = np.random.default_rng(traffic["pool_seed"])
    geom, dtg, _ = spec_fields(cfg["spec"])
    a = dict(cfg_mod.anchors(cfg, traffic["viewport"]["anchor"]))
    a["xy_p"] = a["p"]
    reqs = []
    for _ in range(traffic["pool"]):
        op = _pick_op(rng, traffic["ops"])
        req = {}
        req["t0"], req["t1"] = _window(rng, cfg, traffic["window_days"],
                                       traffic.get("window_dist", "uniform"))
        req["bbox"] = _viewport(rng, a, traffic["viewport"])
        _op_fields(op, req)
        reqs.append(_finish(req, geom, dtg))
    return reqs


def cyclic(seed: int, n_items: int):
    """Pool items in cyclic seed-drawn permutations, without end: every
    item is sent once before any is sent again."""
    rng = np.random.default_rng([seed, 7])
    while True:
        yield from rng.permutation(n_items).tolist()


# ---------------------------------------------------------------------------
# the client process
# ---------------------------------------------------------------------------
def _send(client, schema, req):
    """Send one request; return its decoded answer."""
    op = req["op"]
    if op == "density":
        W, H = req["grid"]
        return client.density(schema, req["ecql"], bbox=req["bbox"],
                              width=W, height=H, weight=req.get("weight"))
    if op == "count":
        return int(client.count(schema, req["ecql"]))
    if op == "stats":
        cs, mm = client.stats(schema, req["stat"], req["ecql"]).stats
        lo = float("nan") if mm.lo is None else float(mm.lo)
        hi = float("nan") if mm.hi is None else float(mm.hi)
        return (int(cs.count), lo, hi)
    raise ValueError(f"unknown op {op!r}")


class _Recorder:
    """Thread-safe log of the window's requests and kept answers. A kept
    answer (grid, stats) is stored once per pool request and digest: a
    repeat that equals it bit for bit points at the stored copy."""

    def __init__(self, trace: bool):
        self.lock = threading.Lock()
        self.records = []
        self.kept = {}
        self.first = {}
        self.trace = trace

    def run(self, client, schema, req, rid, extra):
        from benchmarks import answers

        t_send = time.monotonic()
        rec = dict(extra, pool=rid, t_send=t_send)
        try:
            ans = _send(client, schema, req)
            rec["t_done"] = time.monotonic()
            rec["ok"] = True
            kept = answers.keep(req["op"], ans)
            rec["digest"] = (answers.digest(req["op"], ans) if kept is None
                             else answers.kept_digest(kept))
        except Exception as e:  # a failed request is recorded, not fatal
            rec["t_done"] = time.monotonic()
            rec["ok"] = False
            rec["err"] = repr(e)[:300]
            kept = None
        if self.trace:
            from geomesa_tpu import tracing

            tr = tracing.pop_thread_trace()
            rec["trace_id"] = tr.trace_id if tr is not None else None
        with self.lock:
            rec["seq"] = len(self.records)
            self.records.append(rec)
            if kept is not None:
                key = (rid, rec["digest"])
                if key not in self.first:
                    self.first[key] = rec["seq"]
                    self.kept[rec["seq"]] = kept
                rec["kept"] = self.first[key]
        return rec


def _drive(job, reqs, seed, seconds, rec_sink, clients):
    """N closed-loop clients for ``seconds``, each sending the next request
    of the shared seed-drawn order when the reply to its last one arrived;
    returns (t_start, t_end) once every request sent in the window has its
    reply (a minute past the close at most)."""
    order = cyclic(seed, len(reqs))
    lock = threading.Lock()
    t0 = time.monotonic()
    t_end = t0 + seconds

    def loop(c):
        prev_done = None
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            with lock:
                rid = next(order)
            r = rec_sink.run(clients[c], job["schema"], reqs[rid], rid,
                             {"client": c, "t_due": now,
                              "late": 0.0 if prev_done is None
                              else now - prev_done})
            prev_done = r["t_done"]

    threads = [threading.Thread(target=loop, args=(c,), daemon=True)
               for c in range(len(clients))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=seconds + 60)
    return t0, t_end


def client_main(job_path: str) -> int:
    with open(job_path) as f:
        job = json.load(f)
    from geomesa_tpu.sidecar.client import GeoFlightClient

    reqs = job["requests"]
    clients = [GeoFlightClient(job["location"])
               for _ in range(job["clients"])]
    warm = _Recorder(False)
    # every pool request once, one at a time: its kernel compiles here
    for rid, req in enumerate(reqs):
        warm.run(clients[0], job["schema"], req, rid, {})
    # the window's own pattern, untimed: requests that arrive together
    # fuse, and their batch kernels compile here
    _drive(job, reqs, job["seed"] + 1, job["warm_seconds"], warm, clients)
    bad = [r for r in warm.records if not r["ok"]]
    first = [(r["t_done"] - r["t_send"]) * 1e3
             for r in warm.records[:len(reqs)]]
    print(f"warm {len(warm.records)} requests, {len(bad)} failed; first "
          f"visits (ms): p50 {np.percentile(first, 50):.1f} max "
          f"{max(first):.1f} total {sum(first) / 1e3:.1f} s", flush=True)
    for r in bad[:3]:
        print(f"warm-error {r['err']}", file=sys.stderr, flush=True)
    if sys.stdin.readline().strip() != "go":
        return 1
    rec = _Recorder(job["trace"])
    t0, t_end = _drive(job, reqs, job["seed"], job["seconds"], rec, clients)
    print("closed", flush=True)
    arrays = {}
    for seq, kept in rec.kept.items():
        for k, v in kept.items():
            arrays[f"{seq}_{k}"] = v
    np.savez(job["out"] + ".npz", **arrays)
    with open(job["out"] + ".json", "w") as f:
        json.dump({"t_start": t0, "t_end": t_end, "records": rec.records}, f)
    for c in clients:
        c.close()
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(client_main(sys.argv[1]))
