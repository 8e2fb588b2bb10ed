"""AIS vessel traffic: generator and the anchors its traffic is drawn around.

The schema is MarineCadastre.gov's daily AIS file, all seventeen columns at
their types, with LAT/LON as the point and BaseDateTime as the date. A
fixed gazetteer of US coastal ports (each with its berths, an anchorage and
a sea buoy on a coastal lane), two coastal lanes through offshore
waypoints, and ferry routes inside some ports is the same for every run.
A run's ``seed`` draws the fleet (type, MMSI, name, dimensions, home port)
and each vessel's fourteen days of stays and passages:

* cargo vessels and tankers call at ports along their coast's lane: a
  stay at a berth, out through the anchorage to the sea buoy, along the
  lane, a wait at the next port's anchorage, in to a berth;
* fishing vessels leave their home port for grounds offshore, trawl
  slowly for hours, and come back to a berth;
* passenger vessels shuttle on a ferry route from early morning to late
  evening and lie at their terminal overnight;
* tugs wait at their port's tug berth and run short assist jobs to other
  berths and to the anchorage.

A vessel at a berth or an anchorage reports from one spot, with GPS jitter,
for hours to days: thousands of pings pile up in one grid cell. Every
vessel pings once a minute at its own second, coordinates have five
decimals, and rows come in time order, as the daily files are loaded.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks import configs_common as cc

#: offshore waypoints (lon, lat) of the two coastal lanes, in order along
#: the coast
LANES = {
    "atlantic_gulf": (
        (-96.9, 27.6), (-94.55, 29.15), (-89.45, 28.8), (-88.05, 30.05),
        (-83.05, 27.55), (-82.0, 24.35), (-79.95, 25.85), (-81.2, 30.4),
        (-80.7, 31.9), (-79.6, 32.6), (-75.2, 35.0), (-75.85, 36.95),
        (-74.85, 38.75), (-73.8, 40.4), (-69.9, 40.8), (-70.7, 42.35),
    ),
    "pacific": (
        (-118.25, 33.6), (-120.8, 34.3), (-122.7, 37.75), (-124.6, 40.4),
        (-124.2, 46.25), (-124.9, 48.45), (-123.1, 48.25), (-122.45, 47.7),
    ),
}

#: (name, lane, lane waypoint index of its sea buoy, berth centre (lon,
#: lat), anchorage (lon, lat), share of the traffic, ferry route or None)
PORTS = (
    ("Los Angeles/Long Beach", "pacific", 0, (-118.235, 33.755),
     (-118.17, 33.69), 10, ((-118.275, 33.745), (-118.325, 33.345))),
    ("Oakland/San Francisco", "pacific", 2, (-122.31, 37.8),
     (-122.38, 37.76), 4, ((-122.393, 37.796), (-122.278, 37.796))),
    ("Seattle/Tacoma", "pacific", 7, (-122.35, 47.58),
     (-122.39, 47.63), 5, ((-122.34, 47.602), (-122.51, 47.623))),
    ("Corpus Christi", "atlantic_gulf", 0, (-97.4, 27.81),
     (-97.02, 27.7), 3, None),
    ("Houston", "atlantic_gulf", 1, (-95.01, 29.68),
     (-94.65, 29.25), 9, None),
    ("New Orleans", "atlantic_gulf", 2, (-90.06, 29.93),
     (-89.55, 28.9), 6, None),
    ("Mobile", "atlantic_gulf", 3, (-88.04, 30.69),
     (-88.08, 30.2), 2, None),
    ("Tampa", "atlantic_gulf", 4, (-82.44, 27.92),
     (-82.85, 27.6), 2, None),
    ("Miami/Port Everglades", "atlantic_gulf", 6, (-80.17, 25.77),
     (-80.07, 25.86), 4, ((-80.17, 25.77), (-80.12, 26.09))),
    ("Jacksonville", "atlantic_gulf", 7, (-81.56, 30.4),
     (-81.3, 30.38), 2, None),
    ("Savannah", "atlantic_gulf", 8, (-81.1, 32.09),
     (-80.8, 31.95), 6, None),
    ("Charleston", "atlantic_gulf", 9, (-79.93, 32.8),
     (-79.75, 32.68), 3, None),
    ("Norfolk", "atlantic_gulf", 11, (-76.33, 36.92),
     (-76.05, 36.95), 5, ((-76.33, 36.92), (-76.3, 36.84))),
    ("New York/New Jersey", "atlantic_gulf", 13, (-74.14, 40.67),
     (-74.03, 40.55), 10, ((-74.013, 40.701), (-74.072, 40.644))),
    ("Boston", "atlantic_gulf", 15, (-71.03, 42.36),
     (-70.95, 42.36), 2, ((-71.05, 42.36), (-70.93, 42.3))),
)

KINDS = ("cargo", "tanker", "fishing", "passenger", "tug")
#: flag-state MIDs of foreign-flagged cargo vessels and tankers (Liberia,
#: Marshall Islands, Panama, Hong Kong, Singapore, Bahamas, Malta, Greece)
FOREIGN_MIDS = (636, 538, 352, 477, 563, 311, 248, 240)
US_MIDS = (338, 366, 367, 368, 369)
NAME_A = ("ATLANTIC", "PACIFIC", "GULF", "NORTHERN", "SOUTHERN", "OCEAN",
          "SEA", "STAR", "GOLDEN", "SILVER", "BLUE", "EVER", "MAERSK",
          "CAPE", "LADY", "MISS", "CAPTAIN", "HARBOR", "BAY", "ISLAND")
NAME_B = ("SPIRIT", "PIONEER", "VOYAGER", "TRADER", "EXPRESS", "GRACE",
          "HOPE", "GLORY", "DAWN", "WIND", "RUNNER", "QUEEN", "PRIDE",
          "LEGEND", "HUNTER", "EAGLE", "CHALLENGER", "FORTUNE")
#: (VesselType range, Length, Width, Draft ranges in m) of each kind
DIMS = {
    "cargo": ((70, 79), (150, 366), (25, 51), (8.0, 15.0)),
    "tanker": ((80, 89), (180, 330), (32, 60), (10.0, 17.0)),
    "fishing": ((30, 30), (15, 40), (5, 10), (3.0, 6.0)),
    "passenger": ((60, 69), (30, 100), (10, 25), (2.0, 5.0)),
    "tug": ((52, 52), (20, 40), (8, 12), (3.0, 6.0)),
}
MOVING, AT_ANCHOR, MOORED, FISHING = 0, 1, 5, 7
MIN_PER_DAY = 1440
NM_PER_DEG = 60.0


def _nm(a, b) -> float:
    """Distance in nautical miles between two (lon, lat) points (flat
    earth at their mean latitude)."""
    c = math.cos(math.radians((a[1] + b[1]) / 2))
    return math.hypot((b[0] - a[0]) * c, b[1] - a[1]) * NM_PER_DEG


def gazetteer(cfg):
    """The fixed ports: berths, anchorage, sea buoy and share of each,
    drawn once from ``gazetteer_seed`` (not from the run's seed)."""
    a = cfg["assumed"]
    rng = np.random.default_rng(a["gazetteer_seed"])
    k, s = a["berths_per_port"], a["berth_spread_deg"]
    ports = []
    for name, lane, wi, berth, anch, share, ferry in PORTS:
        b = np.round(np.asarray(berth) + rng.uniform(-s, s, (k, 2)), 5)
        ports.append({"name": name, "lane": lane, "wi": wi, "berths": b,
                      "anch": anch, "sea": LANES[lane][wi],
                      "share": share, "ferry": ferry})
    return ports


class _Track:
    """One vessel's legs: (start minute, end minute, x0, y0, x1, y1, knots,
    status). A stay has equal ends and 0 knots; a passage lasts its
    distance over its speed."""

    def __init__(self, t0: float):
        self.t = t0
        self.pos = None
        self.legs = []

    def stay(self, p, minutes: float, status: int):
        t1 = self.t + max(1.0, minutes)
        self.legs.append((self.t, t1, p[0], p[1], p[0], p[1], 0.0, status))
        self.t = t1
        self.pos = p

    def sail(self, path, kn: float, status: int = MOVING):
        for q in path:
            d = _nm(self.pos, q)
            if d > 0:
                t1 = self.t + max(1.0, d / kn * 60.0)
                self.legs.append((self.t, t1, self.pos[0], self.pos[1],
                                  q[0], q[1], kn, status))
                self.t = t1
            self.pos = q


def _lu(rng, lo, hi) -> float:
    return math.exp(rng.uniform(math.log(lo), math.log(hi)))


def _spot(rng, p, spread):
    return (round(p[0] + rng.uniform(-spread, spread), 5),
            round(p[1] + rng.uniform(-spread, spread), 5))


def _lane_path(port_a, port_b):
    """Sea-buoy waypoints from port a's to port b's along their lane."""
    w = LANES[port_a["lane"]]
    i, j = port_a["wi"], port_b["wi"]
    step = 1 if j >= i else -1
    return [w[k] for k in range(i, j + step, step)]


def _schedule(cfg, rng, kind, home, ports, span_min):
    """The legs of one vessel from before minute 0 to past ``span_min``."""
    a = cfg["assumed"]
    p = ports[home]
    tr = _Track(-rng.uniform(0, 2 * MIN_PER_DAY))
    kn = rng.uniform(*a["transit_kn"][kind])
    hk = a["harbor_kn"]
    spread = a["anchorage_spread_deg"]
    if kind in ("cargo", "tanker"):
        lane = [q for q in ports if q["lane"] == p["lane"]]
        share = np.asarray([q["share"] for q in lane], np.float64)
        tr.pos = tuple(p["berths"][rng.integers(len(p["berths"]))])
        while tr.t < span_min:
            tr.stay(tr.pos, 60 * _lu(rng, *a["berth_hours"][kind]), MOORED)
            # the next call is at another port of the lane, where it has one
            nxt = lane[int(cc.draw(rng, share / share.sum(), 1)[0])]
            while nxt is p and len(lane) > 1:
                nxt = lane[int(cc.draw(rng, share / share.sum(), 1)[0])]
            tr.sail([p["anch"], p["sea"]], rng.uniform(*hk))
            tr.sail(_lane_path(p, nxt)[1:], kn)
            tr.sail([nxt["anch"]], rng.uniform(*hk))
            tr.stay(_spot(rng, nxt["anch"], spread),
                    60 * _lu(rng, *a["anchorage_hours"]), AT_ANCHOR)
            berth = tuple(nxt["berths"][rng.integers(len(nxt["berths"]))])
            tr.sail([berth], rng.uniform(*hk))
            p = nxt
    elif kind == "fishing":
        berth = tuple(p["berths"][rng.integers(len(p["berths"]))])
        tr.pos = berth
        while tr.t < span_min:
            tr.stay(berth, 60 * _lu(rng, *a["berth_hours"][kind]), MOORED)
            th = rng.uniform(0, 2 * math.pi)
            r = rng.uniform(*a["fishing_ground_deg"])
            ground = (p["sea"][0] + r * math.cos(th),
                      p["sea"][1] + r * math.sin(th))
            tr.sail([p["anch"], p["sea"], ground], kn)
            end = tr.t + 60 * _lu(rng, *a["fishing_trip_hours"])
            while tr.t < end:
                th = rng.uniform(0, 2 * math.pi)
                d = rng.uniform(0.02, 0.1)
                tr.sail([(tr.pos[0] + d * math.cos(th),
                          tr.pos[1] + d * math.sin(th))],
                        rng.uniform(*a["fishing_kn"]), FISHING)
            tr.sail([p["sea"], p["anch"], berth], kn)
    elif kind == "passenger":
        ends = p["ferry"]
        lo, hi = a["ferry_service_hours"]
        tr.pos = ends[0]
        while tr.t < span_min:
            day0 = math.floor(tr.t / MIN_PER_DAY) * MIN_PER_DAY
            hour = (tr.t - day0) / 60.0
            if hour < lo or hour >= hi:
                nxt = day0 + (MIN_PER_DAY if hour >= hi else 0) + lo * 60
                if tr.pos != ends[0]:
                    tr.sail([ends[0]], kn)
                tr.stay(ends[0], nxt - tr.t, MOORED)
                continue
            tr.stay(tr.pos, _lu(rng, *a["ferry_dwell_min"]), MOORED)
            tr.sail([ends[1] if tr.pos == ends[0] else ends[0]], kn)
    else:  # tug
        base = tuple(p["berths"][0])
        tr.pos = base
        while tr.t < span_min:
            tr.stay(base, 60 * _lu(rng, *a["berth_hours"][kind]), MOORED)
            job = (_spot(rng, p["anch"], spread) if rng.random() < 0.3
                   else tuple(p["berths"][rng.integers(len(p["berths"]))]))
            tr.sail([job], kn)
            end = tr.t + 60 * _lu(rng, *a["assist_hours"])
            while tr.t < end:
                tr.sail([_spot(rng, job, 0.01)], rng.uniform(*a["assist_kn"]))
            tr.sail([base], kn)
    return tr.legs


def _digits(values, width: int) -> np.ndarray:
    """Non-negative integers as fixed-width ASCII digit columns."""
    v = np.asarray(values, np.int64)
    out = np.empty((len(v), width), np.uint8)
    for k in range(width):
        out[:, width - 1 - k] = (v // 10 ** k) % 10 + 48
    return out


def fleet(cfg, rng):
    """The vessels of a run: kind, home port and static columns of each.
    The vessel count follows ``rows``: one ping a minute for ``days``."""
    a = cfg["assumed"]
    per = cfg["days"] * MIN_PER_DAY * 60 // cfg["ping_interval_s"]
    n = max(1, -(-cfg["rows"] // per))
    ports = gazetteer(cfg)
    ts = np.asarray([a["type_shares"][k] for k in KINDS], np.float64)
    kind = cc.draw(rng, ts / ts.sum(), n)
    share = np.asarray([p["share"] for p in ports], np.float64)
    ferry = np.asarray([p["ferry"] is not None for p in ports])
    home = cc.draw(rng, share / share.sum(), n)
    fshare = np.where(ferry, share, 0.0)
    home = np.where(kind == KINDS.index("passenger"),
                    cc.draw(rng, fshare / fshare.sum(), n), home)
    vtype = np.empty(n, np.int32)
    dims = np.empty((n, 3), np.float32)
    for i, k in enumerate(KINDS):
        m = kind == i
        (t0, t1), (l0, l1), (w0, w1), (d0, d1) = DIMS[k]
        vtype[m] = rng.integers(t0, t1 + 1, m.sum())
        dims[m, 0] = np.round(rng.uniform(l0, l1, m.sum()))
        dims[m, 1] = np.round(rng.uniform(w0, w1, m.sum()))
        dims[m, 2] = np.round(rng.uniform(d0, d1, m.sum()), 1)
    us = rng.random(n) < np.asarray(
        [a["us_flag_share"][KINDS[k]] for k in kind])
    mid = np.where(us, np.asarray(US_MIDS)[rng.integers(0, len(US_MIDS), n)],
                   np.asarray(FOREIGN_MIDS)[rng.integers(0, len(FOREIGN_MIDS),
                                                         n)])
    mmsi = mid * 1_000_000 + rng.permutation(1_000_000)[:n]
    big = np.isin(kind, [KINDS.index(k) for k in ("cargo", "tanker",
                                                  "passenger")])
    class_b = ((kind == KINDS.index("fishing"))
               & (rng.random(n) < a["class_b_fishing_share"]))
    names = [f"{NAME_A[rng.integers(len(NAME_A))]} "
             f"{NAME_B[rng.integers(len(NAME_B))]}"
             + (f" {rng.integers(2, 30)}" if rng.random() < 0.4 else "")
             for _ in range(n)]
    calls = [("WD" if u else "V7") + f"{rng.integers(0, 10000):04d}"
             for u in us]
    imo = [f"IMO{9_000_000 + int(rng.integers(0, 999_999))}" if b else None
           for b in big]
    cargo = np.where(np.isin(kind, [0, 1]), vtype, 0).astype(np.int32)
    return {
        "n": n, "kind": kind, "home": home, "ports": ports, "mmsi": mmsi,
        "phase_s": rng.integers(0, 60, n),
        "static": {
            "MMSI": np.asarray([str(m) for m in mmsi], dtype=object),
            "VesselName": np.asarray(names, dtype=object),
            "IMO": np.asarray(imo, dtype=object),
            "CallSign": np.asarray(calls, dtype=object),
            "VesselType": vtype,
            "Length": dims[:, 0],
            "Width": dims[:, 1],
            "Draft": dims[:, 2],
            "Cargo": cargo,
            "TransceiverClass": np.where(class_b, "B", "A").astype(object),
        },
        "class_b": class_b,
    }


def legs(cfg, rng, fl):
    """Every vessel's legs as flat arrays, in vessel order, each vessel's
    legs in time order and covering minutes 0 to the end of the data."""
    span = cfg["days"] * MIN_PER_DAY
    rows = []
    for v in range(fl["n"]):
        lv = _schedule(cfg, rng, KINDS[fl["kind"][v]], int(fl["home"][v]),
                       fl["ports"], span)
        # the first leg that covers minute 0 starts the vessel's record
        first = max(i for i, leg in enumerate(lv) if leg[0] <= 0)
        rows.extend((v,) + leg for leg in lv[first:] if leg[0] < span)
    arr = np.asarray(rows, np.float64)
    return {"vessel": arr[:, 0].astype(np.int64), "t0": arr[:, 1],
            "t1": arr[:, 2], "x0": arr[:, 3], "y0": arr[:, 4],
            "x1": arr[:, 5], "y1": arr[:, 6], "kn": arr[:, 7],
            "status": arr[:, 8].astype(np.int32)}


def _positions(lg, vessel, minute):
    """(leg index, x, y) of each (vessel, minute), without jitter."""
    span = 1 << 32
    key = lg["vessel"] * span + np.maximum(lg["t0"], 0).astype(np.int64)
    i = np.searchsorted(key, vessel * span + minute, "right") - 1
    t0 = lg["t0"][i]
    f = np.clip((minute - t0) / (lg["t1"][i] - t0), 0.0, 1.0)
    x = lg["x0"][i] + f * (lg["x1"][i] - lg["x0"][i])
    y = lg["y0"][i] + f * (lg["y1"][i] - lg["y0"][i])
    return i, x, y


def anchors(cfg, kind: str):
    """Viewport centres for the traffic: ``pings`` draws a place by its
    share of pings, as drawing a ping would: ``anchor_samples`` pings of a
    fleet drawn once from ``anchor_seed``, each with an equal share."""
    if kind != "pings":
        raise KeyError(f"ais_vessels has no anchors {kind!r}")
    a = cfg["assumed"]
    rng = np.random.default_rng(a["anchor_seed"])
    fl = fleet(cfg, rng)
    lg = legs(cfg, rng, fl)
    k = a["anchor_samples"]
    v = rng.integers(0, fl["n"], k)
    m = rng.integers(0, cfg["days"] * MIN_PER_DAY, k)
    _, x, y = _positions(lg, v, m)
    xy = np.round(np.stack([x, y], 1), 5)
    return {"xy": xy, "p": np.full(k, 1.0 / k)}


def generate(cfg, seed: int):
    """(columns for ``GeoDataset.insert`` in ingest order, fids, reference
    arrays). Rows come in time order, as the daily files are loaded."""
    a = cfg["assumed"]
    rng = np.random.default_rng(seed)
    fl = fleet(cfg, rng)
    lg = legs(cfg, rng, fl)
    per = cfg["days"] * MIN_PER_DAY
    n = cfg["rows"]
    # every vessel pings each minute; the last one's record starts late so
    # that the pings number exactly ``rows``
    vessel = np.repeat(np.arange(fl["n"], dtype=np.int64), per)
    minute = np.tile(np.arange(per, dtype=np.int64), fl["n"])
    vessel, minute = vessel[len(vessel) - n:], minute[len(minute) - n:]
    t = (cc.iso_ms(cfg["t_start"]) + minute * 60_000
         + fl["phase_s"][vessel] * 1000)
    order = np.argsort(t, kind="stable")
    vessel, minute, t = vessel[order], minute[order], t[order]
    i, x, y = _positions(lg, vessel, minute)
    del order, minute
    jit = a["jitter_deg"]
    x = np.round(x + rng.normal(0.0, jit, n), 5)
    y = np.round(y + rng.normal(0.0, jit, n), 5)
    kn = lg["kn"][i]
    moving = kn > 0
    sog = np.where(moving, kn + rng.normal(0.0, 0.3, n),
                   np.abs(rng.normal(0.0, 0.04, n)))
    sog = np.round(np.maximum(sog, 0.0), 1).astype(np.float32)
    lat = np.radians((lg["y0"][i] + lg["y1"][i]) / 2)
    course = np.degrees(np.arctan2((lg["x1"][i] - lg["x0"][i]) * np.cos(lat),
                                   lg["y1"][i] - lg["y0"][i])) % 360.0
    cog = np.where(moving, course + rng.normal(0.0, 1.5, n),
                   rng.uniform(0.0, 360.0, n))
    cog = (np.round(cog % 360.0, 1) % 360.0).astype(np.float32)
    stay_heading = rng.integers(0, 360, len(lg["t0"]))[i]
    heading = np.where(moving, np.round(course + rng.normal(0.0, 2.0, n)),
                       stay_heading).astype(np.int32) % 360
    heading = np.where(fl["class_b"][vessel], 511, heading).astype(np.int32)
    st = fl["static"]
    columns = {k: st[k][vessel] for k in st}
    columns.update({
        "Status": lg["status"][i],
        "SOG": sog,
        "COG": cog,
        "Heading": heading,
        "dtg": t.astype("datetime64[ms]"),
        "geom__x": x,
        "geom__y": y,
    })
    # "<MMSI>-<epoch seconds>", from digit tables of the vessels and of
    # the seconds of the data's span: two row gathers, not 19 digit passes
    t0_s = cc.iso_ms(cfg["t_start"]) // 1000
    fid = np.empty((n, 20), np.uint8)
    fid[:, :9] = _digits(fl["mmsi"], 9)[vessel]
    fid[:, 9] = ord("-")
    fid[:, 10:] = _digits(t0_s + np.arange(per * 60 + 60), 10)[t // 1000 - t0_s]
    fids = fid.view("S20").reshape(n)
    ref = {"x": x, "y": y, "t": t, "SOG": sog}
    return columns, fids, ref
