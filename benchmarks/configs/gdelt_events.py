"""GDELT event feed: generator and the anchors its traffic is drawn around.

The schema is the GeoMesa quick start's GDELT feature type, all fourteen
attributes at their types. GDELT geocodes each event to a place centroid,
so events pile up on exact coordinates: a fixed gazetteer of ``places``
centroids around world population hubs (drawn once from ``place_seed``,
the same for every run), with Zipf popularity; each place carries its
country, geo type and full name. Actors are a fixed list of roles in
countries. A run's ``seed`` draws the events themselves: their place,
day, actors, CAMEO event code and mention counts.
"""

from __future__ import annotations

import numpy as np

from benchmarks import configs_common as cc

#: (lon, lat, share, FIPS country of the place, CAMEO country of its actors)
#: of the hubs the gazetteer's places cluster around
HUBS = (
    (-77.0, 38.9, 8, "US", "USA"), (-74.0, 40.7, 5, "US", "USA"),
    (-87.6, 41.9, 2, "US", "USA"), (-118.2, 34.1, 3, "US", "USA"),
    (-95.4, 29.8, 1.5, "US", "USA"), (-80.2, 25.8, 1, "US", "USA"),
    (-122.4, 37.8, 1.5, "US", "USA"), (-79.4, 43.7, 1.5, "CA", "CAN"),
    (-99.1, 19.4, 1.5, "MX", "MEX"), (-58.4, -34.6, 1, "AR", "ARG"),
    (-46.6, -23.5, 1.5, "BR", "BRA"), (-74.1, 4.7, 1, "CO", "COL"),
    (-66.9, 10.5, 1, "VE", "VEN"), (-0.1, 51.5, 5, "UK", "GBR"),
    (2.35, 48.9, 3, "FR", "FRA"), (13.4, 52.5, 2.5, "GM", "DEU"),
    (12.5, 41.9, 2, "IT", "ITA"), (-3.7, 40.4, 1.5, "SP", "ESP"),
    (30.5, 50.45, 2.5, "UP", "UKR"), (37.6, 55.75, 4, "RS", "RUS"),
    (28.97, 41.0, 2, "TU", "TUR"), (35.2, 31.8, 4, "IS", "ISR"),
    (36.3, 33.5, 3, "SY", "SYR"), (44.4, 33.3, 3, "IZ", "IRQ"),
    (51.4, 35.7, 3, "IR", "IRN"), (46.7, 24.7, 1.5, "SA", "SAU"),
    (31.2, 30.0, 2, "EG", "EGY"), (3.4, 6.5, 2, "NI", "NGA"),
    (36.8, -1.3, 1.5, "KE", "KEN"), (28.0, -26.2, 1.5, "SF", "ZAF"),
    (32.5, 15.6, 1, "SU", "SDN"), (45.3, 2.0, 1, "SO", "SOM"),
    (69.2, 34.5, 3, "AF", "AFG"), (73.0, 33.7, 3, "PK", "PAK"),
    (77.2, 28.6, 4, "IN", "IND"), (72.9, 19.1, 2, "IN", "IND"),
    (90.4, 23.8, 1, "BG", "BGD"), (100.5, 13.75, 1, "TH", "THA"),
    (106.8, -6.2, 1.5, "ID", "IDN"), (121.0, 14.6, 1.5, "RP", "PHL"),
    (116.4, 39.9, 4, "CH", "CHN"), (121.5, 31.2, 2, "CH", "CHN"),
    (126.98, 37.57, 2, "KS", "KOR"), (139.7, 35.7, 2, "JA", "JPN"),
    (151.2, -33.9, 1.5, "AS", "AUS"), (174.8, -41.3, 0.5, "NZ", "NZL"),
    (-43.2, -22.9, 1, "BR", "BRA"), (18.4, -33.9, 0.5, "SF", "ZAF"),
)

#: CAMEO actor roles; an actor is a role in a country
ROLES = ("GOVERNMENT", "PRESIDENT", "POLICE", "MILITARY", "PROTESTER",
         "OPPOSITION", "REBEL", "COURT", "BUSINESS", "MEDIA", "CITIZEN",
         "PARLIAMENT", "MINISTRY", "SCHOOL", "HOSPITAL", "PARTY")

ROOT_CODES = tuple(f"{i:02d}" for i in range(1, 21))
#: the event codes of a root code: the root plus one digit ("040".."049"),
#: drawn with these shares
SUB_SHARES = (0.4, 0.15, 0.1, 0.08, 0.07, 0.06, 0.05, 0.04, 0.03, 0.02)
EVENT_CODES = tuple(r + str(d) for r in ROOT_CODES for d in range(10))


def _countries():
    """(FIPS codes, CAMEO codes, hub -> country index) of the hubs."""
    fips, cameo, of = [], [], []
    for h in HUBS:
        if h[4] not in cameo:
            fips.append(h[3])
            cameo.append(h[4])
        of.append(cameo.index(h[4]))
    return fips, cameo, np.asarray(of)


def places(cfg):
    """The gazetteer, fixed by the configuration (not by the run's seed):
    (x, y) centroids, Zipf popularity, country index, ActionGeo_Type and
    full name of each place."""
    a = cfg["assumed"]
    rng = np.random.default_rng(a["place_seed"])
    n = a["places"]
    hubs = np.asarray([h[:3] for h in HUBS], np.float64)
    share = hubs[:, 2] / hubs[:, 2].sum()
    h = rng.choice(len(hubs), n, p=share)
    r = np.abs(rng.standard_normal(n)) * a["place_sigma_deg"]
    th = rng.uniform(0, 2 * np.pi, n)
    x = hubs[h, 0] + r * np.cos(th)
    y = np.clip(hubs[h, 1] + r * np.sin(th) * 0.7, -60.0, 75.0)
    x = (x + 180.0) % 360.0 - 180.0
    xy = np.round(np.stack([x, y], 1), 4)
    rank = rng.permutation(n) + 1
    p = rank.astype(np.float64) ** -a["place_zipf_exponent"]
    fips, _, of = _countries()
    country = of[h]
    # GDELT geo types: 1 country, 2 US state, 3 US city, 4 world city,
    # 5 world state
    us = np.asarray([fips[c] == "US" for c in country])
    kind = rng.choice(3, n, p=a["geo_type_shares"])  # country, state, city
    gtype = np.where(kind == 0, 1, np.where(
        kind == 1, np.where(us, 2, 5), np.where(us, 3, 4))).astype(np.int32)
    names = np.asarray([f"Place {i}, {fips[c]}" if g != 1 else fips[c]
                        for i, (c, g) in enumerate(zip(country, gtype))],
                       dtype=object)
    return {"xy": xy, "p": p / p.sum(), "country": country, "type": gtype,
            "name": names}


def anchors(cfg, kind: str):
    """Viewport centres for the traffic: ``events`` draws a place by its
    event share, as drawing an event would."""
    if kind != "events":
        raise KeyError(f"gdelt_events has no anchors {kind!r}")
    g = places(cfg)
    return {"xy": g["xy"], "p": g["p"]}


def _actors(rng, a, n, local_country):
    """Actor codes (index into the actor list, -1 for none) of ``n``
    events: present with the configured share, from the event's country
    with the configured share, else from any country."""
    _, cameo, _ = _countries()
    nc = len(cameo)
    role = cc.draw(rng, np.asarray(a["role_shares"], np.float64), n)
    other = rng.integers(0, nc, n)
    country = np.where(rng.random(n) < a["actor_local_share"],
                       local_country, other)
    code = country * len(ROLES) + role
    return np.where(rng.random(n) < a["actor_present_share"], code, -1)


def _vocab(values, codes):
    """Object array of ``values[code]`` with "" for code -1 (a column of
    shared string objects, as a client hands the store)."""
    v = np.asarray(list(values) + [""], dtype=object)
    return v[codes]


def generate(cfg, seed: int):
    """(columns for ``GeoDataset.insert`` in ingest order, fids, reference
    arrays). Rows come in day order, as a daily feed is loaded."""
    a = cfg["assumed"]
    rng = np.random.default_rng(seed)
    n, days = cfg["rows"], cfg["days"]
    g = places(cfg)
    fips, cameo, _ = _countries()
    per_day = rng.multinomial(n, np.full(days, 1.0 / days))
    day = np.repeat(np.arange(days, dtype=np.int64), per_day)
    t = cc.iso_ms(cfg["t_start"]) + day * 86_400_000
    place = cc.draw(rng, g["p"], n)
    root = cc.draw(rng, np.asarray([a["root_code_shares"][c]
                                    for c in ROOT_CODES]), n)
    sub = cc.draw(rng, np.asarray(SUB_SHARES), n)
    event = (root * 10 + sub).astype(np.int32)
    mentions = (1 + rng.geometric(a["mentions_p"], n)).astype(np.int32)
    sources = (1 + rng.binomial(mentions - 1, 0.3)).astype(np.int32)
    articles = (mentions + rng.binomial(mentions, 0.2)).astype(np.int32)
    country = g["country"][place]
    actor1 = _actors(rng, a, n, country)
    actor2 = _actors(rng, a, n, country)
    actors = [f"{c} {r}" for c in cameo for r in ROLES]
    x, y = g["xy"][place, 0], g["xy"][place, 1]
    fids = cc.digit_ids(a["first_event_id"], n)
    columns = {
        "GLOBALEVENTID": fids.astype("U9").astype(object),
        "Actor1Name": _vocab(actors, actor1),
        "Actor1CountryCode": _vocab(cameo, np.where(
            actor1 < 0, -1, actor1 // len(ROLES))),
        "Actor2Name": _vocab(actors, actor2),
        "Actor2CountryCode": _vocab(cameo, np.where(
            actor2 < 0, -1, actor2 // len(ROLES))),
        "EventCode": _vocab(EVENT_CODES, event),
        "NumMentions": mentions,
        "NumSources": sources,
        "NumArticles": articles,
        "ActionGeo_Type": g["type"][place],
        "ActionGeo_FullName": g["name"][place],
        "ActionGeo_CountryCode": _vocab(fips, country),
        "dtg": t.astype("datetime64[ms]"),
        "geom__x": x,
        "geom__y": y,
    }
    ref = {"x": x, "y": y, "t": t, "NumMentions": mentions}
    return columns, fids, ref
