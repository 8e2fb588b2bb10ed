"""Protocol conformance suite (docs/PROTOCOL.md v1).

Drives the FULL lifecycle — schema CRUD, Arrow ingest, CQL queries,
projection/limit/sampling, density, stats, BIN export, explain, audit,
selectivity counters, streaming, errors — exclusively through
``sidecar/client.py`` against a REAL subprocess server (no in-process
shortcuts), the way the GeoTools shim would. This is the compatibility
contract the JVM client (jvm/GeoMesaTpuFlightClient.java) codes against.
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.flight as fl
import pytest

from geomesa_tpu.sidecar.client import GeoFlightClient

SPEC = "name:String:index=true,speed:Float,dtg:Date,*geom:Point"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    catalog = str(tmp_path_factory.mktemp("catalog"))
    port = _free_port()
    pp = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))] + pp
    ))
    proc = subprocess.Popen(
        [sys.executable, "-m", "geomesa_tpu.cli", "serve",
         "--catalog", catalog, "--port", str(port)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
    )
    loc = f"grpc+tcp://127.0.0.1:{port}"
    deadline = time.time() + 60
    last = None
    while time.time() < deadline:
        try:
            with GeoFlightClient(loc) as c:
                c.version()
            break
        except Exception as e:  # not up yet
            last = e
            if proc.poll() is not None:
                out = proc.stdout.read().decode()
                raise RuntimeError(f"server died: {out}")
            time.sleep(0.25)
    else:
        proc.kill()
        raise RuntimeError(f"server never came up: {last}")
    yield loc
    proc.terminate()
    proc.wait(timeout=20)


@pytest.fixture()
def client(server):
    with GeoFlightClient(server) as c:
        yield c


@pytest.fixture()
def seeded(client):
    """Idempotently ensure the shared 'conf' schema exists with the
    standard table, so every test also passes in isolation (-k / xdist),
    not just in file order."""
    if "conf" not in client.list_schemas():
        client.create_schema("conf", SPEC)
        client.insert_arrow("conf", _table())
    return client


N = 5_000


def _table(n=N, seed=1):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(-120, -70, n)
    ys = rng.uniform(25, 50, n)
    flat = np.empty(2 * n)
    flat[0::2], flat[1::2] = xs, ys
    return pa.table({
        "__fid__": pa.array([f"f{i}" for i in range(n)], pa.utf8()),
        "name": pa.array([f"n{i % 5}" for i in range(n)]).dictionary_encode(),
        "speed": pa.array(rng.uniform(0, 30, n).astype(np.float32)),
        "dtg": pa.array(
            (np.datetime64("2024-05-01", "ms")
             + rng.integers(0, 20 * 86_400_000, n)), pa.timestamp("ms")
        ),
        "geom": pa.FixedSizeListArray.from_arrays(pa.array(flat), 2),
    })


CQL = "BBOX(geom, -100, 30, -80, 45) AND name = 'n1'"


def _oracle_mask(t):
    geom = np.asarray(t["geom"].combine_chunks().flatten())
    x, y = geom[0::2], geom[1::2]
    names = np.asarray(t["name"].to_pylist())
    return (x >= -100) & (x <= -80) & (y >= 30) & (y <= 45) & (names == "n1")


def test_01_version_handshake(client):
    info = client.check_version()
    assert info["protocol"] == 1


def test_02_schema_lifecycle(client):
    assert client.create_schema("lc", SPEC) == "lc"
    assert "lc" in client.list_schemas()
    desc = client.describe("lc")
    assert "name" in desc and "geom" in desc
    with pytest.raises(fl.FlightError):
        client.create_schema("lc", SPEC)  # duplicate
    client.delete_schema("lc")


def test_03_ingest_and_count(client):
    if "conf" not in client.list_schemas():
        client.create_schema("conf", SPEC)
    t = _table()
    client.insert_arrow("conf", t)
    assert client.count("conf") == N
    assert client.count("conf", CQL) == int(_oracle_mask(t).sum())


def test_04_query_cql_projection_limit(seeded):
    client = seeded
    t = _table()
    want = int(_oracle_mask(t).sum())
    got = client.query("conf", CQL)
    assert got.num_rows == want
    assert set(got["name"].to_pylist()) == {"n1"}
    # schema metadata carries the spec string (PROTOCOL §2)
    assert b"geomesa:spec" in got.schema.metadata
    proj = client.query("conf", properties=["speed"])
    assert set(proj.column_names) == {"__fid__", "speed"}
    assert client.query("conf", max_features=9).num_rows == 9
    samp = client.query("conf", sampling=10)
    assert 0 < samp.num_rows <= N // 10 + 1


def test_05_streaming_batches(seeded, server):
    """PROTOCOL §3: query results arrive as incremental record batches."""
    os.environ["GEOMESA_ARROW_BATCH_ROWS"] = "100000"
    ticket = fl.Ticket(b'{"op": "query", "schema": "conf"}')
    with GeoFlightClient(server) as c:
        reader = c._client.do_get(ticket)
        nbatches = rows = 0
        for chunk in reader:
            nbatches += 1
            rows += chunk.data.num_rows
    assert rows == N
    assert nbatches >= 1


def test_06_density(seeded):
    client = seeded
    t = _table()
    grid = client.density("conf", CQL, bbox=(-100, 30, -80, 45),
                          width=64, height=64)
    assert grid.shape == (64, 64)
    assert int(grid.sum()) == int(_oracle_mask(t).sum())


def test_07_stats(seeded):
    client = seeded
    t = _table()
    mm = client.stats("conf", "MinMax(speed)", CQL)
    speeds = np.asarray(t["speed"].to_pylist())[_oracle_mask(t)]
    v = mm.value()
    assert v["min"] == pytest.approx(float(speeds.min()), rel=1e-6)
    assert v["max"] == pytest.approx(float(speeds.max()), rel=1e-6)
    enum = client.stats("conf", "Enumeration(name)", CQL)
    assert set(enum.value().keys()) == {"n1"}


def test_08_bin_export(seeded):
    client = seeded
    t = _table()
    blob = client.export_bin("conf", CQL, track="name")
    want = int(_oracle_mask(t).sum())
    assert len(blob) == want * 16


def test_09_explain_and_audit(seeded):
    client = seeded
    plan = client.explain("conf", CQL)
    assert "Chosen index" in plan
    client.count("conf", CQL)
    evs = client.audit(5)
    assert evs
    last = evs[-1]
    # selectivity counters cross the wire (PROTOCOL §5)
    assert last["table_rows"] == N
    assert last["scanned"] >= last["hits"] > 0


def test_10_discovery(seeded):
    client = seeded
    infos = list(client._client.list_flights())
    names = [i.descriptor.path[0].decode() for i in infos]
    assert "conf" in names


def test_11_errors(seeded):
    client = seeded
    with pytest.raises(fl.FlightError, match="conf2|no schema"):
        client.count("conf2")
    with pytest.raises(fl.FlightError, match="nosuch"):
        client.count("conf", "nosuch = 3")
    with pytest.raises(fl.FlightError, match="unknown action"):
        client._action("bogus-action")


def test_12_delete_schema(client):
    # delete semantics on a data-bearing schema of its own
    client.create_schema("tmpdel", SPEC)
    client.insert_arrow("tmpdel", _table(500, seed=3))
    assert client.count("tmpdel") == 500
    client.delete_schema("tmpdel")
    assert "tmpdel" not in client.list_schemas()


def test_13_density_curve_over_wire(client):
    """PROTOCOL §3 density_curve: sparse blocks + snapped bbox metadata."""
    client.create_schema("tiles", SPEC)
    t = _table(2_000, seed=9)
    client.insert_arrow("tiles", t)
    grid, snapped = client.density_curve(
        "tiles", "BBOX(geom, -100, 30, -80, 45)", level=7,
        bbox=(-100, 30, -80, 45),
    )
    geom = np.asarray(t["geom"].combine_chunks().flatten())
    x, y = geom[0::2], geom[1::2]
    want = int(((x >= -100) & (x <= -80) & (y >= 30) & (y <= 45)).sum())
    assert int(grid.sum()) == want
    assert snapped[0] <= -100 and snapped[2] >= -80
    client.delete_schema("tiles")
