"""Warm-path executor proof (docs/PERF.md): shape-bucketed, version-stable,
LRU-managed kernel caching + the double-buffered partition pipeline.

The contract under test:

* two same-shape queries compile once (registry hit on the repeat);
* distinct-but-same-bucket queries share one compiled kernel (the kNN
  kernel parameterizes location/radius as traced scalars, and shape
  bucketing folds their differing window counts into one K bucket);
* a store MUTATION does not recompile anything (kernel keys carry no store
  version — only the dictionary-growth fingerprint);
* dictionary growth DOES recompile (string predicates bake resolved codes
  into the closure — reusing it across growth would be a stale-closure bug);
* the partition prefetch pipeline returns bit-identical results to
  sequential execution, and the whole warm path is bit-identical to a cold
  run with bucketing + pipeline disabled.

These are the tier-1 recompile-regression tests: fast, CPU-only, no TPU.
"""

import os

import numpy as np
import pytest

from geomesa_tpu import GeoDataset, config, metrics
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.kernels.registry import KernelRegistry, bucket_count


def _recompiles() -> int:
    return metrics.registry().counter(metrics.KERNEL_RECOMPILES).value


def _hits() -> int:
    return metrics.registry().counter(metrics.KERNEL_BUCKET_HIT).value


def _mk_data(n: int, seed: int = 11, names=("a", "b", "c")):
    rng = np.random.default_rng(seed)
    lo = parse_iso_ms("2020-01-01")
    hi = parse_iso_ms("2020-02-01")
    return {
        "name": rng.choice(list(names), n),
        "geom__x": rng.uniform(-120, -70, n),
        "geom__y": rng.uniform(25, 50, n),
        "dtg": rng.integers(lo, hi, n).astype("datetime64[ms]"),
        "weight": rng.uniform(0, 1, n).astype(np.float32),
    }


def _mk_ds(n: int = 20_000, partitioned: bool = False, seed: int = 11):
    spec = "name:String,weight:Float,dtg:Date,*geom:Point"
    if partitioned:
        spec += ";geomesa.partition='time'"
    ds = GeoDataset(n_shards=4)
    ds.create_schema("t", spec)
    ds.insert("t", _mk_data(n, seed), fids=np.arange(n).astype(str))
    ds.flush("t")
    return ds


DURING = "dtg DURING 2020-01-05T00:00:00Z/2020-01-25T00:00:00Z"


def _bbox_q(x0, y0, x1, y1):
    return f"BBOX(geom, {x0}, {y0}, {x1}, {y1}) AND {DURING}"


# ---------------------------------------------------------------------------
# registry unit behavior
# ---------------------------------------------------------------------------


def test_bucket_count_ladder():
    with config.COMPACT_BUCKETING.scoped("true"), \
            config.COMPACT_BUCKET_FLOOR.scoped("8"):
        # everything at or below the floor shares one bucket
        assert [bucket_count(k) for k in (0, 1, 2, 5, 8)] == [8] * 5
        # above the floor: powers of two
        assert bucket_count(9) == 16
        assert bucket_count(16) == 16
        assert bucket_count(17) == 32
    with config.COMPACT_BUCKETING.scoped("false"):
        # old behavior: exact pow2, no floor
        assert bucket_count(1) == 1
        assert bucket_count(3) == 4


def test_kernel_registry_lru_evicts_one_at_a_time():
    reg = KernelRegistry(capacity=2)
    reg.put(("site_a", 1), "k1")
    reg.put(("site_a", 2), "k2")
    assert reg.get(("site_a", 1)) == "k1"  # 1 is now MRU
    reg.put(("site_b", 3), "k3")           # evicts LRU = key 2 only
    assert len(reg) == 2
    assert reg.get(("site_a", 2)) is None
    assert reg.get(("site_a", 1)) == "k1"
    assert reg.get(("site_b", 3)) == "k3"
    # per-site trace accounting
    assert reg.traces("site_a") == 2
    assert reg.traces("site_b") == 1


@pytest.mark.parametrize("source", ["env", "property", "neither"])
def test_persistent_compile_cache_knob(source, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; else the
    geomesa.compile.cache.dir property; else the fixed <checkout>/.jax_cache.
    Each case runs in a fresh interpreter: the choice is made against the
    process's own environment."""
    import subprocess
    import sys

    from geomesa_tpu.kernels import registry as regmod

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "GEOMESA_COMPILE_CACHE_DIR")}
    want = str(tmp_path / source)
    if source == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = want
    elif source == "property":
        env["GEOMESA_COMPILE_CACHE_DIR"] = want
    else:
        want = regmod.CHECKOUT_CACHE_DIR
    code = (
        "import jax\n"
        "from geomesa_tpu.kernels.registry import enable_persistent_cache\n"
        "print(enable_persistent_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], env=dict(env, JAX_PLATFORMS="cpu"),
        cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    assert want.endswith(".jax_cache") == (source == "neither")


# ---------------------------------------------------------------------------
# compile behavior through the public API
# ---------------------------------------------------------------------------


def test_same_shape_query_compiles_once():
    ds = _mk_ds()
    q = _bbox_q(-100, 30, -80, 45)
    c1 = ds.count("t", q)
    r0, h0 = _recompiles(), _hits()
    c2 = ds.count("t", q)
    assert c2 == c1 > 0
    assert _recompiles() == r0        # zero new traces
    assert _hits() > h0               # served from the kernel registry


def test_mutation_does_not_recompile():
    ds = _mk_ds()
    q = _bbox_q(-100, 30, -80, 45)
    ds.count("t", q)
    r0 = _recompiles()
    # mutation with NO dictionary growth: known vocab, numeric columns
    ds.insert("t", _mk_data(3_000, seed=12),
              fids=(np.arange(3_000) + 1_000_000).astype(str))
    ds.flush("t")
    c = ds.count("t", q)
    assert c > 0
    assert _recompiles() == r0, "a store mutation must not retrace kernels"


def test_dictionary_growth_does_recompile_string_predicates():
    # the safety side of version-stable keys: string predicates bake
    # resolved dictionary codes, so vocabulary growth must NOT reuse the
    # stale closure
    ds = _mk_ds()
    q = f"name IN ('a', 'zed') AND {DURING}"
    c1 = ds.count("t", q)
    r0 = _recompiles()
    fresh = _mk_data(2_000, seed=13, names=("zed",))
    ds.insert("t", fresh, fids=(np.arange(2_000) + 2_000_000).astype(str))
    ds.flush("t")
    c2 = ds.count("t", q)
    assert c2 > c1  # the new 'zed' rows match now
    assert _recompiles() > r0  # grown vocab -> fresh closure


def test_distinct_same_bucket_queries_share_one_kernel():
    # kNN parameterizes origin/box as traced scalars and shares one cache
    # token; its expanding-radius windows differ per origin (K of 8 vs 16
    # at this data shape), but shape bucketing folds every K <= floor
    # into ONE compiled kernel
    with config.COMPACT_BUCKET_FLOOR.scoped("32"):
        ds = _mk_ds()
        origins = [(-100.0, 35.0), (-92.5, 40.0), (-85.0, 30.5)]
        assert len(ds.knn("t", *origins[0], k=5)) == 5
        r0 = _recompiles()
        for x, y in origins[1:]:
            assert len(ds.knn("t", x, y, k=5)) == 5
        assert _recompiles() == r0, (
            "distinct same-bucket kNN queries must share the compiled kernel"
        )
        # and without bucketing, the same sequence retraces per K shape
        with config.COMPACT_BUCKETING.scoped("false"):
            ds2 = _mk_ds()
            len(ds2.knn("t", *origins[0], k=5))
            r1 = _recompiles()
            for x, y in origins[1:]:
                len(ds2.knn("t", x, y, k=5))
            assert _recompiles() > r1


# ---------------------------------------------------------------------------
# the acceptance proof: >= 3 distinct-but-same-bucket queries, repeated
# after an insert — exactly one trace per (jit site, query), zero
# recompiles on the repeats, bit-identical to the cold A/B run
# ---------------------------------------------------------------------------


@pytest.fixture
def k_floor_64():
    # fold every window count at this data shape (K <= 64 across queries
    # AND partitions) into one bucket, so the one-trace-per-site
    # assertions are exact
    with config.COMPACT_BUCKET_FLOOR.scoped("64"):
        yield


def test_warm_path_proof_zero_recompiles_and_bit_identity(k_floor_64):
    queries = [
        _bbox_q(-100, 30, -80, 45),
        _bbox_q(-103, 31, -82, 44),
        _bbox_q(-97, 29, -78, 46),
    ]
    bbox = (-100.0, 30.0, -80.0, 45.0)

    ds = _mk_ds(partitioned=True)
    st = ds._store("t")
    reg = ds._executor(st).kernel_registry()
    counts1 = [ds.count("t", q) for q in queries]
    grids1 = [np.asarray(ds.density("t", q, bbox=bbox, width=64, height=64))
              for q in queries]
    # one trace per (jit site, query): the count site compiled exactly
    # once per distinct query, never more
    assert reg.traces("count") == len(queries)
    r0 = _recompiles()
    counts2 = [ds.count("t", q) for q in queries]
    grids2 = [np.asarray(ds.density("t", q, bbox=bbox, width=64, height=64))
              for q in queries]
    assert counts2 == counts1
    for a, b in zip(grids1, grids2):
        np.testing.assert_array_equal(a, b)
    assert _recompiles() == r0, "repeat queries must be compile-free"

    # mutate (no dictionary growth), then repeat: STILL zero recompiles
    extra = _mk_data(4_000, seed=21)
    ds.insert("t", extra, fids=(np.arange(4_000) + 500_000).astype(str))
    ds.flush("t")
    r1 = _recompiles()
    counts3 = [ds.count("t", q) for q in queries]
    grids3 = [np.asarray(ds.density("t", q, bbox=bbox, width=64, height=64))
              for q in queries]
    assert _recompiles() == r1, "post-mutation repeats must be compile-free"

    # A/B: a cold dataset holding the same final rows, with bucketing and
    # the prefetch pipeline disabled (the pre-warm-path executor) must
    # produce bit-identical results
    with config.COMPACT_BUCKETING.scoped("false"), \
            config.PIPELINE_PREFETCH.scoped("false"):
        cold = GeoDataset(n_shards=4)
        cold.create_schema(
            "t", "name:String,weight:Float,dtg:Date,*geom:Point"
            ";geomesa.partition='time'"
        )
        base = _mk_data(20_000, seed=11)
        cold.insert("t", base, fids=np.arange(20_000).astype(str))
        cold.insert("t", extra, fids=(np.arange(4_000) + 500_000).astype(str))
        cold.flush("t")
        cold_counts = [cold.count("t", q) for q in queries]
        cold_grids = [
            np.asarray(cold.density("t", q, bbox=bbox, width=64, height=64))
            for q in queries
        ]
    assert counts3 == cold_counts
    for a, b in zip(grids3, cold_grids):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# double-buffered partition pipeline
# ---------------------------------------------------------------------------


def test_pipeline_bit_identical_and_prefetches():
    q = _bbox_q(-100, 30, -80, 45)
    bbox = (-100.0, 30.0, -80.0, 45.0)
    with config.MAX_RESIDENT_PARTITIONS.scoped("2"):
        ds = _mk_ds(n=30_000, partitioned=True)
        st = ds._store("t")
        assert len(st.partition_bins()) > 2  # spills + reloads exercised

        pf0 = metrics.registry().counter(metrics.PIPELINE_PREFETCH).value
        with config.PIPELINE_PREFETCH.scoped("true"):
            c_pipe = ds.count("t", q)
            g_pipe = np.asarray(
                ds.density("t", q, bbox=bbox, width=64, height=64))
            f_pipe = ds.query("t", q)
            # staged columns were consumed: partitions after the first
            # loaded while their predecessor executed
            assert metrics.registry().counter(
                metrics.PIPELINE_PREFETCH).value > pf0
        with config.PIPELINE_PREFETCH.scoped("false"):
            c_seq = ds.count("t", q)
            g_seq = np.asarray(
                ds.density("t", q, bbox=bbox, width=64, height=64))
            f_seq = ds.query("t", q)
    assert c_pipe == c_seq > 0
    np.testing.assert_array_equal(g_pipe, g_seq)
    assert len(f_pipe) == len(f_seq)
    assert sorted(f_pipe.fids) == sorted(f_seq.fids)


def test_pipeline_partitions_share_kernels_across_children(k_floor_64):
    # partitions of one store execute the same plan: one trace, many tables
    with config.MAX_RESIDENT_PARTITIONS.scoped("2"):
        ds = _mk_ds(n=30_000, partitioned=True)
        st = ds._store("t")
        ex = ds._executor(st)
        q = _bbox_q(-100, 30, -80, 45)
        assert ds.count("t", q) > 0
        # every partition child executed the count through ONE compiled
        # kernel (shard-length bucketing + shared registry)
        assert ex.kernel_registry().traces("count") == 1


# ---------------------------------------------------------------------------
# aggregate-cache cell queries share the kernel registry (ROADMAP item)
# ---------------------------------------------------------------------------


def test_cache_cell_kernels_survive_epoch_bump():
    ds = _mk_ds()
    q = _bbox_q(-100, 30, -80, 45)
    with config.CACHE_ENABLED.scoped("true"):
        c1 = ds.count("t", q)  # decomposes into cells; traces once per cell
        r0 = _recompiles()
        # mutation drops every cached RESULT (epoch bump) but must keep
        # every compiled cell kernel (version-stable keys)
        ds.insert("t", _mk_data(2_000, seed=31),
                  fids=(np.arange(2_000) + 700_000).astype(str))
        ds.flush("t")
        c2 = ds.count("t", q)
        assert c2 >= c1
        assert _recompiles() == r0, (
            "cold re-decomposition after a mutation must reuse cell kernels"
        )


# ---------------------------------------------------------------------------
# per-site recompile alert (docs/OBSERVABILITY.md; ROADMAP item closed)
# ---------------------------------------------------------------------------


def test_per_site_recompile_counters_and_alert_trip():
    from geomesa_tpu.kernels import registry as kreg

    kreg.reset_alert()
    ds = _mk_ds(n=8_000)
    q = _bbox_q(-100, 30, -80, 45)
    site_counter = metrics.registry().counter(
        f"{metrics.KERNEL_RECOMPILES}.count"
    )
    c0 = site_counter.value
    # threshold 0: the FIRST fresh trace at any site inside one query
    # window trips the alert gauge
    with config.KERNEL_ALERT_THRESHOLD.scoped("0"):
        assert ds.count("t", q) > 0
    assert site_counter.value > c0, "per-site recompile counter must move"
    gauge = metrics.registry().gauge(metrics.KERNEL_RECOMPILE_ALERT)
    assert gauge.value >= 1, "alert gauge must trip past the threshold"
    assert metrics.registry().counter(
        metrics.KERNEL_RECOMPILE_ALERTS
    ).value >= 1
    assert kreg.query_recompiles().get("count", 0) >= 1
    # surfaced in the exposition format (the /metrics contract)
    text = metrics.registry().prometheus()
    assert "geomesa_kernel_recompiles_count " in text
    assert "geomesa_kernel_recompile_alert " in text
    # a healthy (compile-free) warm repeat does NOT clear the latch: the
    # gauge stays visible for the scrape TTL so a trip can't be raced
    # away by the next query's window reset
    with config.KERNEL_ALERT_THRESHOLD.scoped("0"):
        r0 = _recompiles()
        assert ds.count("t", q) > 0
    assert _recompiles() == r0, "warm repeat must be compile-free"
    assert gauge.value >= 1, "alert latch must survive the next query"
    kreg.reset_alert()
    assert gauge.value == 0
