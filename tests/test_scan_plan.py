"""Planned multi-range scan: dedupe, shard-parallel execution, decode cache,
and the batched materialization path's byte-for-byte equivalence with the
per-example path (O2O stays clean)."""
import numpy as np
import pytest

from repro.core import events as ev
from repro.core.consistency import audit, batches_equal
from repro.core.materialize import Materializer
from repro.core.projection import TenantProjection, table1_tenants
from repro.storage import columnar
from repro.storage.immutable_store import ImmutableUIHStore, ScanRequest

SCHEMA = ev.default_schema()


@pytest.fixture(scope="module")
def sim(planned_sim):
    # the shared module-scoped heavy sim (tests/conftest.py)
    return planned_sim


PROJ = TenantProjection("t", seq_len=64, feature_groups=("core",),
                        traits_per_group={"core": ("timestamp", "item_id")})


# -- store-level planner ------------------------------------------------------

def test_plan_dedupes_and_groups_by_shard(sim):
    store = sim.immutable
    reqs = [ScanRequest(u, "core", 0, 10**12) for u in range(6)]
    dup = reqs + reqs  # duplicate-heavy batch
    plan = store.plan(dup)
    assert len(plan.unique) == 6
    assert plan.dedup_hits == 6
    assert plan.assignment == list(range(6)) * 2
    assert sum(len(g) for g in plan.shard_groups.values()) == 6
    assert plan.fanout == len({store.router.route(u) for u in range(6)})


def test_execute_plan_matches_serial_scans(sim):
    store = sim.immutable
    reqs = [ScanRequest(u, g, 0, 10**12)
            for u in range(6) for g in ("core", "engagement")]
    got = store.multi_range_scan(reqs + reqs)
    want = [store.scan(r) for r in reqs]
    assert len(got) == 2 * len(want)
    for a, b in zip(got, want + want):
        assert batches_equal(a, b)


def test_batched_scan_counters(sim):
    store = sim.immutable
    reqs = [ScanRequest(u, "core", 0, 10**12) for u in range(6)]
    before = store.stats.snapshot()
    store.multi_range_scan(reqs * 3)
    d = store.stats.delta(before)
    assert d.requests == 6            # post-dedupe executions only
    assert d.dedup_hits == 12
    assert d.parallel_shards == len({store.router.route(u) for u in range(6)})
    assert d.batched_requests == 1


def test_decode_cache_hits_on_overlapping_windows(sim):
    store = sim.immutable
    assert store.decode_cache is not None
    store.decode_cache.clear()
    req = ScanRequest(0, "core", 0, 10**12)
    before = store.stats.snapshot()
    first = store.scan(req)
    d1 = store.stats.delta(before)
    assert ev.batch_len(first) > 0 and d1.bytes_decoded > 0
    # same stripes, different (non-identical) request -> decode LRU hits
    before = store.stats.snapshot()
    again = store.scan(ScanRequest(0, "core", 1, 10**12))
    d2 = store.stats.delta(before)
    assert d2.decode_cache_hits == d2.stripes_read > 0
    assert d2.bytes_decoded == 0
    np.testing.assert_array_equal(first["item_id"][-ev.batch_len(again):],
                                  again["item_id"])


def test_decode_cache_lru_bound_and_identity():
    cache = columnar.StripeDecodeCache(max_entries=2)
    stripes = []
    for seed in range(3):
        rng = np.random.default_rng(seed)
        n = 32
        batch = {
            "timestamp": np.sort(rng.integers(0, 10**9, n)).astype(np.int64),
            "item_id": rng.integers(0, 1000, n).astype(np.int64),
        }
        stripes.append(columnar.encode_stripe_and_layout(batch, SCHEMA))
    traits = ("timestamp", "item_id")
    a, hit = cache.get(*stripes[0], traits)
    assert not hit
    _, hit = cache.get(*stripes[0], traits)
    assert hit
    cache.get(*stripes[1], traits)
    cache.get(*stripes[0], traits)   # promote 0 over 1
    cache.get(*stripes[2], traits)   # evicts 1 (LRU), not 0
    _, hit = cache.get(*stripes[0], traits)
    assert hit
    _, hit = cache.get(*stripes[1], traits)
    assert not hit
    # cached arrays are frozen: in-place mutation must fail loudly
    with pytest.raises(ValueError):
        a[1][0] = -1


def test_latency_model_charged_per_shard(sim):
    """Shard groups run concurrently: a constant per-shard delay costs ~max,
    not the sum over shards."""
    import time

    store = sim.immutable
    users = list(range(8))
    fanout = len({store.router.route(u) for u in users})
    assert fanout > 1
    delay = 0.05
    store.latency_model = lambda seeks, nbytes, f: delay
    try:
        t0 = time.perf_counter()
        store.multi_range_scan([ScanRequest(u, "core", 0, 10**12) for u in users])
        wall = time.perf_counter() - t0
    finally:
        store.latency_model = None
    assert wall < delay * fanout  # parallel shards overlap their latency


# -- materializer batch path --------------------------------------------------

def test_batched_materialization_identical_to_per_example(sim):
    for projection in (None, PROJ, *table1_tenants(256, 64, 8).values()):
        mat_a = sim.materializer()
        mat_b = sim.materializer()
        per_example = [mat_a.materialize(e, projection) for e in sim.examples]
        planned = mat_b.materialize_batch(sim.examples, projection)
        assert len(per_example) == len(planned)
        for a, b in zip(per_example, planned):
            assert batches_equal(a, b)


def test_batched_audit_stays_o2o_clean(sim):
    report = audit(sim.examples, sim.references, sim.materializer(),
                   sim.schema, batched=True)
    assert report.examples == len(sim.examples) > 0
    assert report.o2o_mismatches == 0
    assert report.leaked_events == 0


def test_batched_path_dedupes_same_user_windows(sim):
    """A duplicate-heavy (same-user, same-day) batch executes one scan per
    unique window x group; the plan's twins surface as dedup_hits."""
    ex = next(e for e in sim.examples if e.version and e.version.seq_len > 0)
    batch = [ex] * 5
    mat = sim.materializer(validate_checksum=False)
    before = sim.immutable.stats.snapshot()
    outs = mat.materialize_batch(batch, PROJ)
    d = sim.immutable.stats.delta(before)
    n_groups = len(PROJ.feature_groups)
    assert d.requests == n_groups              # one execution per group
    assert d.dedup_hits == 4 * n_groups        # the other 4 examples
    assert d.batched_requests == 1             # single store round-trip
    for o in outs:
        assert batches_equal(o, outs[0])


def test_window_cache_lru_promotes_on_hit(sim):
    users = {e.user_id for e in sim.examples if e.version}
    a, b, c = [next(e for e in sim.examples
                    if e.version and e.user_id == u) for u in list(users)[:3]]
    mat = sim.materializer(validate_checksum=False)
    mat.window_cache_size = 2
    mat.materialize_batch([a], PROJ)
    mat.materialize_batch([b], PROJ)
    mat.materialize_batch([a], PROJ)   # hit: promote a over b
    assert mat.stats.window_cache_hits == 1
    mat.materialize_batch([c], PROJ)   # evicts b (LRU), not a
    before = sim.immutable.stats.snapshot()
    mat.materialize_batch([a], PROJ)   # still cached -> no store traffic
    assert sim.immutable.stats.delta(before).requests == 0
    assert mat.stats.window_cache_hits == 2
    before = sim.immutable.stats.snapshot()
    mat.materialize_batch([b], PROJ)   # evicted -> refetched
    assert sim.immutable.stats.delta(before).requests > 0


def test_worker_surfaces_plan_counters(sim):
    """WorkerStats reports the planned-scan savings of ITS materializer's
    lookups (not global store traffic)."""
    from repro.dpp.featurize import FeatureSpec
    from repro.dpp.worker import DPPWorker

    spec = FeatureSpec(seq_len=64, uih_traits=("item_id", "timestamp"))
    worker = DPPWorker(sim.materializer(validate_checksum=False), PROJ, spec,
                       sim.schema)
    ex = next(e for e in sim.examples if e.version and e.version.seq_len > 0)
    worker.process([ex] * 4 + sim.examples[:8])
    assert worker.stats.dedup_hits >= 3 * len(PROJ.feature_groups)
    assert worker.stats.parallel_shards >= 1
    # another worker's traffic must not leak into this worker's counters
    other = DPPWorker(sim.materializer(validate_checksum=False), PROJ, spec,
                      sim.schema)
    before = worker.stats.dedup_hits
    other.process(sim.examples[:8])
    assert worker.stats.dedup_hits == before


def test_mixed_fat_and_vlm_batch(sim):
    """Fat Row + VLM examples in one batch keep their positions."""
    from repro.core.snapshot import FatRowSnapshotter

    fat_snap = FatRowSnapshotter(sim.mutable, sim.immutable, sim.schema)
    fat_ex = fat_snap.snapshot(sim.examples[0].user_id,
                               sim.examples[0].request_ts, {"item_id": 1},
                               {"click": 0.0})
    mat = sim.materializer(validate_checksum=False)
    batch = [sim.examples[0], fat_ex, sim.examples[1]]
    outs = mat.materialize_batch(batch, PROJ)
    assert batches_equal(outs[0], mat.materialize(sim.examples[0], PROJ))
    assert batches_equal(outs[1], mat.materialize(fat_ex, PROJ))
    assert batches_equal(outs[2], mat.materialize(sim.examples[1], PROJ))


# -- column-wise scan: the store's counters ------------------------------------

# recorded on this fixed batch with the per-stripe scan path, before the
# column-wise pass replaced it: the counters must not move
_FIXED_BATCH_COUNTERS = {
    "projected": dict(requests=7, seeks=7, stripes_read=38,
                      bytes_scanned=14139, bytes_decoded=3169, dedup_hits=25,
                      decode_cache_hits=0),
    "full": dict(requests=21, seeks=21, stripes_read=114,
                 bytes_scanned=52466, bytes_decoded=11754, dedup_hits=75,
                 decode_cache_hits=0),
}


@pytest.mark.parametrize("which", ["projected", "full"])
def test_scan_counters_unchanged_on_a_fixed_batch(sim, which):
    projection = PROJ if which == "projected" else None
    batch = sim.examples[-24:] + sim.examples[-24:-16]   # repeated examples
    sim.immutable.decode_cache.clear()
    first = sim.materializer(validate_checksum=False)
    first.materialize_batch(batch, projection)
    d = first.io_stats
    want = _FIXED_BATCH_COUNTERS[which]
    assert {k: getattr(d, k) for k in want} == want
    # every window read stripes, so every scan assembled one window
    assert d.windows_assembled == d.requests
    # the same batch again: every stripe from the decode LRU, nothing decoded
    again = sim.materializer(validate_checksum=False)
    again.materialize_batch(batch, projection)
    d2 = again.io_stats
    assert d2.decode_cache_hits == d2.stripes_read == want["stripes_read"]
    assert d2.bytes_decoded == 0
    assert d2.dedup_hits == want["dedup_hits"]


def test_iostats_arithmetic_covers_every_field():
    import dataclasses

    from repro.storage.immutable_store import IOStats

    names = [f.name for f in dataclasses.fields(IOStats)]
    a = IOStats(*range(1, len(names) + 1))
    b = IOStats(*(10 * i for i in range(1, len(names) + 1)))
    snap = a.snapshot()
    a.merge(b)
    assert [getattr(a, n) for n in names] == [11 * i for i in range(1, len(names) + 1)]
    assert [getattr(a.delta(snap), n) for n in names] == \
        [10 * i for i in range(1, len(names) + 1)]
    assert [getattr(snap, n) for n in names] == list(range(1, len(names) + 1))


def test_scan_span_meta_counts_windows_and_stripes(sim):
    from repro.dpp.featurize import FeatureSpec
    from repro.dpp.worker import DPPWorker
    from repro.obs.spans import SpanTracker, current_span

    spec = FeatureSpec(seq_len=64, uih_traits=("item_id", "timestamp"))
    worker = DPPWorker(sim.materializer(validate_checksum=False), PROJ, spec,
                       sim.schema)
    batch = sim.examples[-12:] + sim.examples[-12:-8]
    tr = SpanTracker(sample_every=1)
    tr.mint(0)
    tr.enter_item(0)
    try:
        worker.process_jagged(batch)
        meta = dict(current_span().meta)
    finally:
        tr.exit_item()
    d = worker.materializer.io_stats
    windows = {(e.user_id, e.version.end_ts, e.version.seq_len,
                e.version.checksum) for e in batch}
    assert meta["windows"] == d.windows_assembled == len(windows) > 0
    assert meta["stripes"] == d.stripes_read > meta["windows"]
    assert meta["bytes_scanned"] == d.bytes_scanned > 0


def test_materialize_batch_equals_per_example_with_mutable_and_audit(sim):
    """Repeated users, non-empty mutable slices, checksums validated: the
    window-wide join gives each example what ``materialize`` gives it."""
    late = [e for e in sim.examples[-32:] if e.version is not None]
    with_mutable = [e for e in late
                    if e.mutable_uih and ev.batch_len(e.mutable_uih) > 0]
    assert with_mutable and len({e.user_id for e in late}) < len(late)
    batch = late + with_mutable[:4] + late[:3]
    for projection in (PROJ, None, *table1_tenants(256, 64, 8).values()):
        solo = sim.materializer(validate_checksum=True)
        planned = sim.materializer(validate_checksum=True)
        want = [solo.materialize(e, projection) for e in batch]
        got = planned.materialize_batch(batch, projection)
        for a, b in zip(got, want):
            assert list(a) == list(b)
            for k in b:
                assert a[k].dtype == b[k].dtype
                np.testing.assert_array_equal(a[k], b[k])
        if projection is None:   # full windows: every one checksum-validated
            assert planned.stats.checksum_validated == \
                planned.stats.windows_fetched > 0
        assert planned.stats.checksum_failures == 0
        for f in ("examples", "immutable_events", "mutable_events"):
            assert getattr(planned.stats, f) == getattr(solo.stats, f)
