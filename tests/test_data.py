"""Declarative read path (DatasetSpec -> open_feed -> Feed) + multi-tenant
co-scan planning.

Covers:
  * canonical trait ordering/dedup in ``TenantProjection.traits_for`` (the
    override vs schema-default asymmetry regression);
  * ``ScanRequest`` construction-time validation (with the legitimate
    pre-first-compaction empty-window sentinel);
  * store-level union-projection planning: containment subsumption in
    ``plan()``/``execute_plan()`` and the metadata-exact ``estimate_scan``;
  * co-scan equivalence: ``MultiTenantPlanner``/``materialize_multi`` output
    is byte-identical to per-tenant solo materialization, across pinned vs
    live generation policies and under a concurrent compaction flip (the
    PR 3 stress-churn harness);
  * ``open_feed`` compiling batch (sim + warehouse) AND streaming specs into
    the ONE ``Feed`` protocol, consumed end-to-end by the ``Trainer``;
  * the deprecated ``make_device_feed``/``make_streaming_feed`` shims keep
    working (DeprecationWarning + the same Feed protocol).
"""
import threading
import time

import numpy as np
import pytest

from repro.core import events as ev
from repro.core.materialize import Materializer, TenantShareStats
from repro.core.projection import TenantProjection, project_view
from repro.core.simulation import ProductionSim, SimConfig
from repro.data import (
    DatasetSpec,
    Feed,
    MultiTenantPlanner,
    SimSource,
    StreamSource,
    WarehouseSource,
    open_feed,
)
from repro.dpp.featurize import FeatureSpec
from repro.storage.immutable_store import ScanRequest

from conftest import make_sim

SCHEMA = ev.default_schema()


def _sim(users=6, days=2, seed=0, req=3, pin=True):
    # shared fixture builder (tests/conftest.py); this file never audits, so
    # references are skipped
    return make_sim(users=users, days=days, seed=seed, req=req, pin=pin,
                    capture_reference=False)


# ---------------------------------------------------------------------------
# satellite: canonical trait ordering (override vs schema-default asymmetry)
# ---------------------------------------------------------------------------

def test_traits_for_canonical_ordering_and_dedupe():
    default = TenantProjection("a", 8, ("core",))
    # same trait SET, pathologically ordered + duplicated override
    override = TenantProjection(
        "b", 8, ("core",),
        traits_per_group={"core": ("item_id", "timestamp", "item_id",
                                   "action_type")})
    canonical = ("timestamp", "item_id", "action_type")
    assert default.traits_for(SCHEMA, "core") == canonical
    assert override.traits_for(SCHEMA, "core") == canonical
    # the regression: equivalent projections must order all_traits identically
    assert default.all_traits(SCHEMA) == override.all_traits(SCHEMA)
    # timestamp is injected for overrides that omit it, first
    no_ts = TenantProjection("c", 8, ("core",),
                             traits_per_group={"core": ("item_id",)})
    assert no_ts.traits_for(SCHEMA, "core") == ("timestamp", "item_id")
    # non-schema extras keep declaration order, after schema-ordered traits
    extra = TenantProjection("d", 8, ("core",),
                             traits_per_group={"core": ("zz", "item_id")})
    assert extra.traits_for(SCHEMA, "core") == ("timestamp", "item_id", "zz")


def test_projection_hashable_and_union():
    a = TenantProjection("a", 8, ["core"],
                         traits_per_group={"core": ["timestamp", "item_id"]})
    b = TenantProjection("a", 8, ("core",),
                         traits_per_group={"core": ("timestamp", "item_id")})
    assert a == b and hash(a) == hash(b)     # list inputs normalized
    assert len({a, b}) == 1
    long = TenantProjection("long", 64, ("core", "sideinfo"))
    short = TenantProjection("short", 8, ("core",),
                             traits_per_group={"core": ("timestamp",
                                                        "item_id")})
    u = TenantProjection.union([long, short], SCHEMA)
    assert u.seq_len == 64
    assert u.feature_groups == ("core", "sideinfo")
    # per-group union covers every tenant's traits, canonically ordered
    assert u.traits_for(SCHEMA, "core") == ("timestamp", "item_id",
                                            "action_type")
    assert set(short.traits_for(SCHEMA, "core")) <= set(
        u.traits_for(SCHEMA, "core"))
    # union of one tenant is that tenant
    assert TenantProjection.union([short], SCHEMA) is short
    # a hashable projection must be mutation-proof: its mapping is read-only
    with pytest.raises(TypeError):
        a.traits_per_group["core"] = ("timestamp",)


# ---------------------------------------------------------------------------
# satellite: ScanRequest validates at the API boundary
# ---------------------------------------------------------------------------

def test_scan_request_validates_on_construction():
    with pytest.raises(ValueError, match="max_events"):
        ScanRequest(0, "core", 0, 10, max_events=-2)
    with pytest.raises(ValueError, match="generation"):
        ScanRequest(0, "core", 0, 10, generation=-3)
    # the legitimate empty-window sentinel: end_ts < 0 means "no immutable
    # watermark yet" (examples logged before the first compaction)
    ScanRequest(0, "core", start_ts=5, end_ts=-1)
    ScanRequest(0, "core", 0, 10, max_events=-1, generation=-1)


def test_inverted_bounds_scan_empty_not_raise():
    # start_ts > end_ts is a legitimate empty-window request, NOT an error:
    # the snapshotter emits it whenever a user's immutable watermark is older
    # than request_ts - lookback (a user returning after a long idle).
    sim = _sim(days=2, pin=False)
    store = sim.immutable
    uid = sim.examples[-1].user_id
    wm = store.watermark(uid)
    assert wm >= 0
    got = store.scan(ScanRequest(uid, "core", start_ts=wm + 1_000, end_ts=wm))
    assert ev.batch_len(got) == 0


def test_snapshotter_survives_watermark_older_than_lookback():
    # Regression: with a 1-day lookback, day-2 requests put start_ts
    # (request_ts - lookback) past the day-1 consolidation watermark, so
    # _fetch_both_tiers builds ScanRequests with start_ts > end_ts >= 0.
    # This used to raise ValueError("inverted scan bounds") from
    # ScanRequest.__post_init__; it must yield an empty immutable window.
    cfg = SimConfig(
        stream=ev.StreamConfig(n_users=4, n_items=500, days=4,
                               events_per_user_day_mean=10.0, seed=1),
        stripe_len=16,
        requests_per_user_day=2,
        lookback_ms=1 * ev.MS_PER_DAY,
        seed=1,
        pin_generations=False,
    )
    sim = ProductionSim(cfg)
    sim.run_days(2, capture_reference=False)
    assert sim.examples
    # and the lookback contract holds: the mutable read is clamped to the
    # window start, so the returning-idle user's UIH never contains events
    # older than request_ts - lookback (which an unclamped (watermark,
    # request_ts] read would feed it)
    for exm in sim.examples:
        mut = exm.mutable_uih
        if mut and ev.batch_len(mut):
            assert int(mut["timestamp"].min()) >= exm.request_ts - cfg.lookback_ms


# ---------------------------------------------------------------------------
# store: union-projection planning (subsumption) + metadata-exact estimates
# ---------------------------------------------------------------------------

def test_plan_subsumes_contained_requests_byte_identically():
    sim = _sim(days=2, pin=False)
    store = sim.immutable
    uid = sim.examples[-1].user_id
    end = store.watermark(uid)
    wide = ScanRequest(uid, "core", 0, end)                       # unbounded
    narrow = ScanRequest(uid, "core", 0, end, max_events=4,
                         traits=("timestamp", "item_id"))
    plan = store.plan([wide, narrow])
    assert plan.subsumed == 1 and len(plan.shard_groups) == 1
    before = store.stats.snapshot()
    got_wide, got_narrow = store.execute_plan(plan)
    d = store.stats.delta(before)
    assert d.subsumed_hits == 1
    assert d.requests == 1            # only the covering request scanned
    # byte-identical to executing each request alone
    solo_narrow = store.scan(narrow)
    assert list(got_narrow.keys()) == list(solo_narrow.keys())
    for k in solo_narrow:
        assert got_narrow[k].dtype == solo_narrow[k].dtype
        assert np.array_equal(got_narrow[k], solo_narrow[k])
    solo_wide = store.scan(wide)
    for k in solo_wide:
        assert np.array_equal(got_wide[k], solo_wide[k])
    # non-contained requests (disjoint traits) are NOT subsumed
    other = ScanRequest(uid, "core", 0, end, max_events=4,
                        traits=("timestamp", "action_type"))
    p2 = store.plan([narrow, other])
    assert p2.subsumed == 0


def test_estimate_scan_matches_actual_io():
    sim = _sim(days=2, pin=False)
    store = sim.immutable
    store.decode_cache = None
    for exm in sim.examples[-6:]:
        v = exm.version
        req = ScanRequest(exm.user_id, "core", v.start_ts, v.end_ts,
                          max_events=32)
        est_stripes, est_bytes = store.estimate_scan(req)
        before = store.stats.snapshot()
        store.scan(req)
        d = store.stats.delta(before)
        assert (d.stripes_read, d.bytes_scanned) == (est_stripes, est_bytes)


# ---------------------------------------------------------------------------
# co-scan equivalence: byte-identical to solo, pinned vs live, under churn
# ---------------------------------------------------------------------------

def _tenants():
    return [
        TenantProjection("wide", 48, ("core", "engagement", "sideinfo")),
        TenantProjection("mid", 16, ("core", "engagement")),
        TenantProjection("narrow", 6, ("core",),
                         traits_per_group={"core": ("timestamp", "item_id")}),
    ]


def _assert_views_equal(a, b, ctx):
    assert list(a.keys()) == list(b.keys()), (ctx, sorted(a), sorted(b))
    for k in a:
        assert a[k].dtype == b[k].dtype, (ctx, k)
        assert np.array_equal(a[k], b[k]), (ctx, k)


@pytest.mark.parametrize("pin", [True, False], ids=["pinned", "live"])
def test_coscan_byte_identical_to_solo_under_compaction_flip(pin):
    """Property: every tenant's co-scan output == its solo materialization,
    for pinned AND live generation policies, while compaction churns NEW
    generations concurrently (the PR 3 stress harness: re-compactions at the
    established watermark — identical windows, fresh generation ids)."""
    sim = _sim(users=6, days=2, seed=13, req=4, pin=True)
    tenants = _tenants()
    wm_box = [sim.compaction_watermark]
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            sim.run_compaction(wm_box[0], evict=False)
            time.sleep(0.003)

    th = threading.Thread(target=churn, daemon=True)
    th.start()
    try:
        multi = Materializer(sim.immutable, sim.schema, pin_generations=pin)
        solos = {t.name: Materializer(sim.immutable, sim.schema,
                                      pin_generations=pin) for t in tenants}
        share = TenantShareStats()
        for lo in range(0, len(sim.examples), 8):
            batch = sim.examples[lo:lo + 8]
            got = multi.materialize_multi(batch, tenants, share_stats=share)
            for t in tenants:
                want = solos[t.name].materialize_batch(batch, t)
                for i, (a, b) in enumerate(zip(got[t.name], want)):
                    _assert_views_equal(a, b, (t.name, pin, lo + i))
        assert share.co_scan_windows > 0
        assert share.bytes_saved_vs_solo > 0   # nested tenants => real saving
        if pin:
            # leases held by the publisher => the pinned path really served
            assert multi.stats.pinned_windows > 0
    finally:
        stop.set()
        th.join()
    # generations actually flipped during the run
    assert sim.immutable.generation >= 2


def test_project_view_carves_solo_fetch():
    sim = _sim(days=2, pin=False)
    tenants = _tenants()
    union = TenantProjection.union(tenants, SCHEMA)
    mat = Materializer(sim.immutable, sim.schema)
    exm = max(sim.examples, key=lambda e: e.version.seq_len)
    wide = mat._fetch_immutable(exm, union)
    for t in tenants:
        carved = project_view(wide, t, SCHEMA)
        solo = mat._fetch_immutable(exm, t)
        _assert_views_equal(
            ev.project_traits(solo, [c for c in t.all_traits(SCHEMA)
                                     if c in solo]),
            carved, t.name)


# ---------------------------------------------------------------------------
# DatasetSpec: frozen, hashable, validated
# ---------------------------------------------------------------------------

def test_dataset_spec_validation_and_hash():
    t = TenantProjection("t", 8, ("core",))
    a = DatasetSpec(tenant=t, source=SimSource(), batch_size=8)
    b = DatasetSpec(tenant=t, source=SimSource(), batch_size=8)
    assert a == b and len({a, b}) == 1
    with pytest.raises(ValueError, match="consistency"):
        DatasetSpec(tenant=t, consistency="sometimes")
    with pytest.raises(ValueError, match="generations"):
        DatasetSpec(tenant=t, generations="latest")
    with pytest.raises(ValueError, match="batch sizes"):
        DatasetSpec(tenant=t, batch_size=0)
    # derived featurization: every non-timestamp projected trait
    fs = a.resolve_features(SCHEMA)
    assert fs.seq_len == 8
    assert fs.uih_traits == ("item_id", "action_type")
    assert a.validate_checksum is False and a.pin_generations is False
    audit = DatasetSpec(tenant=t, consistency="audit", generations="pinned")
    assert audit.validate_checksum and audit.pin_generations
    with pytest.raises(ValueError, match="prefetch_depth"):
        DatasetSpec(tenant=t, prefetch_depth=-1)


def test_open_feed_honors_explicit_prefetch_depth_zero():
    """prefetch_depth=0 forces the host feed even when a cell is targeted
    (None means auto)."""
    sim = _sim(users=4, days=1, pin=False)
    feed = open_feed(_tiny_spec(SimSource(), prefetch_depth=0), sim)
    assert feed.prefetcher is None
    for b in feed:
        feed.recycle(b)
    feed.join()


def test_open_feed_device_materialize_byte_identical():
    """DESIGN §3 acceptance at the open_feed level: the SAME spec with
    ``device_materialize=True`` (jagged emission + on-device fused densify in
    the prefetch stage) yields batch-for-batch identical device batches to
    the host-densify path, while shipping fewer H2D bytes."""
    import jax

    host_feed = open_feed(
        _tiny_spec(SimSource(), prefetch_depth=2), _sim(pin=False))
    want = [b for b in host_feed]
    host_feed.close(timeout=10.0)
    host_bytes = host_feed.stats().client.h2d_bytes
    assert want and host_bytes > 0

    dev_feed = open_feed(
        _tiny_spec(SimSource(), prefetch_depth=2, device_materialize=True),
        _sim(pin=False))
    got = [b for b in dev_feed]
    dev_feed.close(timeout=10.0)
    dev_bytes = dev_feed.stats().client.h2d_bytes

    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert set(g) == set(w)          # device_put sorts dict keys
        for k in w:
            assert g[k].dtype == w[k].dtype, k
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(w[k]),
                                          err_msg=k)
    # the flag is operational, not dataset identity: same resume fingerprint
    from repro.data.spec import resume_fingerprint
    assert (resume_fingerprint(_tiny_spec(SimSource(), prefetch_depth=2))
            == resume_fingerprint(_tiny_spec(SimSource(), prefetch_depth=2,
                                             device_materialize=True)))
    assert 0 < dev_bytes < host_bytes


@pytest.mark.parametrize("source,depth,prep,match", [
    (StreamSource(), 2, None, "streaming"),
    (SimSource(), 0, None, "device-prefetch"),
    (SimSource(), None, None, "device-prefetch"),   # auto depth, no cell
    (SimSource(), 2, lambda b: b, "prep_fn"),
])
def test_open_feed_device_materialize_refuses_host_fallback(
        source, depth, prep, match):
    """``device_materialize=True`` that the feed cannot honour raises before
    anything starts; it never quietly densifies on the host."""
    with pytest.raises(ValueError, match=match):
        open_feed(_tiny_spec(source, prefetch_depth=depth,
                             device_materialize=True),
                  _sim(pin=False), prep_fn=prep)


def test_multitenant_planner_rejects_mixed_policies():
    t1 = TenantProjection("a", 8, ("core",))
    t2 = TenantProjection("b", 8, ("core",))
    sim = _sim(days=1, pin=False)
    with pytest.raises(ValueError, match="policy"):
        MultiTenantPlanner(
            [DatasetSpec(tenant=t1, consistency="audit"),
             DatasetSpec(tenant=t2, consistency="off")],
            sim.immutable, sim.schema)
    with pytest.raises(ValueError, match="unique"):
        MultiTenantPlanner([t1, t1], sim.immutable, sim.schema)


# ---------------------------------------------------------------------------
# open_feed: batch + warehouse + streaming through the ONE Feed protocol
# ---------------------------------------------------------------------------

def _tiny_spec(source, **kw):
    tenant = TenantProjection(
        "t", 16, ("core",),
        traits_per_group={"core": ("timestamp", "item_id", "action_type")})
    features = FeatureSpec(seq_len=16, uih_traits=("item_id", "action_type"))
    kw.setdefault("batch_size", 8)
    kw.setdefault("base_batch_size", 4)
    kw.setdefault("n_workers", 2)
    return DatasetSpec(tenant=tenant, source=source, features=features, **kw)


def test_open_feed_warehouse_replay_covers_all_examples():
    sim = _sim(users=6, days=2, pin=False)
    feed = open_feed(_tiny_spec(WarehouseSource()), sim)
    assert isinstance(feed, Feed)
    rows = 0
    users = []
    for b in feed:
        rows += len(b["uih_len"])
        users.extend(b["user_id"].tolist())
        feed.recycle(b)
    feed.join()
    assert feed.drained
    total = len(sim.examples)
    assert rows == total
    assert sorted(users) == sorted(e.user_id for e in sim.examples)
    st = feed.stats()
    assert st.workers.examples == total
    assert st.client.full_batches > 0


def test_open_feed_close_drains_early_exit():
    sim = _sim(users=6, days=2, pin=False)
    feed = open_feed(_tiny_spec(SimSource(epochs=2)), sim)
    first = feed.get(timeout=10.0)
    assert first is not None
    feed.close(timeout=10.0)   # walk away after one batch: must not hang
    assert feed._joiner is not None and not feed._joiner.is_alive()


def test_trainer_runs_batch_and_stream_through_one_feed_protocol():
    """Acceptance: the Trainer consumes batch AND streaming feeds through the
    single Feed protocol returned by open_feed."""
    import jax.numpy as jnp

    from repro.train.train_loop import Trainer, TrainerConfig

    def loss_fn(params, b):
        score = jnp.sum(b["uih_item_id"] * params["w"], axis=1)
        return jnp.mean((score - b["label_click"]) ** 2)

    params = {"w": jnp.zeros((16,), jnp.float32)}

    # batch: host feed (no device prefetch stage)
    sim = _sim(users=6, days=2, pin=False)
    feed = open_feed(_tiny_spec(SimSource(min_rows=64)), sim)
    tr = Trainer(loss_fn, params, TrainerConfig(log_every=1000))
    tr.fit(feed, max_steps=3)
    assert tr.step == 3
    feed.close(timeout=10.0)

    # streaming: pinned generations + device prefetch stage, same protocol
    sim2 = _sim(users=6, days=2, pin=True)
    sim2.stream.close()   # backlog only: the feed drains it and ends
    feed2 = open_feed(
        _tiny_spec(StreamSource(backfill=False), consistency="audit",
                   generations="pinned", prefetch_depth=2),
        sim2)
    tr2 = Trainer(loss_fn, params, TrainerConfig(log_every=1000))
    tr2.fit(feed2)        # runs until the stream drains
    assert tr2.step >= 1
    assert feed2.drained
    feed2.close()
    st = feed2.stats()
    assert st.freshness is not None         # streaming-only counters surfaced
    assert st.workers.examples == len(sim2.examples)
    # every lease released once the stream drained
    assert sim2.stream.pending_leases() == 0


# ---------------------------------------------------------------------------
# satellite: deprecated make_*_feed shims keep working
# ---------------------------------------------------------------------------

def test_make_device_feed_shim_warns_and_returns_feed_protocol():
    from repro.launch.steps import make_device_feed

    host = [{"x": np.arange(4, dtype=np.int32)} for _ in range(3)]
    with pytest.warns(DeprecationWarning, match="open_feed"):
        feed = make_device_feed(None, host, mesh=None, depth=1)
    assert isinstance(feed, Feed)
    out = list(feed)
    assert len(out) == 3
    assert feed.drained
    feed.record_train_step(0.001)           # protocol surface intact
    assert feed.stats().client.full_batches == 3
    # legacy contract: `.stats` also reads as the live ClientStats attribute
    # (old DevicePrefetcher call sites did `feed.stats.starvation_pct`)
    assert feed.stats.full_batches == 3
    assert feed.stats.starvation_pct >= 0.0
    feed.stats.starved_time_s += 0.0        # legacy in-place mutation works
    feed.close()


def test_shim_feed_close_drains_caller_owned_pool():
    # Regression: a shim Feed wraps a BARE client (pool owned by the caller,
    # as at legacy call sites mid-migration). close() must still drain the
    # host pipeline so workers parked on the bounded slot queues exit —
    # otherwise the caller's own pool.join() hangs.
    from repro.data.compile import _batch_items, compile_worker_plan
    from repro.dpp.client import RebatchingClient
    from repro.dpp.elastic import DPPWorkerPool
    from repro.launch.steps import make_device_feed

    sim = _sim(users=6, days=2, pin=False)
    spec = _tiny_spec(WarehouseSource(), buffer_batches=1)
    client = RebatchingClient(spec.batch_size, buffer_batches=1)
    pool = DPPWorkerPool.from_plan(compile_worker_plan(spec, sim), client,
                                   n_workers=2)
    pool.start(_batch_items(spec, sim))
    with pytest.warns(DeprecationWarning, match="open_feed"):
        feed = make_device_feed(None, client, mesh=None, depth=1)
    assert feed.client is client and feed.pool is None
    assert feed.get(timeout=10.0) is not None   # consume one, walk away early
    feed.close(timeout=30.0)                    # must unpark the workers
    joined = threading.Event()

    def _join():
        pool.join()
        joined.set()

    threading.Thread(target=_join, daemon=True).start()
    assert joined.wait(timeout=30.0), "caller-owned pool.join() hung"


def test_make_streaming_feed_shim_warns_and_returns_feed_protocol():
    from repro.launch.steps import make_streaming_feed
    from repro.streaming.session import StreamingSession
    from repro.streaming.source import MicroBatchConfig

    sim = _sim(users=4, days=1, pin=True)
    sim.stream.close()
    spec = _tiny_spec(StreamSource())
    from repro.data import compile_worker_plan

    session = StreamingSession(
        sim.stream, compile_worker_plan(spec, sim), full_batch_size=8,
        micro_batch=MicroBatchConfig(max_examples=4, max_delay_s=0.02),
        n_workers=1)
    with pytest.warns(DeprecationWarning, match="open_feed"):
        feed = make_streaming_feed(None, session, mesh=None, depth=1)
    assert isinstance(feed, Feed)
    rows = sum(len(b["uih_len"]) for b in feed)
    assert rows == len(sim.examples)
    assert feed.drained
    feed.close()
    assert sim.stream.pending_leases() == 0
