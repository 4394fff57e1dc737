"""Storage tier: mutable blind-writes, immutable range scans, compaction
idempotence, right-to-delete, schema evolution, symmetric sharding."""
import numpy as np
import pytest

from repro.core import events as ev
from repro.storage import columnar
from repro.storage.compaction import (
    CompactionConfig,
    CompactionPipeline,
    make_scrub,
)
from repro.storage.immutable_store import ImmutableUIHStore, ScanRequest
from repro.storage.mutable_store import MutableUIHStore
from repro.storage.sharding import ShardRouter, shard_of

SCHEMA = ev.default_schema()


def _gen(users=4, days=5, seed=0):
    return ev.SyntheticEventStream(
        ev.StreamConfig(n_users=users, n_items=1_000, days=days,
                        events_per_user_day_mean=50.0, seed=seed),
        SCHEMA,
    )


def _build_store(gen, users, as_of_ts, stripe_len=16, scrub=None):
    store = ImmutableUIHStore(SCHEMA, n_shards=4)
    pipe = CompactionPipeline(SCHEMA, CompactionConfig(stripe_len=stripe_len))
    source = lambda uid, lo, hi: ev.time_slice(gen.history_until(uid, hi), lo, hi)
    report = pipe.run(source, list(range(users)), as_of_ts, store, scrub=scrub)
    return store, report


# -- mutable store -------------------------------------------------------------

def test_mutable_blind_write_merge_on_read():
    store = MutableUIHStore(SCHEMA)
    gen = _gen(users=1)
    batch = gen.day_events(0, 0)
    n = ev.batch_len(batch)
    assert n > 5
    # append shuffled chunks (out of order) — merge-on-read must sort
    rng = np.random.default_rng(0)
    perm = rng.permutation(n)
    for idx in np.array_split(perm, 4):
        store.append(0, ev.take_batch(batch, np.sort(idx)))
    view = store.read(0, -1, 10**18)
    np.testing.assert_array_equal(view["timestamp"], batch["timestamp"])
    np.testing.assert_array_equal(view["item_id"], batch["item_id"])


def test_mutable_read_respects_bounds():
    store = MutableUIHStore(SCHEMA)
    gen = _gen(users=1)
    batch = gen.day_events(0, 0)
    store.append(0, batch)
    ts = batch["timestamp"]
    mid = int(ts[len(ts) // 2])
    out = store.read(0, mid, 10**18)
    assert np.all(out["timestamp"] > mid)
    out2 = store.read(0, -1, mid)
    assert np.all(out2["timestamp"] <= mid)


def test_mutable_eviction_coupled_to_watermark():
    store = MutableUIHStore(SCHEMA)
    gen = _gen(users=1)
    b0, b1 = gen.day_events(0, 0), gen.day_events(0, 1)
    store.append(0, b0)
    store.append(0, b1)
    watermark = int(b0["timestamp"][-1])
    store.evict_until(0, watermark)
    view = store.read(0, -1, 10**18)
    assert np.all(view["timestamp"] > watermark)
    assert ev.batch_len(view) == ev.batch_len(b1)


# -- immutable store -------------------------------------------------------------

def test_range_scan_matches_source_of_truth():
    gen = _gen()
    as_of = 3 * ev.MS_PER_DAY
    store, _ = _build_store(gen, 4, as_of)
    for uid in range(4):
        truth = gen.history_until(uid, as_of)
        got = store.scan(ScanRequest(uid, "core", 0, as_of))
        np.testing.assert_array_equal(got["timestamp"], truth["timestamp"])
        np.testing.assert_array_equal(got["item_id"], truth["item_id"])


def test_bounded_scan_temporal_predicate():
    gen = _gen()
    as_of = 4 * ev.MS_PER_DAY
    store, _ = _build_store(gen, 2, as_of)
    truth = gen.history_until(0, as_of)
    ts = truth["timestamp"]
    lo, hi = int(ts[len(ts) // 4]), int(ts[3 * len(ts) // 4])
    got = store.scan(ScanRequest(0, "core", lo, hi))
    want = ev.time_slice(truth, lo, hi)
    np.testing.assert_array_equal(got["timestamp"], want["timestamp"])


def test_sequence_length_projection_reads_fewer_stripes():
    gen = _gen(users=1, days=6)
    as_of = 5 * ev.MS_PER_DAY
    store, _ = _build_store(gen, 1, as_of, stripe_len=8)
    truth = gen.history_until(0, as_of)
    n = ev.batch_len(truth)
    assert n > 64

    before = store.stats.snapshot()
    short = store.scan(ScanRequest(0, "core", 0, as_of, max_events=8))
    short_stats = store.stats.delta(before)

    before = store.stats.snapshot()
    full = store.scan(ScanRequest(0, "core", 0, as_of))
    full_stats = store.stats.delta(before)

    assert ev.batch_len(short) == 8
    np.testing.assert_array_equal(short["timestamp"], truth["timestamp"][-8:])
    assert short_stats.stripes_read < full_stats.stripes_read
    assert short_stats.bytes_scanned < full_stats.bytes_scanned


def test_feature_group_and_trait_projection():
    gen = _gen(users=1)
    as_of = 3 * ev.MS_PER_DAY
    store, _ = _build_store(gen, 1, as_of)
    got = store.scan(
        ScanRequest(0, "engagement", 0, as_of, traits=("timestamp", "like"))
    )
    assert set(got.keys()) == {"timestamp", "like"}
    truth = gen.history_until(0, as_of)
    np.testing.assert_array_equal(got["like"], truth["like"])


def test_single_seek_per_scan():
    gen = _gen(users=1, days=6)
    store, _ = _build_store(gen, 1, 5 * ev.MS_PER_DAY, stripe_len=8)
    before = store.stats.snapshot()
    store.scan(ScanRequest(0, "core", 0, 5 * ev.MS_PER_DAY))
    d = store.stats.delta(before)
    assert d.seeks == 1  # single-level layout: one seek then sequential I/O
    assert d.stripes_read > 1


# -- compaction ----------------------------------------------------------------

def test_compaction_idempotent():
    gen = _gen()
    as_of = 3 * ev.MS_PER_DAY
    s1, r1 = _build_store(gen, 4, as_of)
    s2, r2 = _build_store(gen, 4, as_of)
    assert r1.events == r2.events and r1.stripes == r2.stripes
    for uid in range(4):
        a = s1.scan(ScanRequest(uid, "core", 0, as_of))
        b = s2.scan(ScanRequest(uid, "core", 0, as_of))
        np.testing.assert_array_equal(a["timestamp"], b["timestamp"])


def test_right_to_delete_scrub():
    gen = _gen(users=2)
    as_of = 3 * ev.MS_PER_DAY
    truth = gen.history_until(0, as_of)
    victim = int(truth["item_id"][0])
    store, report = _build_store(
        gen, 2, as_of, scrub=make_scrub(deleted_items=[victim])
    )
    assert report.scrubbed_events > 0
    got = store.scan(ScanRequest(0, "core", 0, as_of))
    assert victim not in got["item_id"]


def test_scrub_is_idempotent_across_generations():
    gen = _gen(users=2)
    as_of = 3 * ev.MS_PER_DAY
    truth = gen.history_until(0, as_of)
    victim = int(truth["item_id"][0])
    scrub = make_scrub(deleted_items=[victim])
    store = ImmutableUIHStore(SCHEMA, n_shards=4)
    pipe = CompactionPipeline(SCHEMA, CompactionConfig(stripe_len=16))
    source = lambda uid, lo, hi: ev.time_slice(gen.history_until(uid, hi), lo, hi)
    pipe.run(source, [0, 1], as_of, store, scrub=scrub)
    first = store.scan(ScanRequest(0, "core", 0, as_of))
    pipe.run(source, [0, 1], as_of, store, scrub=scrub)  # re-run: same result
    second = store.scan(ScanRequest(0, "core", 0, as_of))
    np.testing.assert_array_equal(first["timestamp"], second["timestamp"])
    assert store.generation == 1


def test_schema_evolution_single_run():
    """Adding a SideInfo trait only requires one compaction run (§4.3)."""
    gen = _gen(users=2)
    as_of = 3 * ev.MS_PER_DAY
    new_trait = ev.TraitSpec("is_weekend", np.dtype(np.int8), ev.SPARSE_FLAG)
    evolved = SCHEMA.with_traits(
        add=[new_trait],
        feature_groups={**{g: c for g, c in SCHEMA.feature_groups.items()},
                        "sideinfo": SCHEMA.feature_groups["sideinfo"] + ("is_weekend",)},
    )

    def source(uid, lo, hi):
        h = ev.time_slice(gen.history_until(uid, hi), lo, hi)
        day_of_week = (h["timestamp"] // ev.MS_PER_DAY) % 7
        h["is_weekend"] = (day_of_week >= 5).astype(np.int8)
        return h

    store = ImmutableUIHStore(evolved, n_shards=2)
    pipe = CompactionPipeline(evolved, CompactionConfig(stripe_len=16))
    pipe.run(source, [0, 1], as_of, store)
    got = store.scan(ScanRequest(0, "sideinfo", 0, as_of))
    assert "is_weekend" in got
    # deprecating works the same way
    shrunk = evolved.with_traits(drop=["surface"])
    store2 = ImmutableUIHStore(shrunk, n_shards=2)
    pipe2 = CompactionPipeline(shrunk, CompactionConfig(stripe_len=16))

    def source2(uid, lo, hi):
        h = source(uid, lo, hi)
        h.pop("surface")
        return h

    pipe2.run(source2, [0, 1], as_of, store2)
    got2 = store2.scan(ScanRequest(0, "sideinfo", 0, as_of))
    assert "surface" not in got2 and "is_weekend" in got2


# -- symmetric sharding -----------------------------------------------------------

def test_shard_router_stable_and_uniform():
    r = ShardRouter(8)
    ids = np.arange(10_000)
    shards = np.array([r.route(int(u)) for u in ids])
    counts = np.bincount(shards, minlength=8)
    assert counts.min() > 0.7 * counts.mean()
    assert shard_of(12345, 8) == shard_of(12345, 8)


def test_shard_of_golden_values():
    """The hash placement is LOAD-BEARING persistent state: warehouse buckets
    and store shards are bucketed with it, so a silent drift (new mix
    constants, int-width change) would invalidate symmetric bucketing of
    every already-written generation. Golden values pin it forever."""
    golden = {
        0: [0, 0, 0, 0, 0],
        1: [0, 1, 1, 1, 9],
        2: [0, 0, 2, 2, 10],
        7: [0, 0, 2, 6, 6],
        42: [0, 1, 1, 5, 13],
        999_983: [0, 0, 0, 0, 0],
        123_456_789: [0, 0, 2, 6, 14],
        2**31 - 1: [0, 1, 3, 3, 11],
        2**63 - 1: [0, 1, 3, 7, 7],
    }
    for user_id, want in golden.items():
        got = [shard_of(user_id, n) for n in (1, 2, 4, 8, 16)]
        assert got == want, f"shard_of({user_id}) drifted: {got} != {want}"


def test_symmetric_sharding_zero_fanout_for_bucketed_batch():
    """A user-bucketed batch touches exactly one immutable shard (§4.2.3)."""
    n_shards = 8
    r = ShardRouter(n_shards)
    users = [u for u in range(200) if r.route(u) == 3][:16]
    store = ImmutableUIHStore(SCHEMA, n_shards=n_shards)
    reqs = [ScanRequest(u, "core", 0, 10**12) for u in users]
    assert store.fanout(reqs) == 1


# -- column-wise window assembly: byte identity with the per-stripe reference --

def _reference_scan(node, req):
    """The window as the per-stripe path built it: each chosen stripe decoded
    from its raw blob with its header parsed, then ``concat_batches``,
    ``time_slice`` and ``tail_view``."""
    traits = req.traits or node.schema.group_traits(req.group)
    _, entry = node._locate(req.user_id, req.group, req.generation)
    chosen = node._select_stripes(req, entry) if entry is not None else []
    parts = [columnar.decode_stripe(s.blob, node.schema, traits) for s in chosen]
    out = ev.concat_batches(parts)
    if not out:
        return ev.empty_batch(node.schema, traits)
    return ev.tail_view(ev.time_slice(out, req.start_ts, req.end_ts),
                        req.max_events)


def _assert_same_columns(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.fixture(scope="module")
def windows_stores():
    """A monolith and a 2-node sharded store over the same two generations:
    generation 0 is leased, then generation 1 scrubs an item from it, so a
    pinned scan of generation 0 reads a retained table."""
    from repro.storage.sharded_store import ShardedUIHStore

    gen = _gen(users=5, days=6)
    as_of = 5 * ev.MS_PER_DAY
    truth = gen.history_until(0, as_of)
    scrub = make_scrub(deleted_items=[int(truth["item_id"][5])])
    source = lambda uid, lo, hi: ev.time_slice(gen.history_until(uid, hi), lo, hi)
    pipe = CompactionPipeline(SCHEMA, CompactionConfig(stripe_len=16))
    stores = {"monolith": ImmutableUIHStore(SCHEMA, n_shards=4),
              "sharded": ShardedUIHStore(SCHEMA, n_shards=4, n_nodes=2)}
    leases = []
    for store in stores.values():
        pipe.run(source, list(range(5)), as_of, store, generation=0)
        leases.append(store.acquire_lease())
        pipe.run(source, list(range(5)), as_of, store, scrub=scrub,
                 generation=1)
    yield stores, truth, as_of
    for lease in leases:
        lease.release()
    for store in stores.values():
        store.close()


def _window_cases(truth, as_of):
    ts = truth["timestamp"]
    mid_lo, mid_hi = int(ts[20]) + 1, int(ts[len(ts) - 20])  # inside stripes
    return {
        "all_traits": ScanRequest(0, "core", 0, as_of),
        "trait_subset": ScanRequest(0, "core", 0, as_of,
                                    traits=("timestamp", "item_id")),
        "other_group_subset": ScanRequest(0, "engagement", 0, as_of,
                                          traits=("timestamp", "like")),
        "below_window": ScanRequest(0, "core", 0, as_of, max_events=5),
        "above_window": ScanRequest(0, "core", 0, as_of, max_events=10**6),
        "cut_inside_stripes": ScanRequest(0, "sideinfo", mid_lo, mid_hi),
        "bounds_on_events": ScanRequest(0, "core", int(ts[20]),
                                        int(ts[len(ts) - 20])),
        "cut_and_budget": ScanRequest(0, "core", mid_lo, mid_hi,
                                      max_events=7,
                                      traits=("timestamp", "action_type")),
        "empty_window": ScanRequest(0, "core", mid_hi, mid_lo),
        "before_history": ScanRequest(0, "core", -10, -1),
        "missing_user": ScanRequest(99, "core", 0, as_of),
        "pinned_retained": ScanRequest(0, "core", 0, as_of, generation=0),
        "pinned_budget": ScanRequest(0, "core", mid_lo, as_of, max_events=40,
                                     generation=0),
    }


_CASES = ("all_traits", "trait_subset", "other_group_subset", "below_window",
          "above_window", "cut_inside_stripes", "bounds_on_events",
          "cut_and_budget",
          "empty_window", "before_history", "missing_user", "pinned_retained",
          "pinned_budget")


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("where", ["monolith", "store_node", "sharded"])
def test_columnwise_scan_matches_per_stripe_reference(windows_stores, where,
                                                      case):
    stores, truth, as_of = windows_stores
    req = _window_cases(truth, as_of)[case]
    if where == "monolith":
        store = node = stores["monolith"]
    else:
        store = stores["sharded"]
        node = store._node_for(req.user_id, req.generation)
        if where == "store_node":
            store = node
    want = _reference_scan(node, req)
    _assert_same_columns(store.scan(req), want)
    _assert_same_columns(store.multi_range_scan([req, req])[1], want)
    if case == "pinned_retained":   # the scrub changed the live window
        live = store.scan(ScanRequest(0, "core", 0, as_of))
        assert ev.batch_len(live) < ev.batch_len(want)
    if case in ("below_window", "cut_and_budget"):
        assert 0 < ev.batch_len(want) == req.max_events
    empty = case in ("empty_window", "before_history", "missing_user")
    assert (ev.batch_len(want) == 0) == empty


def test_kept_header_equals_parsed_header():
    """Compaction hands each stripe the header it encoded; a stripe made from
    a blob alone (a bulk load of stored stripes) parses it once. Both equal
    ``_read_header`` of the blob, and a decode from either reads the same
    columns as a decode that parses the blob."""
    from repro.storage.immutable_store import Stripe

    gen = _gen(users=2)
    as_of = 3 * ev.MS_PER_DAY
    store, _ = _build_store(gen, 2, as_of)
    made = [s for shard in store._shards for _, stripes in shard.values()
            for s in stripes]
    assert made
    loaded = [Stripe(s.start_ts, s.end_ts, s.n_events, s.blob) for s in made]
    reloaded = ImmutableUIHStore(SCHEMA, n_shards=4)
    reloaded.bulk_load({(0, "core"): loaded[:3]}, generation=0)
    for s in made + loaded:
        header, body_off = columnar._read_header(s.blob)
        assert s.layout.header == header
        assert s.layout.body_off == body_off
        traits = tuple(c["name"] for c in header["cols"])
        want = columnar.decode_stripe(s.blob, SCHEMA)
        got = dict(zip(traits, s.layout.decode(s.blob, traits)))
        _assert_same_columns(got, want)
        assert s.layout.decoded_bytes(traits[:2]) == sum(
            c["len"] for c in header["cols"][:2])
    assert loaded[0] == made[0]   # the layout is not part of a stripe's identity
