"""Unified telemetry (DESIGN.md §13): metrics registry, pipeline spans,
control-plane event timeline, report CLI.

Covers the PR's acceptance spine:
  * registry semantics — monotone counters, additive gauges, fixed-bucket
    histograms with LatencyTracker-compatible quantiles, exact merges, the
    ``publish_dataclass`` naming rule, Prometheus text exposition;
  * span completeness under chaos — a 4-node r=2 replicated tier run through
    a combined fault plan (worker crash, compaction-during-scan race, node
    flap) at ``sample_every=1``: every emitted batch carries a complete,
    monotonically-ordered span chain; zero orphan item spans survive the
    drain; the report shows the breaker transition, the worker restart and
    the generation flip, and >= 90% of measured starvation is attributed to
    a named stage;
  * overhead guard — the span ops added per pipeline item at the DEFAULT
    sampling rate cost well under the 2% rows/s budget enforced (as an
    end-to-end paired measurement) by ``benchmarks/bench_feed.py``.
"""
import dataclasses
import json
import sys
import time
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:  # make `benchmarks.*` importable
    sys.path.insert(0, str(REPO_ROOT))

from conftest import make_sim
from repro.core.projection import TenantProjection
from repro.data import DatasetSpec, WarehouseSource, open_feed, resume_fingerprint
from repro.dpp.featurize import FeatureSpec
from repro.obs import DEFAULT_SAMPLE_EVERY, EventLog, MetricsRegistry, Telemetry
from repro.obs.registry import Counter, Gauge, Histogram, publish_dataclass
from repro.obs.report import render_report
from repro.obs.spans import SpanTracker, critical_path, current_span
from repro.testing import FaultPlan, FaultSpec, wrap_sim

TENANT = TenantProjection(
    "t", 16, ("core",),
    traits_per_group={"core": ("timestamp", "item_id", "action_type")})
FEATURES = FeatureSpec(seq_len=16, uih_traits=("item_id", "action_type"))


def _spec(source, **kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("base_batch_size", 4)
    kw.setdefault("n_workers", 2)
    kw.setdefault("prefetch_depth", 0)
    # no cross-batch window cache: every work item issues at least one store
    # scan, so the fault schedule's scan ticks are always reached AND every
    # sampled item span carries a scan stage
    kw.setdefault("window_cache_size", 0)
    return DatasetSpec(tenant=TENANT, source=source, features=FEATURES, **kw)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_monotone_set_total_and_merge():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.set_total(10.0)
    assert c.value == 10.0
    c.set_total(4.0)          # republishing an older snapshot cannot regress
    assert c.value == 10.0
    other = Counter()
    other.inc(5.0)
    c.merge_from(other)       # counters add across workers
    assert c.value == 15.0


def test_gauge_last_write_and_additive_merge():
    g = Gauge()
    g.set(7.0)
    g.set(3.0)
    assert g.value == 3.0
    g.inc()
    g.dec(2.0)
    assert g.value == 2.0
    other = Gauge()
    other.set(5.0)
    g.merge_from(other)       # per-worker queue depths sum tier-wide
    assert g.value == 7.0


def test_histogram_bucket_quantiles_and_merge():
    h = Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(0.605)
    # interpolated quantiles stay inside the populated buckets
    assert 0.0 < h.quantile(0.5) <= 0.1
    assert 0.1 < h.quantile(0.99) <= 1.0
    snap = h.to_dict()
    assert snap["count"] == 4 and snap["min"] == 0.005 and snap["max"] == 0.5
    assert snap["p50"] is not None and snap["p99"] is not None
    other = Histogram(buckets=(0.01, 0.1, 1.0))
    other.observe(0.05)
    h.merge_from(other)       # bucket vectors add exactly
    assert h.count == 5
    with pytest.raises(ValueError):
        h.merge_from(Histogram(buckets=(0.5, 5.0)))


def test_histogram_window_latency_tracker_compat():
    # window mode serves the legacy LatencyTracker contract: None below
    # min_samples, index-method quantile over the sorted window
    h = Histogram(window=64, min_samples=5)
    for v in (0.1, 0.2, 0.3):
        h.record(v)           # LatencyTracker-compatible alias
    assert h.quantile(0.5) is None
    h.record(0.4)
    h.record(0.5)
    assert h.quantile(0.5) == 0.3
    assert h.quantile(0.99) == 0.5
    assert h.observed_at_least(0.3) == 3


def test_family_label_validation_and_kind_conflicts():
    reg = MetricsRegistry()
    fam = reg.counter("repro_test_ops_total", labels=("node",))
    fam.labels(node=1).inc()
    fam.labels(node=1).inc()
    fam.labels(node=2).inc(3)
    by_node = {lbl["node"]: child.value for lbl, child in fam.series()}
    assert by_node == {"1": 2.0, "2": 3.0}
    with pytest.raises(ValueError):
        fam.labels()                       # missing the node label
    with pytest.raises(ValueError):
        fam.labels(node=1, extra="x")      # unknown label
    with pytest.raises(ValueError):
        reg.gauge("repro_test_ops_total")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("repro_test_ops_total", labels=("shard",))  # label conflict


def test_registry_merge_from_and_prometheus_text():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("repro_x_total", help="x ops").inc(2)
    b.counter("repro_x_total").inc(3)
    b.gauge("repro_depth").set(4)
    b.histogram("repro_rtt_seconds").observe(0.02)
    a.merge_from(b)
    assert a.counter("repro_x_total").value == 5.0
    assert a.gauge("repro_depth").value == 4.0
    assert a.histogram("repro_rtt_seconds").count == 1
    text = a.prometheus_text()
    assert "# HELP repro_x_total x ops" in text
    assert "# TYPE repro_x_total counter" in text
    assert "repro_x_total 5.0" in text
    assert 'repro_rtt_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_rtt_seconds_count 1" in text


@dataclasses.dataclass
class _FakeStats:
    scans: int = 0
    bytes_scanned: int = 0
    depth: float = 0.0
    healthy: bool = True            # bools are skipped
    extra: dict = dataclasses.field(default_factory=dict)  # non-numeric: skipped


def test_publish_dataclass_naming_rule_and_monotonicity():
    reg = MetricsRegistry()
    st = _FakeStats(scans=10, bytes_scanned=4096, depth=2.0)
    publish_dataclass(reg, st, prefix="fake", labels={"node": 0},
                      gauge_fields=("depth",))
    names = {f.name: f.kind for f in reg.families()}
    assert names == {"repro_fake_scans_total": "counter",
                     "repro_fake_bytes_scanned_total": "counter",
                     "repro_fake_depth": "gauge"}
    # republish an OLDER snapshot: counters hold, the gauge follows
    publish_dataclass(reg, _FakeStats(scans=4, bytes_scanned=100, depth=1.0),
                      prefix="fake", labels={"node": 0},
                      gauge_fields=("depth",))
    assert reg.counter("repro_fake_scans_total",
                       labels=("node",)).labels(node=0).value == 10.0
    assert reg.gauge("repro_fake_depth",
                     labels=("node",)).labels(node=0).value == 1.0


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def test_event_log_ring_seq_and_jsonl(tmp_path):
    log = EventLog(capacity=4)
    for i in range(6):
        log.emit("breaker_open", node=i)
    log.emit("failover", frm=1, to=2)
    events = log.snapshot()
    assert len(events) == 4                       # ring keeps the newest
    assert [e.seq for e in events] == [4, 5, 6, 7]  # seq never reused
    assert log.emitted == 7
    mono = [e.t_mono for e in events]
    assert mono == sorted(mono)
    assert log.counts() == {"breaker_open": 3, "failover": 1}
    p = tmp_path / "events.jsonl"
    log.write_jsonl(p)
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    assert recs[-1]["kind"] == "failover" and recs[-1]["frm"] == 1
    assert {"seq", "t_mono", "t_wall", "kind"} <= set(recs[0])


# ---------------------------------------------------------------------------
# span tracker (synthetic pipeline)
# ---------------------------------------------------------------------------

def _run_item(tr, seq):
    sp = tr.mint(seq)
    tr.enter_item(seq)
    now = time.perf_counter()
    amb = current_span()
    if amb is not None:
        amb.stage("scan", now, now + 1e-4)
        amb.stage("featurize", now + 1e-4, now + 2e-4)
        amb.stage("place", now + 2e-4, now + 3e-4)
    tr.exit_item()
    tr.finish_item(seq)
    return sp


def test_span_tracker_full_lifecycle_and_registry_export():
    reg = MetricsRegistry()
    tr = SpanTracker(sample_every=1, registry=reg)
    spans = [_run_item(tr, i) for i in range(4)]
    tr.emit_batch(0, spans[:2], rows=8)
    tr.emit_batch(1, spans[2:], rows=8)
    assert tr.mark_delivered() is not None
    assert tr.record_train(0.001) is not None
    assert tr.mark_delivered() is not None
    assert tr.record_train(0.001) is not None
    tr.drain()
    assert tr.orphan_items() == []
    lc = tr.lifecycle_counts()
    assert lc["minted"] == 4 and lc["emitted_batches"] == 2
    assert lc["delivered_batches"] == 2 and lc["completed"] == 2
    assert lc["dropped_in_flight"] == 0 and lc["abandoned"] == 0
    for bs in tr.completed:
        assert bs.sampled and bs.t_deliver is not None
        assert bs.t_deliver >= bs.t_emit
        assert bs.latency_s() > 0
        assert "train" in bs.stages
        for sp in bs.items:
            assert sp.stages["scan"][0] <= sp.stages["featurize"][0] \
                <= sp.stages["place"][0]
    # per-stage histogram observed into the registry at finalize time
    hist = reg.histogram("repro_stage_seconds", labels=("stage",))
    by_stage = {lbl["stage"]: child.count for lbl, child in hist.series()}
    assert by_stage["scan"] == 4 and by_stage["train"] == 2


def test_span_sampling_placeholders_keep_fifos_aligned():
    tr = SpanTracker(sample_every=2)
    spans = [_run_item(tr, seq) for seq in range(6)]
    assert tr.minted == 3           # seqs 0,2,4 sampled; 1,3,5 not
    assert spans[1] is None and spans[2] is not None
    assert current_span() is None   # TLS cleared after every item
    # batches alternate sampled / placeholder; the FIFO stays in lockstep
    tr.emit_batch(0, [], rows=8)    # placeholder
    tr.emit_batch(1, [spans[0], spans[2]], rows=8)
    ph = tr.mark_delivered()
    assert ph is not None and not ph.sampled and ph.t_deliver is None
    bs = tr.mark_delivered()
    assert bs is not None and bs.sampled
    tr.record_train(0.0)            # placeholder: no finalize
    tr.record_train(0.0)
    assert len(tr.completed) == 1 and tr.delivered_batches == 2


def test_span_abandon_and_drop_accounting():
    tr = SpanTracker(sample_every=1)
    tr.mint(0)
    tr.enter_item(0)
    tr.exit_item()
    tr.abandon(0)                   # retries exhausted: accounted, not orphaned
    sp = _run_item(tr, 1)
    tr.emit_batch(0, [sp], rows=4)  # emitted but never delivered
    tr.drain()
    lc = tr.lifecycle_counts()
    assert lc["abandoned"] == 1 and lc["dropped_in_flight"] == 1
    assert tr.orphan_items() == [] and lc["live_items"] == 0


def test_span_tracker_rejects_bad_sampling():
    with pytest.raises(ValueError):
        SpanTracker(sample_every=0)


def test_critical_path_attribution_math():
    totals = {"scan": 3.0, "featurize": 1.0, "place": 0.0}
    cp = critical_path(totals, starved_host_s=2.0, starved_h2d_s=1.0,
                       starved_time_s=3.0)
    assert cp["attribution_s"]["h2d"] == pytest.approx(1.0)
    assert cp["attribution_s"]["scan"] == pytest.approx(1.5)   # 3/4 of host
    assert cp["attribution_s"]["featurize"] == pytest.approx(0.5)
    assert cp["attributed_frac"] == pytest.approx(1.0)
    assert cp["dominant_stage"] == "scan"
    # no sampled host spans: the host share falls back to scan (the stage
    # owning the store round-trip)
    cp = critical_path({}, starved_host_s=2.0, starved_time_s=2.0)
    assert cp["attribution_s"] == {"scan": 2.0}
    # nothing starved: vacuously fully attributed
    assert critical_path(totals)["attributed_frac"] == 1.0


# ---------------------------------------------------------------------------
# telemetry facade + run dir + report CLI
# ---------------------------------------------------------------------------

def test_write_run_dir_and_report_render(tmp_path):
    tel = Telemetry(sample_every=1)
    tr = tel.spans
    sp = _run_item(tr, 0)
    tr.emit_batch(0, [sp], rows=8)
    tr.mark_delivered()
    tr.record_train(0.002)
    tel.events.emit("generation_flip", store="immutable", generation=3)
    tel.events.emit("breaker_open", node=1, prev="closed")
    tel.publish_stats(_FakeStats(scans=7), "fake")
    run_dir = tel.write_run_dir(tmp_path / "run")
    for name in ("metrics.json", "metrics.prom", "events.jsonl",
                 "spans.jsonl", "summary.json"):
        assert (run_dir / name).exists(), name
    summary = json.loads((run_dir / "summary.json").read_text())
    assert summary["spans"]["completed"] == 1
    assert summary["events"] == {"generation_flip": 1, "breaker_open": 1}
    out = render_report(run_dir)
    assert "per-stage breakdown" in out and "scan" in out
    assert "starvation attribution" in out
    assert "generation_flip" in out and "breaker_open" in out
    assert "span lifecycle" in out


def test_report_cli_main(tmp_path, capsys):
    from repro.obs import report as report_mod

    tel = Telemetry()
    tel.events.emit("worker_restart")
    run_dir = tel.write_run_dir(tmp_path / "run")
    assert report_mod.main([str(run_dir), "--top-k", "3"]) == 0
    out = capsys.readouterr().out
    assert "telemetry report" in out and "worker_restart" in out
    with pytest.raises(FileNotFoundError):
        render_report(tmp_path / "missing")


def test_dataset_spec_telemetry_excluded_from_identity():
    # the telemetry handle must not perturb spec equality or the resume
    # fingerprint (a resumed run constructs a FRESH Telemetry)
    a = _spec(WarehouseSource())
    b = dataclasses.replace(a, telemetry=Telemetry())
    assert a == b
    assert resume_fingerprint(a) == resume_fingerprint(b)


# ---------------------------------------------------------------------------
# chaos integration: span completeness + acceptance report
# ---------------------------------------------------------------------------

CHAOS_FAULTS = [
    FaultSpec("worker_crash", 1),               # pool self-healing + restart
    FaultSpec("compaction_during_scan", 2),     # generation flip races a read
    # replica failover + breaker: two worker threads race for the scan ticks,
    # so the flap spans four of them to be sure a read of node 1 lands in it
    FaultSpec("node_flap", 3, node=1, duration=4),
]


@pytest.fixture(scope="module")
def chaos_run(tmp_path_factory):
    """One chaotic 4-node r=2 run, every item sampled, shared by the
    completeness and acceptance-report tests."""
    sim = make_sim(users=6, days=2, seed=5, nodes=4, replication=2)
    # a single failure must flip the breaker: the flap lasts 4 scan ticks, so
    # the default threshold of 3 consecutive failures may never be reached
    for b in sim.immutable._breakers:
        b.threshold = 1
    plan = FaultPlan(
        CHAOS_FAULTS,
        on_compact=lambda: sim.run_compaction(sim.compaction_watermark,
                                              evict=False))
    tel = Telemetry(sample_every=1)
    spec = _spec(WarehouseSource(), consistency="audit", telemetry=tel)
    feed = open_feed(spec, wrap_sim(sim, plan))
    batches = []
    for b in feed:
        batches.append(b)
        feed.record_train_step(0.001)   # close each chain with a train stage
    feed.join()
    feed.close()
    assert plan.n_fired == len(CHAOS_FAULTS)
    run_dir = tel.write_run_dir(tmp_path_factory.mktemp("obs") / "chaos")
    return {"tel": tel, "feed": feed, "batches": batches, "sim": sim,
            "run_dir": run_dir}


def test_chaos_every_batch_has_complete_monotonic_span_chain(chaos_run):
    tel, batches = chaos_run["tel"], chaos_run["batches"]
    tr = tel.spans
    rows = sum(len(b["user_id"]) for b in batches)
    assert rows == len(chaos_run["sim"].examples)

    # zero orphans: every minted span was placed or abandoned by the drain
    assert tr.orphan_items() == []
    lc = tr.lifecycle_counts()
    assert lc["abandoned"] == 0 and lc["live_items"] == 0
    assert lc["emitted_batches"] == len(batches)
    assert lc["delivered_batches"] == len(batches)
    assert lc["dropped_in_flight"] == 0
    assert lc["completed"] == len(batches)

    completed = list(tr.completed)
    seen_seqs = set()
    for bs in completed:
        assert bs.sampled and bs.items, "sampled batch lost its item spans"
        assert bs.t_deliver is not None and bs.t_deliver >= bs.t_emit
        assert bs.t_train_end is not None and bs.t_train_end >= bs.t_deliver
        assert bs.latency_s() > 0
        for sp in bs.items:
            seen_seqs.add(sp.seq)
            # complete chain: every surviving attempt scanned the store
            # (window cache off), featurized, and was placed — in that order
            for name in ("scan", "featurize", "place"):
                assert name in sp.stages, (bs.emit_seq, sp.seq, sp.stages)
            assert sp.t_mint <= sp.stages["scan"][0]
            assert sp.stages["scan"][0] <= sp.stages["featurize"][0]
            assert sp.stages["featurize"][0] <= sp.stages["place"][0]
            # the commit that stamped t_emit happens INSIDE the final
            # contributor's place window, so only the start ordering holds
            assert sp.stages["place"][0] <= bs.t_emit
            assert sp.attempts >= 1
            # the scan stage carries its IOStats delta (an item whose users
            # have no history yet legitimately scans zero bytes)
            assert "bytes_scanned" in sp.meta and "bytes_decoded" in sp.meta
    assert sum(sp.meta["bytes_scanned"]
               for bs in completed for sp in bs.items) > 0
    # every minted work item contributed rows to some emitted batch
    assert len(seen_seqs) == lc["minted"]
    # the crashed item's surviving chain records the retry
    assert max(sp.attempts for bs in completed for sp in bs.items) >= 2

    # the control-plane timeline saw the whole story
    counts = tel.events.counts()
    assert counts.get("worker_crash", 0) >= 1
    assert counts.get("item_requeued", 0) >= 1
    assert counts.get("worker_restart", 0) >= 1
    assert counts.get("generation_flip", 0) >= 1
    assert counts.get("breaker_open", 0) >= 1
    assert counts.get("node_down", 0) >= 1
    assert counts.get("node_recover", 0) >= 1
    # event seqs strictly increase (the timeline is ordered)
    seqs = [e.seq for e in tel.events.snapshot()]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_chaos_report_meets_acceptance(chaos_run):
    tel, run_dir = chaos_run["tel"], chaos_run["run_dir"]
    # >= 90% of measured starvation attributed to a named stage
    cp = tel.summary()["critical_path"]
    assert cp["attributed_frac"] >= 0.9
    if cp["starved_time_s"] > 0:
        assert cp["dominant_stage"] in ("scan", "featurize", "place", "h2d")
    out = render_report(run_dir)
    assert "breaker_open" in out            # >= 1 breaker transition
    assert "worker_restart" in out          # >= 1 worker restart
    assert "generation_flip" in out         # >= 1 generation flip
    assert "starvation attribution" in out
    assert "attributed: 100.0%" in out or "attributed: 9" in out
    # store counters flushed through Feed.close() -> publish_telemetry()
    metrics = json.loads((run_dir / "metrics.json").read_text())
    assert any(name.startswith("repro_client_") for name in metrics)
    assert any(name.startswith("repro_worker_") for name in metrics)
    assert any(name.startswith("repro_io_") for name in metrics)


def test_feed_snapshot_members_are_copies(chaos_run):
    feed = chaos_run["feed"]
    snap = feed.stats()
    assert snap.workers is not None
    live = feed.client_stats.full_batches
    snap.client.full_batches += 1000
    assert feed.client_stats.full_batches == live
    # and the legacy attribute contract still reads through live
    assert feed.stats.full_batches == live


# ---------------------------------------------------------------------------
# overhead guard (<= 2% budget at the default sampling rate)
# ---------------------------------------------------------------------------

def test_telemetry_overhead_budget():
    """Deterministic form of the bench_feed guard: the span ops added per
    pipeline item at DEFAULT_SAMPLE_EVERY must cost well under 2% of the
    telemetry-off pipeline wall time for the same workload.  (bench_feed's
    feed/telemetry_overhead measures the same budget end-to-end with paired
    order-alternating runs; this test bounds the op cost directly so a hot-
    path regression fails CI without depending on a quiet machine.)"""
    from benchmarks.bench_feed import _feed_slot, _synth

    seq_len, base, full = 256, 16, 64
    n = 16 * full
    spec = FeatureSpec(seq_len=seq_len,
                       uih_traits=("item_id", "action_type", "watch_time_ms",
                                   "like"),
                       candidate_fields=("item_id",), label_fields=("click",))
    examples, uihs = _synth(n, seq_len)
    chunks = [(examples[i:i + base], uihs[i:i + base])
              for i in range(0, n, base)]

    # telemetry-off pipeline time (the denominator): best of 3
    t_off = min(_time_once(lambda: _feed_slot(chunks, spec, full,
                                              recycle=True))
                for _ in range(3))

    # pure telemetry op cost for the same item/batch counts, default sampling
    n_items, n_batches = len(chunks), n // full
    tel = Telemetry()   # DEFAULT_SAMPLE_EVERY
    tr = tel.spans
    assert tr.sample_every == DEFAULT_SAMPLE_EVERY

    def _ops():
        pending = []
        for i in range(n_items):
            tr.mint(i)
            tr.enter_item(i)
            sp = current_span()
            if sp is not None:
                now = time.perf_counter()
                sp.stage("scan", now, now)
                sp.stage("featurize", now, now)
                sp.stage("place", now, now)
                pending.append(sp)
            tr.exit_item()
            tr.finish_item(i)
            if (i + 1) % (n_items // n_batches) == 0:
                tr.emit_batch(i, pending, full)
                pending = []
                tr.mark_delivered()
                tr.record_train(0.0)
        tr.drain()

    t_ops = min(_time_once(_ops) for _ in range(5))
    # the ops are ~100x below budget; even heavy scheduler noise on t_off
    # cannot flip this assertion
    assert t_ops <= 0.02 * t_off, (
        f"telemetry op cost {1e3 * t_ops:.3f}ms exceeds 2% of the "
        f"{1e3 * t_off:.1f}ms telemetry-off pipeline time "
        f"(sample_every={DEFAULT_SAMPLE_EVERY})")


def _time_once(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
