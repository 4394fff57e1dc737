"""``open_feed``: compile a declarative ``DatasetSpec`` into the data plane.

One compiler replaces the two hand-wired pipelines that used to live in
``launch.steps`` (``make_device_feed`` for batch, ``make_streaming_feed`` for
streaming — both now thin deprecated shims):

  batch  spec --> work items (warehouse buckets | affinity-planned sim epochs)
                 --> DPPWorkerPool(WorkerPlan) --> RebatchingClient
  stream spec --> StreamingSession (micro-batching, backfill handoff,
                 generation-lease release, freshness)
  either --> optional DevicePrefetcher stage (cell-sharded device batches)
  --> Feed  (one protocol, consumed identically by the Trainer)

The ``sim`` argument is the data-platform handle: a ``ProductionSim`` or any
object exposing ``schema``, ``immutable`` (the store), plus ``warehouse`` /
``stream`` / ``examples`` for the matching source kinds.
"""
from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

from repro.core.backoff import Backoff
from repro.core.materialize import Materializer
from repro.data.feed import Feed
from repro.data.spec import (
    DatasetSpec,
    SimSource,
    StreamSource,
    WarehouseSource,
    resume_fingerprint,
)
from repro.dpp.affinity import plan_affine
from repro.dpp.client import RebatchingClient
from repro.dpp.elastic import DPPWorkerPool
from repro.dpp.worker import WorkerPlan


def compile_worker_plan(spec: DatasetSpec, sim: Any) -> WorkerPlan:
    """The per-worker slice of a spec: projection + features + a thread-local
    materializer factory carrying the spec's consistency/generation policy."""
    schema = sim.schema
    store = sim.immutable
    features = spec.resolve_features(schema)

    def make_materializer() -> Materializer:
        return Materializer(
            store, schema,
            validate_checksum=spec.validate_checksum,
            pin_generations=spec.pin_generations,
            window_cache_size=spec.window_cache_size,
        )

    return WorkerPlan(projection=spec.tenant, feature_spec=features,
                      schema=schema, make_materializer=make_materializer)


def _retry_backoff(spec: DatasetSpec) -> Optional[Backoff]:
    """Seeded deterministic backoff between a work item's crash-recovery
    retries (the same shared helper the store failover executor uses): short
    enough not to stall a healthy pool, long enough that the second retry of
    a node-outage item usually lands after the flap, and a pure function of
    the spec seed so chaos runs stay reproducible."""
    if spec.max_item_retries <= 0:
        return None
    return Backoff(base_s=0.005, multiplier=2.0, max_s=0.1, jitter=0.5,
                   seed=spec.reshuffle_seed or 0)


def _batch_items(spec: DatasetSpec, sim: Any) -> List[list]:
    """The batch work list a spec describes (each item = one worker unit)."""
    src = spec.source
    bb = spec.base_batch_size
    if isinstance(src, WarehouseSource):
        hours = (list(src.hours) if src.hours is not None
                 else sim.warehouse.hours())
        items: List[list] = []
        for _ in range(src.epochs):
            for hour in hours:
                # buckets ARE the affinity plan: user-clustered at ingestion,
                # bucket key == storage shard key (§4.2.3)
                for bucket in sim.warehouse.iter_bucketed(hour):
                    for lo in range(0, len(bucket), bb):
                        items.append(bucket[lo:lo + bb])
        return items
    assert isinstance(src, SimSource)
    examples = list(sim.examples)
    if not examples:
        return []
    n_shards = sim.immutable.n_shards
    # honor the live generation's placement map (heavy-tail overrides): with a
    # sharded store, work items then stay NODE-local, not just shard-local
    placement = sim.immutable.live_placement()
    rng = np.random.default_rng(spec.reshuffle_seed or 0)
    items = []
    rows, epoch_i = 0, 0
    while True:
        epoch = ([examples[i] for i in rng.permutation(len(examples))]
                 if src.shuffle else list(examples))
        items.extend(plan_affine(epoch, n_shards, bb, placement=placement).items)
        rows += len(epoch)
        epoch_i += 1
        if src.min_rows is not None:
            if rows >= src.min_rows:
                break
        elif epoch_i >= src.epochs:
            break
    return items


def _skip_rows(items: List[list], n: int) -> List[list]:
    """Drop the first ``n`` example rows of a work-item list (crash resume):
    whole items that fall inside the trained prefix disappear, the boundary
    item is trimmed. Row ORDER is untouched, so an ordered feed over the
    result continues the uninterrupted run's batch sequence exactly."""
    if n <= 0:
        return items
    out: List[list] = []
    remaining = n
    for item in items:
        if remaining <= 0:
            out.append(item)
        elif len(item) <= remaining:
            remaining -= len(item)
        else:
            out.append(item[remaining:])
            remaining = 0
    return out


def _warehouse_hour_rows(spec: DatasetSpec, sim: Any) -> List[tuple]:
    """(hour, rows) pairs in replay order (epochs repeated) — the metadata
    behind the checkpoint's observability cursor (hour + intra-hour offset)."""
    src = spec.source
    hours = (list(src.hours) if src.hours is not None
             else sim.warehouse.hours())
    per_hour = [(h, sim.warehouse.hour_rows(h)) for h in hours]
    return per_hour * src.epochs


def _check_resume(spec: DatasetSpec, resume_from: dict) -> tuple:
    """Validate a checkpoint against the spec; returns (rows, batches)."""
    fp = resume_fingerprint(spec)
    got = resume_from.get("fingerprint")
    if got is not None and got != fp:
        raise ValueError(
            "resume_from was checkpointed by a different DatasetSpec "
            f"(fingerprint mismatch):\n  checkpoint: {got}\n  spec:       {fp}")
    want_kind = "stream" if isinstance(spec.source, StreamSource) else "batch"
    kind = resume_from.get("kind", want_kind)
    if kind != want_kind:
        raise ValueError(
            f"resume_from is a {kind!r} checkpoint but the spec compiles a "
            f"{want_kind!r} feed")
    if not spec.ordered:
        raise ValueError("resume requires DatasetSpec.ordered=True "
                         "(deterministic in-order placement)")
    return (int(resume_from.get("trained_rows", 0)),
            int(resume_from.get("trained_batches", 0)))


def cell_input_sharding(cell: Any, mesh: Any):
    """NamedSharding tree for a cell's batch argument (device feed target)."""
    if cell is None or mesh is None:
        return None
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    batch_spec = cell.in_shardings[-1]
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        batch_spec, is_leaf=lambda x: isinstance(x, P))


def _check_device_materialize(spec: DatasetSpec, depth: int,
                              prep_fn) -> None:
    """Refuse a ``device_materialize=True`` spec that the feed cannot
    honour, rather than quietly densifying on the host (DESIGN §3)."""
    why = None
    if isinstance(spec.source, StreamSource):
        why = "a streaming source (streaming sessions densify on the host)"
    elif depth <= 0:
        why = ("no device-prefetch stage (prefetch_depth is 0, or None "
               "without a cell): nothing runs the kernel on the device")
    elif prep_fn is not None:
        why = "a prep_fn, which expects dense host batches"
    if why is not None:
        raise ValueError(f"device_materialize=True cannot be honoured with "
                         f"{why}; set device_materialize=False")


def open_feed(
    spec: DatasetSpec,
    sim: Any,
    *,
    cell: Any = None,
    mesh: Any = None,
    prep_fn=None,
    controller: Any = None,
    resume_from: Optional[dict] = None,
) -> Feed:
    """Compile ``spec`` against ``sim``'s data platform and start the feed.

    * ``cell``/``mesh`` (optional) — target the device-prefetch stage at a
      ``launch.steps.Cell``'s batch shardings (device batches land laid out
      exactly as the jit'd step expects);
    * ``prep_fn`` — model-specific host transform; runs inside the prefetch
      thread when there is one, else on the consumer's ``get``;
    * ``controller`` — optional ``ElasticController`` for live pool resizing;
    * ``resume_from`` — a ``Feed.checkpoint()`` dict (saved by the
      ``CheckpointManager`` as the model checkpoint's ``feed_state`` sidecar):
      the compiled feed produces exactly the examples the killed run had NOT
      yet trained — batch feeds skip the trained row prefix of the canonical
      item order and resume the reshuffle emit counter; streaming feeds apply
      the checkpoint's ``ReplayFilter`` chain to the warehouse re-replay and
      dedupe live ids below the watermark (exactly-once, §10).

    Returns a started ``Feed``; batch and streaming specs yield the same
    protocol. The caller owns shutdown: ``close()`` (or iterate to
    exhaustion + ``join()``).
    """
    plan = compile_worker_plan(spec, sim)
    tel = spec.telemetry
    if tel is not None:
        # attach to the store tier FIRST (generation flips / lease events /
        # breaker listeners / RTT histogram re-home); reaches the real store
        # through fault-injection wrappers, whose __setattr__ delegates
        sim.immutable.telemetry = tel
    # prefetch_depth=None means auto (device stage iff a cell is targeted);
    # an explicit 0 FORCES the host feed even with a cell
    depth = (spec.prefetch_depth if spec.prefetch_depth is not None
             else (2 if cell is not None else 0))
    sharding = cell_input_sharding(cell, mesh)
    base_rows, base_batches = (
        _check_resume(spec, resume_from) if resume_from else (0, 0))
    if spec.device_materialize:
        _check_device_materialize(spec, depth, prep_fn)

    if isinstance(spec.source, StreamSource):
        from repro.streaming.backfill import ReplayFilter
        from repro.streaming.session import StreamingSession
        from repro.streaming.source import MicroBatchConfig

        filters = []
        if resume_from:
            stream_state = resume_from.get("stream") or {}
            filters = [ReplayFilter.from_state(d)
                       for d in stream_state.get("filters", [])]
            if not spec.source.backfill:
                raise ValueError(
                    "streaming resume requires StreamSource(backfill=True): "
                    "the warehouse leg is the durable replay source")
        session = StreamingSession(
            sim.stream, plan,
            full_batch_size=spec.batch_size,
            micro_batch=MicroBatchConfig(
                max_examples=spec.source.micro_batch_examples,
                max_delay_s=spec.source.micro_batch_delay_s),
            n_workers=spec.n_workers,
            controller=controller,
            shuffle_seed=spec.reshuffle_seed,
            buffer_batches=spec.buffer_batches,
            backfill_from=sim.warehouse if spec.source.backfill else None,
            ordered=spec.ordered,
            max_item_retries=spec.max_item_retries,
            retry_backoff=_retry_backoff(spec),
            emit_seq_start=base_batches,
            resume_filters=filters,
            backfill_start_hour=spec.source.backfill_start_hour,
            backfill_end_hour=spec.source.backfill_end_hour,
        )
        if spec.ordered and session.coordinator is not None:
            # BEFORE start, and only when the feed will actually be
            # checkpointable (the Feed's pops are what bound this FIFO): the
            # resume cursor reads every emitted batch's row count from it
            # (prep_fn may reshape batches)
            session.client.track_emitted_rows = True
        if tel is not None:
            session.telemetry = tel    # before start(): spans ride the FIFOs
        session.start()
        prefetcher = None
        inner: Any = session
        if depth > 0:
            from repro.dpp.prefetch import DevicePrefetcher

            prefetcher = DevicePrefetcher(session, depth=depth,
                                          sharding=sharding, prep_fn=prep_fn)
            if tel is not None:
                prefetcher.telemetry = tel
            inner = prefetcher
        resume_meta = None
        if spec.ordered and session.coordinator is not None:
            resume_meta = {"fingerprint": resume_fingerprint(spec),
                           "base_rows": base_rows,
                           "base_batches": base_batches}
        return Feed(inner, session=session, prefetcher=prefetcher,
                    prep_fn=prep_fn, spec=spec, resume_meta=resume_meta,
                    telemetry=tel, store=sim.immutable)

    dev_mat = bool(spec.device_materialize)
    client = RebatchingClient(spec.batch_size,
                              buffer_batches=spec.buffer_batches,
                              shuffle_seed=spec.reshuffle_seed,
                              emit_seq_start=base_batches,
                              emit_jagged=dev_mat)
    # BEFORE the pool starts: the Feed's resume cursor reads every emitted
    # batch's row count from this FIFO (prep_fn may reshape batches)
    client.track_emitted_rows = spec.ordered
    client.telemetry = tel
    pool = DPPWorkerPool.from_plan(plan, client, n_workers=spec.n_workers,
                                   controller=controller,
                                   ordered=spec.ordered,
                                   max_item_retries=spec.max_item_retries,
                                   retry_backoff=_retry_backoff(spec))
    if tel is not None:
        pool.telemetry = tel           # before start(): items mint spans
    pool.start(_skip_rows(_batch_items(spec, sim), base_rows))
    prefetcher = None
    inner = client
    if depth > 0:
        from repro.dpp.prefetch import DevicePrefetcher

        materialize = None
        if dev_mat:
            from repro.dpp.device_mat import DeviceMaterializer

            materialize = DeviceMaterializer(sharding=sharding)
        prefetcher = DevicePrefetcher(client, depth=depth, sharding=sharding,
                                      prep_fn=prep_fn,
                                      materialize=materialize)
        if tel is not None:
            prefetcher.telemetry = tel
        inner = prefetcher
    resume_meta = None
    if spec.ordered:
        resume_meta = {"fingerprint": resume_fingerprint(spec),
                       "base_rows": base_rows,
                       "base_batches": base_batches}
        if isinstance(spec.source, WarehouseSource):
            resume_meta["hour_rows"] = _warehouse_hour_rows(spec, sim)
    return Feed(inner, client=client, pool=pool, prefetcher=prefetcher,
                prep_fn=prep_fn, spec=spec, resume_meta=resume_meta,
                telemetry=tel, store=sim.immutable)
