"""Bifurcated protocol demo (paper §3.2): ONE pipeline serves both training
paradigms.

The same logged traffic is consumed (a) as a live stream by a streaming
trainer, and (b) replayed days later from hourly warehouse partitions by a
batch trainer — the versioned reconstruction yields bit-identical UIH features
and therefore identical losses, with zero Fat Row duplication.

Run:  PYTHONPATH=src python examples/streaming_vs_batch.py
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core.projection import TenantProjection
from repro.core.simulation import ProductionSim, SimConfig
from repro.dpp.featurize import FeatureSpec
from repro.dpp.worker import DPPWorker
from repro.launch.compile_cache import use_compile_cache
from repro.models import recsys as R

SEQ_LEN = 32
BATCH = 16


def make_worker(sim):
    tenant = TenantProjection("t", seq_len=SEQ_LEN,
                              feature_groups=("core", "sideinfo"),
                              traits_per_group={
                                  "core": ("timestamp", "item_id", "action_type"),
                                  "sideinfo": ("category",)})
    spec = FeatureSpec(seq_len=SEQ_LEN,
                       uih_traits=("item_id", "action_type", "category"))
    return DPPWorker(sim.materializer(validate_checksum=True), tenant, spec,
                     sim.schema)


def main() -> None:
    use_compile_cache()
    sim = ProductionSim(SimConfig(
        stream=ev.StreamConfig(n_users=16, n_items=2_000, days=4,
                               events_per_user_day_mean=40.0, seed=3),
        stripe_len=32, requests_per_user_day=4, seed=3))

    # --- streaming side: consume the live stream as days unfold ---
    stream_batches = []
    worker_s = make_worker(sim)

    def consume():
        buf = []
        while True:
            exm = sim.stream.consume()
            if exm is None:
                break
            buf.append(exm)
            if len(buf) == BATCH:
                stream_batches.append(worker_s.process(buf))
                buf = []

    consumer = threading.Thread(target=consume)
    consumer.start()
    sim.run_days(3, capture_reference=False)
    sim.stream.close()
    consumer.join()
    print(f"streaming trainer consumed {len(stream_batches)} batches "
          f"within seconds of logging")

    # --- batch side: replay from the warehouse later (after more compactions) ---
    worker_b = make_worker(sim)
    by_id = {}
    for hour in sim.warehouse.hours():
        for exm in sim.warehouse.read_partition(hour):
            by_id[exm.request_id] = exm

    cfg = R.BERT4RecConfig(name="demo", embed_dim=16, n_blocks=2, n_heads=2,
                           seq_len=SEQ_LEN, item_vocab=2_000,
                           compute_dtype=jnp.float32)
    params = R.init_bert4rec(jax.random.PRNGKey(0), cfg)
    fwd = jax.jit(lambda p, b: R.bert4rec_forward(p, b, cfg))

    mismatches = 0
    for sb in stream_batches[:8]:
        ids = [int(r) for r in sb["request_ts"]]
        # find the same examples in the warehouse by (user, ts)
        keys = list(zip(sb["user_id"].tolist(), sb["request_ts"].tolist()))
        replay = [next(e for e in by_id.values()
                       if (e.user_id, e.request_ts) == k) for k in keys]
        bb = worker_b.process(replay)
        same = all(np.array_equal(sb[k], bb[k]) for k in sb)
        mismatches += 0 if same else 1
        batch = {"uih_item_id": jnp.asarray(sb["uih_item_id"], jnp.int32),
                 "uih_mask": jnp.asarray(sb["uih_mask"]),
                 "cand_item_id": jnp.asarray(sb["cand_item_id"], jnp.int32)}
        batch2 = {k: jnp.asarray(bb[{"uih_item_id": "uih_item_id",
                                     "uih_mask": "uih_mask",
                                     "cand_item_id": "cand_item_id"}[k]],
                                 v.dtype) for k, v in batch.items()}
        s1, s2 = fwd(params, batch), fwd(params, batch2)
        assert jnp.allclose(s1, s2), "scores diverged between paradigms"
    print(f"batch replay vs streaming: {mismatches} feature mismatches "
          f"across {min(8, len(stream_batches))} batches (expect 0)")
    print(f"checksum validations: streaming={worker_s.materializer.stats.checksum_validated},"
          f" batch={worker_b.materializer.stats.checksum_validated}, "
          f"failures={worker_s.materializer.stats.checksum_failures + worker_b.materializer.stats.checksum_failures}")


if __name__ == "__main__":
    main()
