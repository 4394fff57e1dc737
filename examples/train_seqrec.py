"""End-to-end training driver: the complete stack, one process.

  synthetic traffic -> mutable/immutable tiers -> VLM snapshots -> warehouse
  -> declarative read path: DatasetSpec -> open_feed (elastic DPP pool,
     vectorized featurize, slot-based rebatching, device prefetch)
  -> DLRM-UIH trainer (AdamW, grad accumulation, crash-safe checkpointing).

Run:  PYTHONPATH=src python examples/train_seqrec.py [--steps 200] [--resume]
The model is the paper's flagship tenant (DLRM + UIH transformer encoder) at a
CPU-sized config; the same driver drives pod-scale meshes via --arch configs.
The feed is ONE DatasetSpec — adding a tenant means writing another spec, not
another pipeline.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as ev
from repro.core.projection import TenantProjection
from repro.core.simulation import ProductionSim, SimConfig
from repro.data import DatasetSpec, SimSource, open_feed
from repro.dpp.elastic import ElasticConfig, ElasticController
from repro.dpp.featurize import FeatureSpec
from repro.launch.compile_cache import use_compile_cache
from repro.models import recsys as R
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import Trainer, TrainerConfig

SEQ_LEN = 48
BATCH = 32
BASE_BATCH = 8


def build_sim(seed: int = 0) -> ProductionSim:
    sim = ProductionSim(SimConfig(
        stream=ev.StreamConfig(n_users=32, n_items=4_000, days=7,
                               events_per_user_day_mean=40.0, seed=seed),
        stripe_len=32, requests_per_user_day=6, seed=seed,
    ))
    sim.run_days(6, capture_reference=False)
    return sim


def dataset_spec(steps: int, prefetch: bool) -> DatasetSpec:
    """The whole feed, declaratively: tenant projection + source + knobs."""
    tenant = TenantProjection(
        "dlrm-uih", seq_len=SEQ_LEN,
        feature_groups=("core", "sideinfo"),
        traits_per_group={"core": ("timestamp", "item_id", "action_type"),
                          "sideinfo": ("category",)})
    features = FeatureSpec(seq_len=SEQ_LEN,
                           uih_traits=("item_id", "action_type", "category"),
                           candidate_fields=("item_id",),
                           label_fields=("click",))
    return DatasetSpec(
        tenant=tenant,
        source=SimSource(min_rows=steps * BATCH + BATCH),  # cover the run
        batch_size=BATCH, base_batch_size=BASE_BATCH,
        prefetch_depth=2 if prefetch else 0,
        n_workers=2, window_cache_size=256, features=features,
    )


def prep(b, cfg):
    return {
        "uih_item_id": (b["uih_item_id"] % cfg.item_vocab).astype(np.int32),
        "uih_action_type": (b["uih_action_type"] % 16).astype(np.int32),
        "uih_mask": b["uih_mask"],
        "cand_item_id": (b["cand_item_id"] % cfg.item_vocab).astype(np.int32),
        "sparse_ids": np.stack([b["user_id"] % cfg.field_vocab,
                                b["cand_item_id"] % cfg.field_vocab],
                               1).astype(np.int32),
        "dense": np.stack([b["uih_mask"].sum(1)] * 4, 1).astype(np.float32)
        / SEQ_LEN,
        "label": b["label_click"].astype(np.float32),
    }


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_seqrec_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="host-only feed (seed-style sync device transfer)")
    args = ap.parse_args()

    cfg = R.DLRMUIHConfig(
        name="seqrec", seq_len=SEQ_LEN, d_seq=32, n_seq_layers=2, n_heads=4,
        n_dense=4, n_sparse=2, embed_dim=16, item_vocab=4_096,
        field_vocab=4_096, compute_dtype=jnp.float32, remat=False)
    params = R.init_dlrm_uih(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"DLRM-UIH: {n_params/1e6:.2f}M params, seq_len={SEQ_LEN}")

    sim = build_sim()
    trainer = Trainer(
        lambda p, b: R.dlrm_uih_loss(p, b, cfg), params,
        TrainerConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=20,
                                      total_steps=args.steps),
                      ckpt_dir=args.ckpt_dir, ckpt_every=50, grad_accum=2,
                      log_every=20))
    if args.resume and trainer.try_resume():
        print(f"resumed from step {trainer.step}")

    # ONE declarative call replaces the old hand-wired client/pool/prefetcher
    feed = open_feed(
        dataset_spec(args.steps, prefetch=not args.no_prefetch), sim,
        prep_fn=lambda b: prep(b, cfg),
        controller=ElasticController(ElasticConfig(min_workers=1,
                                                   max_workers=8)))
    t0 = time.perf_counter()
    trainer.fit(feed, max_steps=args.steps)
    dt = time.perf_counter() - t0
    feed.close(timeout=10.0)   # drain leftover items so workers exit cleanly
    first = np.mean([h["loss"] for h in trainer.history[:10]])
    last = np.mean([h["loss"] for h in trainer.history[-10:]])
    st = feed.stats()
    cs, ws = st.client, st.workers
    print(f"\ntrained {trainer.step} steps in {dt:.1f}s "
          f"({trainer.step / dt:.1f} steps/s)")
    print(f"loss {first:.4f} -> {last:.4f}")
    print(f"feed: starvation {cs.starvation_pct:.1f}% "
          f"(host {cs.starved_host_s*1e3:.0f}ms, h2d {cs.starved_h2d_s*1e3:.0f}ms), "
          f"h2d total {cs.h2d_time_s*1e3:.0f}ms, slot reuses {cs.slot_reuses}, "
          f"peak workers {st.peak_workers}, worker waste {ws.waste_pct:.1f}%")
    print(f"featurize {ws.featurize_time_s*1e3:.0f}ms over "
          f"{ws.examples} examples ({ws.base_batches} base batches)")


if __name__ == "__main__":
    main()
