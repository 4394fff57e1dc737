"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: train phase + device-materialization phase
    python chip_smoke.py --chips 4   # four chips: the row-sharded DLRM-UIH path only

Train phase: DLRM-UIH at the published widths of ``configs/dlrm_uih.py`` FULL
trains a few steps from a ``ProductionSim`` through ``open_feed`` (DPP
workers, rebatching client, device prefetch) and ``Trainer.fit``, wired as
``examples/train_seqrec.py`` wires them. Step 1's loss is checked against a
float32 forward of the same batch on the chip.

Device-materialization phase: ``open_feed(device_materialize=True)`` ships
compact jagged payloads with an int64 timestamp trait past 2^31 and
densifies them on the chip (compiled Pallas densify + timestamp decode in
one jit); every batch must equal the
host-densified batch after ``jax.device_put``, key for key and byte for byte.

Sharded phase (``--chips 4``): the row-sharded item table at the full
vocabulary on a 1x4 (data x model) mesh, through ``build_cell`` and
``open_feed(cell=..., mesh=...)``; before it, the sharded forward is
compared with the mesh-free forward on the same params.

Every cut from the published configuration is printed. Times are smoke
figures, not benchmarks. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEQ_LEN = 2048                 # configs/dlrm_uih.py FULL.seq_len
# the chip's share of the 10,000,384-row item table row-sharded 4 ways
ONE_CHIP_ITEM_VOCAB = 2_500_096
# the train step compiled for a v5e: B=64 needs 127 MiB more than the
# 15.75 GiB of HBM (the attention backward keeps the f32 scores of all four
# query chunks); B=32 peaks at 13.6 GB
TRAIN_BATCH = 32
SHARDED_BATCH = 128            # 32 rows per chip in the encoder section
MAT_BATCH = 64
STEPS = 4
SEED = 0                       # params and sim data
# sim: one request day late enough that every kept timestamp (ms since
# day 0) is above 2^31, and a lookback short enough that each window's span
# stays inside int32 (the stripe codec's bounded-window contract)
SIM_DAY = 45
LOOKBACK_DAYS = 20
SIM_USERS = 64
EVENTS_PER_USER_DAY = 105.0    # ~2,100 events per window: many users fill L
REQUESTS_PER_USER_DAY = 6
# bf16 keeps an 8-bit significand (2^-8 relative per rounding); the loss is
# a mean of per-row BCE over a few dozen rounded layers, and a full-width CPU
# forward at B=8 over four seeds differed from float32 by at most 1.5e-3
# relative. 1e-2 leaves ~7x margin while catching a wrong kernel or layout.
LOSS_RTOL = 1e-2
# sharded vs mesh-free forward, both float32 at "highest" matmul precision:
# only the reduction order differs (psum over the row shards)
SHARDED_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), summed from its monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.by_fun = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                f = kw.get("fun_name", "?")
                self.by_fun[f] = self.by_fun.get(f, 0) + 1

    def compiles(self, fun: str) -> int:
        """Backend compiles so far of jitted functions named ``fun``."""
        return self.by_fun.get(f"jit({fun})", 0)


def peak_gb(device) -> float:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


# ---------------------------------------------------------------------------
# data: one seeded production sim, specs, and the model's host prep
# ---------------------------------------------------------------------------

def build_sim(seed: int, n_users: int = SIM_USERS,
              events_per_day: float = EVENTS_PER_USER_DAY,
              day: int = SIM_DAY, lookback_days: int = LOOKBACK_DAYS):
    from repro.core import events as ev
    from repro.core.simulation import ProductionSim, SimConfig

    sim = ProductionSim(SimConfig(
        stream=ev.StreamConfig(n_users=n_users, days=day + 1,
                               events_per_user_day_mean=events_per_day,
                               seed=seed),
        requests_per_user_day=REQUESTS_PER_USER_DAY,
        lookback_ms=lookback_days * ev.MS_PER_DAY, seed=seed))
    # compaction of everything before `day` (from the event source of
    # truth), that day's ingestion, and its ranking requests
    sim.run_day(day, capture_reference=False)
    return sim


def dataset_spec(seq_len: int, batch: int, groups, rows=None,
                 prefetch_depth: int = 2, device_materialize: bool = False):
    """``groups``: feature group -> the UIH traits lifted from it."""
    from repro.core.projection import TenantProjection
    from repro.data import DatasetSpec, SimSource
    from repro.dpp.featurize import FeatureSpec

    tenant = TenantProjection("dlrm-uih", seq_len=seq_len,
                              feature_groups=tuple(groups),
                              traits_per_group=dict(groups))
    traits = tuple(t for ts in groups.values() for t in ts)
    return DatasetSpec(
        tenant=tenant,
        source=SimSource(min_rows=rows) if rows else SimSource(),
        batch_size=batch, base_batch_size=8,
        prefetch_depth=prefetch_depth, n_workers=2,
        device_materialize=device_materialize,
        features=FeatureSpec(seq_len=seq_len, uih_traits=traits,
                             candidate_fields=("item_id",),
                             label_fields=("click",)))


TRAIN_GROUPS = {"core": ("item_id", "action_type"), "sideinfo": ("category",)}


def prep(b, cfg, seq_len: int):
    """Host batch -> DLRM-UIH inputs: 13 dense and 4 sparse features."""
    from repro.core import events as ev

    mask = b["uih_mask"]
    act = b["uih_action_type"]
    ts = b["request_ts"]
    dense = [((act == a) & mask).sum(1) / seq_len for a in range(8)]
    dense += [mask.sum(1) / seq_len,
              (ts % ev.MS_PER_DAY) / ev.MS_PER_DAY,
              (ts // ev.MS_PER_DAY % 7) / 7.0,
              np.log1p(b["user_id"]) / 10.0,
              np.log1p(b["cand_item_id"]) / 20.0]
    sparse = np.stack([b["user_id"], b["cand_item_id"],
                       b["uih_item_id"][:, -1], b["uih_category"][:, -1]], 1)
    return {
        "uih_item_id": (b["uih_item_id"] % cfg.item_vocab).astype(np.int32),
        "uih_action_type": (act % 16).astype(np.int32),
        "uih_mask": mask,
        "cand_item_id": (b["cand_item_id"] % cfg.item_vocab).astype(np.int32),
        "sparse_ids": (sparse % cfg.field_vocab).astype(np.int32),
        "dense": np.stack(dense, 1).astype(np.float32),
        "label": b["label_click"].astype(np.float32),
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(sim, cfg, batch: int, steps: int, seed: int,
                clock: CompileClock) -> None:
    """Trainer.fit over open_feed; finite losses, params moved, and step 1's
    loss against a float32 forward of the same batch."""
    import jax
    import jax.numpy as jnp

    from repro.data import open_feed
    from repro.dpp.elastic import ElasticConfig, ElasticController
    from repro.models import recsys as R
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_loop import Trainer, TrainerConfig

    first, lens = [], []

    def prep_fn(b):
        out = prep(b, cfg, cfg.seq_len)
        if not first:
            first.append(out)          # the batch step 1 trains on
        lens.append(out["uih_mask"].sum(1))
        return out

    trainer = Trainer(
        lambda p, b: R.dlrm_uih_loss(p, b, cfg),
        R.init_dlrm_uih(jax.random.PRNGKey(seed), cfg),
        TrainerConfig(opt=AdamWConfig(lr=1e-3, warmup_steps=1,
                                      total_steps=steps), log_every=1))
    before = np.asarray(trainer.params["top_mlp"]["w0"])
    spec = dataset_spec(cfg.seq_len, batch, TRAIN_GROUPS,
                        rows=steps * batch + batch)
    feed = open_feed(spec, sim, prep_fn=prep_fn,
                     controller=ElasticController(
                         ElasticConfig(min_workers=1, max_workers=4)))
    c0 = clock.seconds
    t0 = time.perf_counter()
    try:
        trainer.fit(feed, max_steps=steps)
    finally:
        feed.close(timeout=30.0)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    losses = [h["loss"] for h in trainer.history]
    check(trainer.step == steps, f"took {trainer.step} of {steps} steps")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    after = np.asarray(trainer.params["top_mlp"]["w0"])
    check(not np.array_equal(before, after), "params did not change")
    train_s = feed.stats().client.train_time_s
    lens = np.concatenate(lens)
    say(f"train: UIH events per example (clipped to {cfg.seq_len}): min "
        f"{lens.min()} median {int(np.median(lens))}; "
        f"{np.mean(lens == cfg.seq_len):.0%} reach seq_len; fill "
        f"{lens.mean() / cfg.seq_len:.1%}")
    say(f"train: losses {[round(x, 5) for x in losses]}")
    say(f"train: compile {compile_s:.1f}s; fit {wall:.1f}s; mean step after "
        f"compile {(train_s - compile_s) / steps * 1e3:.1f} ms "
        f"(smoke figure, not a benchmark)")
    del trainer, feed          # free params + optimizer state before the check

    # float32 reference on the chip: same initial params (re-made from the
    # seed) and step 1's batch, f32 compute and f32 matmul passes
    cfg32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    params = R.init_dlrm_uih(jax.random.PRNGKey(seed), cfg32)
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: R.dlrm_uih_loss(p, b, cfg32))(
            params, first[0]))
    rel = abs(losses[0] - ref) / abs(ref)
    say(f"train: step-1 loss {losses[0]:.6f} vs float32 forward {ref:.6f}: "
        f"relative gap {rel:.2e} (limit {LOSS_RTOL:.0e})")
    check(rel <= LOSS_RTOL, f"step-1 loss off the float32 forward by {rel}")


def device_mat_phase(sim, seq_len: int, batch: int,
                     clock: CompileClock) -> None:
    """open_feed(device_materialize=True) against jax.device_put of the
    host-densified batches of the same spec."""
    import jax

    from repro.data import open_feed

    groups = {"core": ("timestamp", "item_id", "action_type")}
    spec = dataset_spec(seq_len, batch, groups, device_materialize=True)
    host_spec = dataclasses.replace(spec, prefetch_depth=0,
                                    device_materialize=False)
    host = open_feed(host_spec, sim)
    try:
        want = list(host)
    finally:
        host.close(timeout=30.0)
    check(len(want) > 0, "host feed produced no batches")
    ts = np.concatenate([w["uih_timestamp"][w["uih_mask"]] for w in want])
    check(ts.min() > 2**31, f"timestamps not all above 2^31 (min {ts.min()})")

    c0 = clock.seconds
    n0 = clock.compiles("densify_decode")
    dev = open_feed(spec, sim)
    try:
        got = list(dev)
        h2d = dev.stats().client.h2d_bytes
    finally:
        dev.close(timeout=30.0)
    check(len(got) == len(want), f"{len(got)} device vs {len(want)} host "
                                 f"batches")
    dense_bytes = 0
    for i, (g, w) in enumerate(zip(got, want)):
        w = jax.device_put(w)
        check(set(g) == set(w), f"batch {i}: keys {sorted(g)} vs {sorted(w)}")
        for k in w:
            dense_bytes += w[k].nbytes
            check(g[k].dtype == w[k].dtype,
                  f"batch {i} {k}: dtype {g[k].dtype} vs {w[k].dtype}")
            check(np.array_equal(np.asarray(g[k]), np.asarray(w[k])),
                  f"batch {i} {k}: bytes differ")
    check(h2d < dense_bytes, f"h2d {h2d} B not below dense {dense_bytes} B")
    say(f"device_mat: {len(got)} batches of {batch} x {seq_len} identical to "
        f"the host path; h2d {h2d} B vs dense {dense_bytes} B "
        f"({h2d / dense_bytes:.1%}); densify_decode compiled "
        f"{clock.compiles('densify_decode') - n0} times "
        f"(one per arena length); compile {clock.seconds - c0:.1f}s")


def kernel_lowering_text(seq_len: int, batch: int) -> str:
    """StableHLO of the densify + decode jit as the feed calls it on this
    backend."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import runtime
    from repro.kernels.fused.fused import densify_decode

    s = jax.ShapeDtypeStruct
    return densify_decode.lower(
        s((1, (batch + 1) * seq_len, 128), jnp.int32),
        s((batch + 1,), jnp.int32), s((batch,), jnp.int32),
        max_len=seq_len, ts_col=0,
        interpret=runtime.interpret_default()).as_text()


def sharded_phase(sim, full_cfg, cmp_vocab: int, batch: int, steps: int,
                  seed: int, clock: CompileClock) -> None:
    """Row-sharded DLRM-UIH on a 1 x n (data x model) mesh: sharded vs
    mesh-free forward at a vocabulary one chip holds, then train steps at
    ``full_cfg``'s vocabulary through build_cell + open_feed(cell=...)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.data import open_feed
    from repro.launch import shardings as SH
    from repro.launch.steps import build_cell, init_train_state
    from repro.models import recsys as R

    devices = jax.devices()
    mesh = jax.make_mesh((1, len(devices)), ("data", "model"))

    # -- sharded forward == mesh-free forward on the same params ----------
    cfg32 = dataclasses.replace(full_cfg, item_vocab=cmp_vocab,
                                compute_dtype=jnp.float32)
    cfg32_mesh = dataclasses.replace(cfg32, mesh=mesh, data_axes=("data",))
    spec = dataset_spec(full_cfg.seq_len, batch, TRAIN_GROUPS,
                        prefetch_depth=0, rows=batch)
    host = open_feed(spec, sim)
    try:
        hb = prep(host.get(), cfg32, full_cfg.seq_len)
    finally:
        host.close(timeout=30.0)
    params = R.init_dlrm_uih(jax.random.PRNGKey(seed), cfg32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda p, b: R.dlrm_uih_forward(p, b, cfg32))(params, hb))
        with jax.set_mesh(mesh):
            pspec = SH.recsys_param_specs(params, mesh)
            p_sh = jax.device_put(params, SH.named(mesh, pspec))
            del params
            got = np.asarray(jax.jit(
                lambda p, b: R.dlrm_uih_forward(p, b, cfg32_mesh))(p_sh, hb))
    del p_sh
    err = float(np.max(np.abs(got - want) / (np.abs(want) + 1e-3)))
    say(f"sharded: forward at item_vocab {cmp_vocab} on {len(devices)} chips "
        f"vs one chip: max relative gap {err:.2e} (limit {SHARDED_RTOL:.0e})")
    check(np.all(np.isfinite(got)), "sharded forward not finite")
    check(err <= SHARDED_RTOL, f"sharded forward off by {err}")

    # -- train steps at the full vocabulary -------------------------------
    arch = get_arch("dlrm-uih")
    arch = dataclasses.replace(arch, shapes={
        "chip_train": {"kind": "train", "batch": batch}})
    cell = build_cell(arch, "chip_train", mesh, cfg_override=full_cfg)
    cfg = cell.meta["cfg"]
    spec = dataset_spec(cfg.seq_len, batch, TRAIN_GROUPS,
                        rows=steps * batch + batch)
    c0 = clock.seconds
    losses = []
    params, opt = init_train_state(cell, mesh, seed)
    with jax.set_mesh(mesh):
        step = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                       out_shardings=cell.out_shardings,
                       donate_argnums=(0, 1))
        feed = open_feed(spec, sim, cell=cell, mesh=mesh,
                         prep_fn=lambda b: prep(b, cfg, cfg.seq_len))
        t0 = time.perf_counter()
        try:
            for b in feed:
                ts = time.perf_counter()
                params, opt, metrics = step(params, opt, b)
                losses.append(float(metrics["loss"]))
                feed.record_train_step(time.perf_counter() - ts)
                if len(losses) == steps:
                    break
        finally:
            feed.close(timeout=30.0)
        wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    check(len(losses) == steps, f"took {len(losses)} of {steps} steps")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    say(f"sharded: item_vocab {cfg.item_vocab} row-sharded over "
        f"{len(devices)} chips, batch {batch}: losses "
        f"{[round(x, 5) for x in losses]}; compile {compile_s:.1f}s; "
        f"loop {wall:.1f}s (smoke figure, not a benchmark)")

    table = params["item_table"]
    shards = table.addressable_shards
    check({s.device for s in shards} == set(devices),
          "item table is not spread over every device")
    check(all(s.data.shape[0] == cfg.item_vocab // len(devices)
              for s in shards), "item table shards are not row slices")
    # bytes each device must hold: its slice of params + both AdamW moments
    per_dev = sum(3 * x.addressable_shards[0].data.nbytes
                  for x in jax.tree.leaves(params))
    for d in devices:
        ms = d.memory_stats() or {}
        say(f"sharded: {d}: bytes_in_use {ms.get('bytes_in_use', 0) / 1e9:.2f}"
            f" GB, peak {ms.get('peak_bytes_in_use', 0) / 1e9:.2f} GB "
            f"(state slice {per_dev / 1e9:.2f} GB)")
        check(ms.get("bytes_in_use", 0) >= per_dev,
              f"{d} holds less than its state slice")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.configs.dlrm_uih import FULL
    from repro.kernels import runtime
    from repro.launch.compile_cache import use_compile_cache

    say(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
        f"compile cache {use_compile_cache()}")
    clock = CompileClock()
    t_start = time.perf_counter()
    say(f"config: dlrm-uih FULL widths: seq_len {FULL.seq_len}, d_seq "
        f"{FULL.d_seq}, {FULL.n_seq_layers} layers, {FULL.n_heads} heads, "
        f"embed_dim {FULL.embed_dim}, n_sparse {FULL.n_sparse}, n_dense "
        f"{FULL.n_dense}, compute {FULL.compute_dtype.__name__}")
    say(f"reduction: sim of {SIM_USERS} users x {REQUESTS_PER_USER_DAY} "
        f"requests on one day (day {SIM_DAY}), ~{EVENTS_PER_USER_DAY:g} "
        f"events/user/day, {LOOKBACK_DAYS}-day lookback, "
        f"{STEPS} train steps")
    t0 = time.perf_counter()
    sim = build_sim(SEED)
    say(f"sim: {len(sim.examples)} examples in "
        f"{time.perf_counter() - t0:.1f}s")
    try:
        if args.chips == 4:
            say(f"reduction: batch 65,536 (RECSYS_SHAPES train_batch) -> "
                f"{SHARDED_BATCH}; sharded-vs-mesh-free check at item_vocab "
                f"{ONE_CHIP_ITEM_VOCAB}")
            sharded_phase(sim, FULL, ONE_CHIP_ITEM_VOCAB, SHARDED_BATCH,
                          STEPS, SEED, clock)
        else:
            say(f"reduction: item_vocab {FULL.item_vocab} -> "
                f"{ONE_CHIP_ITEM_VOCAB} (one chip's share of a 4-way "
                f"row-sharded table; the whole table trains with --chips 4)")
            say(f"reduction: batch 65,536 (RECSYS_SHAPES train_batch) -> "
                f"{TRAIN_BATCH} (B=64 does not fit 16 GB HBM)")
            cfg = dataclasses.replace(FULL, item_vocab=ONE_CHIP_ITEM_VOCAB)
            train_phase(sim, cfg, TRAIN_BATCH, STEPS, SEED, clock)
            say(f"train: peak memory {peak_gb(dev):.2f} GB")
            check(not runtime.interpret_default(),
                  "Pallas kernels would run in interpret mode")
            check("tpu_custom_call" in kernel_lowering_text(SEQ_LEN,
                                                            MAT_BATCH),
                  "densify kernel did not lower to a Mosaic custom call")
            device_mat_phase(sim, SEQ_LEN, MAT_BATCH, clock)
            say(f"device_mat: peak memory {peak_gb(dev):.2f} GB")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"total: compile {clock.seconds:.1f}s, wall "
        f"{time.perf_counter() - t_start:.1f}s, peak memory "
        f"{max(peak_gb(d) for d in devices):.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
