"""End-to-end trainer: DPP data plane -> jit'd train step -> checkpoints.

Integrates the full stack on one host (and, unchanged, on a pod via the mesh
argument): the VLM materialization pipeline feeds batches through the
rebatching client; the train step is jit'd with shardings; the checkpoint
manager gives crash-safe resume; gradient compression is optional.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.obs.spans import stage, trace_gc
from repro.train.grad_compress import EFState, compress_with_feedback, ef_init
from repro.train.optimizer import (
    AdamWConfig,
    AdamWState,
    adamw_init,
    adamw_update,
)


@dataclasses.dataclass
class TrainerConfig:
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep_ckpts: int = 3
    grad_accum: int = 1          # microbatch accumulation factor
    compress_grads: bool = False
    log_every: int = 10
    # double-buffered device feed: issue the host->device transfer for batch
    # N+1 while step N computes (0 disables; 2 = classic double buffering).
    # Ignored when ``fit`` is handed an already-wrapped DevicePrefetcher.
    prefetch_depth: int = 0
    # device-side late materialization (DESIGN §3): when fit auto-wraps the
    # feed in a DevicePrefetcher, attach a DeviceMaterializer so compact
    # jagged payloads (from a ``RebatchingClient(emit_jagged=True)``) densify
    # and delta-decode ON DEVICE. Dense host batches pass through untouched,
    # so the flag is safe to leave on. Requires prefetch_depth > 0.
    device_materialize: bool = False
    # streaming feed mode: bound ``fit`` by wall clock instead of (or in
    # addition to) max_steps — an online trainer's stream never exhausts.
    max_wall_s: Optional[float] = None
    # unified telemetry (§13): a ``repro.obs.Telemetry`` — ``fit`` observes a
    # per-step ``repro_train_step_seconds`` histogram, ``save``/``try_resume``
    # emit checkpoint_save / checkpoint_resume events. Falls back to the
    # feed's own telemetry when None.
    telemetry: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)


class Trainer:
    def __init__(
        self,
        loss_fn: Callable[[Any, Dict[str, Any]], jax.Array],
        params: Any,
        cfg: TrainerConfig,
        mesh=None,
    ):
        self.loss_fn = loss_fn
        self.cfg = cfg
        # the step donates params and optimizer state, so old and new never
        # sit in HBM together; the trainer therefore steps its own copy and
        # the caller's arrays stay valid
        self.params = jax.tree.map(jax.numpy.copy, params)
        self.opt_state = adamw_init(params)
        self.ef_state = ef_init(params) if cfg.compress_grads else None
        self.step = 0
        self.mesh = mesh
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep=cfg.keep_ckpts)
                     if cfg.ckpt_dir else None)
        self.history = []
        self._jit_step = jax.jit(self._train_step, donate_argnums=(0, 1, 2))
        # set by fit(): the active Feed whose cursor rides along with model
        # checkpoints (feed_state sidecar, exactly-once resume). While a feed
        # is active, run_step defers its periodic autosave to fit — the save
        # must happen AFTER record_train_step so the feed's trained-row
        # counter includes the step being checkpointed.
        self._fit_feed = None

    # -- one optimizer step (with optional microbatch accumulation) -----------
    def _train_step(self, params, opt_state, ef_state, microbatches):
        def accum(carry, mb):
            gacc, lacc = carry
            loss, grads = jax.value_and_grad(self.loss_fn)(params, mb)
            gacc = jax.tree.map(lambda a, g: a + g.astype(jax.numpy.float32),
                                gacc, grads)
            return (gacc, lacc + loss), None

        zero = jax.tree.map(
            lambda p: jax.numpy.zeros(p.shape, jax.numpy.float32), params)
        (gsum, lsum), _ = jax.lax.scan(
            accum, (zero, jax.numpy.zeros((), jax.numpy.float32)), microbatches)
        n = self.cfg.grad_accum
        with jax.named_scope("optimizer"):
            grads = jax.tree.map(lambda g: g / n, gsum)
            if ef_state is not None:
                grads, ef_state = compress_with_feedback(grads, ef_state)
            params, opt_state, stats = adamw_update(params, grads, opt_state,
                                                    self.cfg.opt)
        stats["loss"] = lsum / n
        return params, opt_state, ef_state, stats

    def _microbatches(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Every batch array split into ``grad_accum`` microbatches."""
        n = self.cfg.grad_accum
        mbs = {}
        for k, v in batch.items():
            b = v.shape[0]
            assert b % n == 0, f"batch {b} not divisible by accum {n}"
            mbs[k] = v.reshape(n, b // n, *v.shape[1:])
        return mbs

    def run_step(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """batch rows are split into ``grad_accum`` microbatches."""
        with stage("train", "dispatch", span=None):
            with stage("train", "inputs", span=None):
                mbs = self._microbatches(batch)
            self.params, self.opt_state, self.ef_state, stats = \
                self._jit_step(self.params, self.opt_state, self.ef_state,
                               mbs)
        self.step += 1
        with stage("train", "readback", span=None):
            out = {k: float(v) for k, v in stats.items()}
        self.history.append(out)
        if (self.ckpt and self.step % self.cfg.ckpt_every == 0
                and self._fit_feed is None):
            self.save()
        return out

    def step_hlo_text(self, batch: Dict[str, Any]) -> str:
        """The compiled train step's HLO text for batches shaped like
        ``batch``: each instruction's ``metadata={op_name=...}`` names the
        model scope (``embed``, ``encoder``, ``logits``, ``optimizer``) that
        a device op in a profiler trace belongs to."""
        def spec(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=getattr(x, "sharding", None))

        mbs = jax.eval_shape(self._microbatches, batch)
        args = jax.tree.map(spec, (self.params, self.opt_state,
                                   self.ef_state))
        return self._jit_step.lower(*args, mbs).compile().as_text()

    # -- checkpointing ----------------------------------------------------------
    def save(self) -> None:
        assert self.ckpt is not None
        state = {"params": self.params, "opt": self.opt_state}
        if self.ef_state is not None:
            state["ef"] = self.ef_state
        feed_state = None
        feed = self._fit_feed
        if feed is not None and getattr(feed, "can_checkpoint", False):
            feed_state = feed.checkpoint()
        with stage("train", "checkpoint", span=None):
            self.ckpt.save(self.step, state, extra={"step": self.step},
                           feed_state=feed_state)
        tel = self._telemetry()
        if tel is not None:
            tel.events.emit("checkpoint_save", step=self.step,
                            has_feed_state=feed_state is not None)

    def try_resume(self) -> bool:
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        template = {"params": self.params, "opt": self.opt_state}
        if self.ef_state is not None:
            template["ef"] = self.ef_state
        state, step, _ = self.ckpt.restore(template)
        self.params = state["params"]
        self.opt_state = state["opt"]
        self.ef_state = state.get("ef", self.ef_state)
        self.step = step
        tel = self._telemetry()
        if tel is not None:
            tel.events.emit("checkpoint_resume", step=step)
        return True

    def _telemetry(self):
        """The active telemetry: the config's, else the fit feed's."""
        if self.cfg.telemetry is not None:
            return self.cfg.telemetry
        return getattr(self._fit_feed, "telemetry", None)

    # -- full loop ---------------------------------------------------------------
    def fit(self, batches: Iterable[Dict[str, np.ndarray]],
            max_steps: Optional[int] = None) -> None:
        from repro.data.feed import Feed
        from repro.dpp.prefetch import DevicePrefetcher

        feed = batches
        if (self.cfg.prefetch_depth > 0
                and not isinstance(feed, (DevicePrefetcher, Feed))):
            materialize = None
            if self.cfg.device_materialize:
                from repro.dpp.device_mat import DeviceMaterializer
                materialize = DeviceMaterializer()
            feed = DevicePrefetcher(feed, depth=self.cfg.prefetch_depth,
                                    materialize=materialize)
        # GPU-busy accounting feeds the elastic controller's starvation signal
        record = getattr(feed, "record_train_step", None)
        self._fit_feed = feed if isinstance(feed, Feed) else None
        tel = self._telemetry()
        step_hist = (tel.registry.histogram(
            "repro_train_step_seconds",
            help="host time of run_step: dispatch and loss read-back")
            if tel is not None else None)
        trace_gc()
        t0 = time.perf_counter()

        def batches():
            """Feed iterator honoring ``max_wall_s`` even while BLOCKED on an
            idle-but-open stream: with a timeout-capable getter, poll with a
            bounded wait so the wall budget can fire between batches; the
            feed's ``ended`` flag distinguishes end-of-stream from a timeout."""
            wall = self.cfg.max_wall_s
            get = getattr(feed, "get", None) or getattr(feed, "get_full_batch",
                                                        None)
            if wall is None or get is None:
                yield from feed
                return
            # the live mutable ClientStats: a Feed exposes it as
            # ``client_stats`` (its ``stats`` is the composite snapshot
            # method); legacy feeds expose the object directly as ``stats``
            stats = getattr(feed, "client_stats", None)
            if stats is None:
                stats = getattr(feed, "stats", None)
                if callable(stats):
                    stats = None
            pending_wait = 0.0   # timed-out poll waits, unrecorded by the feed
            while True:
                remaining = wall - (time.perf_counter() - t0)
                if remaining <= 0:
                    return
                t_poll = time.perf_counter()
                b = get(timeout=min(0.25, max(remaining, 0.01)))
                if b is None:
                    if getattr(feed, "ended", False):
                        return
                    pending_wait += time.perf_counter() - t_poll
                    continue   # timed out; re-check the wall budget
                if pending_wait > 0.0 and stats is not None:
                    # the feed only records waits ending in a delivered batch;
                    # fold the preceding timed-out polls back into starvation
                    # (host-attributed: that is the scale-the-workers signal)
                    # or the controller would see a starving feed as healthy.
                    # Waits with NO eventual batch (stream over) stay
                    # unrecorded, matching the feed's own rule.
                    stats.starved_time_s += pending_wait
                    stats.starved_host_s += pending_wait
                if pending_wait:
                    pending_wait = 0.0
                yield b

        try:
            for batch in batches():
                with jax.profiler.StepTraceAnnotation("repro.train.step",
                                                      step_num=self.step):
                    ts = time.perf_counter()
                    stats = self.run_step(batch)
                    dt_step = time.perf_counter() - ts
                    if record is not None:
                        record(dt_step)
                    if step_hist is not None:
                        step_hist.observe(dt_step)
                    if (self.ckpt and self._fit_feed is not None
                            and self.step % self.cfg.ckpt_every == 0):
                        # deferred from run_step: the feed's trained-row
                        # counter advanced in record() above, so the
                        # feed_state sidecar now names exactly this step's
                        # training frontier
                        self.save()
                    if self.step % self.cfg.log_every == 0:
                        dt = time.perf_counter() - t0
                        print(f"step {self.step:5d} "
                              f"loss={stats['loss']:.4f} "
                              f"gnorm={stats['grad_norm']:.3f} ({dt:.1f}s)",
                              flush=True)
                if max_steps and self.step >= max_steps:
                    break
                if (self.cfg.max_wall_s is not None
                        and time.perf_counter() - t0 >= self.cfg.max_wall_s):
                    break
        finally:
            self._fit_feed = None
            # break AND exception paths: release the transfer thread and any
            # queued device batches (idempotent; harmless on exhaustion).
            # A Feed's stop() releases ONLY its device-prefetch stage — the
            # host pipeline stays up for the caller to close()/drain.
            if isinstance(feed, (DevicePrefetcher, Feed)):
                feed.stop()
