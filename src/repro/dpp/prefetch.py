"""Double-buffered device feed (paper §4.2): overlap host->device transfer for
batch N+1 with the train step for batch N.

The seed trainer called ``jax.device_put`` (implicitly, via jit argument
transfer) synchronously inside the step loop, so every step paid the full
featurize-tail + H2D latency on the critical path. ``DevicePrefetcher`` sits
between a host-batch source (typically a ``RebatchingClient``) and the
``Trainer``: a background thread pulls the next host batch, applies an
optional ``prep_fn`` (model-specific host transforms), issues the device
transfer, and blocks until the buffers are resident — all while the previous
step computes. ``depth`` bounds how many device batches may be in flight
(2 = classic double buffering).

Starvation attribution: the prefetch thread runs a state clock (host-fetch vs
H2D-copy); when the consumer blocks, the wait is split into
``ClientStats.starved_host_s`` vs ``starved_h2d_s`` proportionally to what the
prefetcher was actually doing during the wait window — the counter split the
elastic controller needs to distinguish "provision more DPP workers" from
"the interconnect is the bottleneck".

Slot recycling: when the source exposes ``recycle`` and ``recycle_host=True``,
the host storage of a transferred batch is returned to the source's slot pool
right after the device copy completes. Only enable this when the transfer is
a true copy (discrete accelerators); on CPU backends ``device_put`` may alias
the host buffer, in which case recycling would corrupt in-flight batches —
hence the conservative default.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from repro.dpp.client import ClientStats
from repro.obs.spans import stage

HostBatch = Dict[str, np.ndarray]


class _StateClock:
    """Cumulative time-in-state tracker readable mid-state from other threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._acc: Dict[str, float] = {}
        self._state: Optional[str] = None
        self._since = 0.0

    def enter(self, state: Optional[str]) -> None:
        now = time.perf_counter()
        with self._lock:
            if self._state is not None:
                self._acc[self._state] = (
                    self._acc.get(self._state, 0.0) + now - self._since)
            self._state = state
            self._since = now

    def snapshot(self) -> Dict[str, float]:
        now = time.perf_counter()
        with self._lock:
            out = dict(self._acc)
            if self._state is not None:
                out[self._state] = out.get(self._state, 0.0) + now - self._since
            return out


class _SourceError:
    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher:
    """Pull host batches from ``source``, transfer to device in a background
    thread, yield ready device batches.

    ``source`` is either a ``RebatchingClient``-like object (``get_full_batch``
    returning ``None`` at end of stream) or any iterable of host batches.
    """

    def __init__(
        self,
        source: Any,
        depth: int = 2,
        device: Any = None,
        sharding: Any = None,
        prep_fn: Optional[Callable[[HostBatch], Any]] = None,
        stats: Optional[ClientStats] = None,
        recycle_host: bool = False,
        materialize: Any = None,
    ):
        assert depth >= 1
        self.source = source
        self.device = device
        self.sharding = sharding
        self.prep_fn = prep_fn
        self.recycle_host = recycle_host
        # device-side late materialization (DESIGN §3): a DeviceMaterializer
        # that turns compact jagged payloads (arena + offsets) into dense
        # device batches by running the kernels/fused pipeline on-device —
        # dense batches (or a None materializer) take the plain path below
        self.materialize = materialize
        self.stats = stats if stats is not None else (
            getattr(source, "stats", None) or ClientStats())
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._clock = _StateClock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._started = False
        self._telemetry = None
        self._h2d_hist = None
        # end-of-stream sentinel observed by the consumer (vs a get timeout)
        self.ended = False

    # -- telemetry ----------------------------------------------------------------
    @property
    def telemetry(self):
        return self._telemetry

    @telemetry.setter
    def telemetry(self, tel) -> None:
        """Attach a ``repro.obs.Telemetry``. Must happen BEFORE ``start()``:
        the span tracker's delivery FIFO switches to the H2D-done lane
        (``has_h2d``) and emitted/consumed counts must match."""
        self._telemetry = tel
        if tel is not None:
            tel.spans.has_h2d = True
            self._h2d_hist = tel.registry.histogram(
                "repro_h2d_seconds",
                help="host->device transfer time per full batch")

    # -- producer (background transfer thread) -----------------------------------
    def _pull(self):
        get = getattr(self.source, "get_full_batch", None)
        if get is not None:
            # record=False: the PREFETCH thread's wait on host data is not GPU
            # starvation — only the consumer-side wait below is
            try:
                return get(record=False)
            except TypeError:
                return get()
        it = getattr(self, "_source_iter", None)
        if it is None:
            it = self._source_iter = iter(self.source)
        return next(it, None)

    def _transfer(self, host_batch: HostBatch):
        import jax

        if self.materialize is not None and isinstance(host_batch, dict) \
                and "_seq_len" in host_batch:
            # compact jagged payload: upload arena+offsets only, densify and
            # delta-decode ON DEVICE (kernels/fused); the [B, L] zero padding
            # never crosses the link
            dev = self.materialize(host_batch)
            self.stats.h2d_bytes += self.materialize.last_h2d_bytes
            jax.block_until_ready(dev)
            return dev
        prepped = host_batch
        if self.prep_fn:
            with stage("prefetch", "prep", span=None):
                prepped = self.prep_fn(host_batch)
        target = self.sharding if self.sharding is not None else self.device
        if target is not None:
            dev = jax.device_put(prepped, target)
        else:
            dev = jax.device_put(prepped)
        if isinstance(prepped, dict):
            self.stats.h2d_bytes += sum(
                getattr(v, "nbytes", 0) for v in prepped.values())
        # block in THIS thread so the consumer receives resident buffers and
        # the H2D cost lands in the prefetcher's clock, not the train step
        jax.block_until_ready(dev)
        return dev

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                self._clock.enter("host")
                host_batch = self._pull()
                if host_batch is None:
                    break
                tel = self._telemetry
                bs = tel.spans.pop_emitted() if tel is not None else None
                self._clock.enter("h2d")
                with stage("prefetch", "h2d", self.stats, "h2d_time_s",
                           span=bs) as st:
                    dev = self._transfer(host_batch)
                if tel is not None:
                    tel.spans.push_h2d_done(bs)
                    self._h2d_hist.observe(st.seconds)
                if self.recycle_host:
                    rec = getattr(self.source, "recycle", None)
                    if rec is not None:
                        rec(host_batch)
                self._clock.enter("idle")
                if not self._offer(dev):
                    return     # stopped while the queue was full
        except BaseException as e:  # propagate to the consumer
            self._clock.enter("idle")
            self._offer(_SourceError(e))
            return
        self._clock.enter(None)
        self._offer(None)

    def _offer(self, item) -> bool:
        """put that re-checks stop: a consumer that walked away (e.g. fit hit
        max_steps) must not leave this thread parked on a full queue pinning
        device buffers forever."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer (trainer loop) --------------------------------------------------
    def start(self) -> "DevicePrefetcher":
        if not self._started:
            self._started = True
            self._thread.start()
        return self

    def get(self, timeout: Optional[float] = None, record: bool = True):
        """Next device-resident batch, or ``None`` at end of stream.

        ``record=False`` suppresses the starvation/full-batch accounting —
        for pulls that are NOT the trainer's critical path (e.g. a stacked
        stage draining this one)."""
        self.start()
        before = self._clock.snapshot()
        t0 = time.perf_counter()
        try:
            out = self._q.get(timeout=timeout)
            if out is None:
                self.ended = True
        except queue.Empty:
            return None
        dt = time.perf_counter() - t0
        if isinstance(out, _SourceError):
            self.stop()
            raise RuntimeError("device prefetch source failed") from out.exc
        if out is not None and record:
            # split the consumer's wait by what the prefetcher was doing
            after = self._clock.snapshot()
            d_host = after.get("host", 0.0) - before.get("host", 0.0)
            d_h2d = after.get("h2d", 0.0) - before.get("h2d", 0.0)
            busy = d_host + d_h2d
            host_share = dt * (d_host / busy) if busy > 0 else dt
            self.stats.starved_time_s += dt
            self.stats.starved_host_s += host_share
            self.stats.starved_h2d_s += dt - host_share
            self.stats.full_batches += 1
        return out

    def record_train_step(self, seconds: float) -> None:
        rec = getattr(self.source, "record_train_step", None)
        if rec is not None and getattr(self.source, "stats", None) is self.stats:
            # the source owns the shared ClientStats: DELEGATE instead of
            # recording here — train time is a single global clock, and the
            # source may have step-completion side effects of its own (e.g.
            # StreamingSession settles event->gradient freshness samples)
            rec(seconds)
            return
        self.stats.train_time_s += seconds
        if rec is not None:
            rec(seconds)

    def _drain(self) -> None:
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def stop(self, timeout: float = 5.0) -> None:
        """Abandon the stream: stop the transfer thread and release queued
        device batches (safe to call from the consumer at any point).

        Drains AFTER the thread exits — a drain racing a producer parked in
        ``_q.put`` would free a queue slot, let that put land, and strand one
        device-resident batch forever. If the thread is stuck in a host
        source that never yields, it parks as a daemon on an empty queue."""
        self._stop.set()
        if self._started:
            deadline = time.monotonic() + timeout
            while self._thread.is_alive() and time.monotonic() < deadline:
                self._drain()
                self._thread.join(timeout=0.05)
        self._drain()

    def __iter__(self) -> Iterator[Any]:
        while True:
            b = self.get()
            if b is None:
                return
            yield b
