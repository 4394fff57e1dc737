"""One run of one cell: set-up, the measured window, the check.

Everything specific to a configuration, a traffic mix or a metric is found
by name: ``BENCHMARK.json`` names the cell's configuration file
(``bench/configs/<c>.json``, with its module ``<c>.py`` beside it), the
traffic file is ``bench/traffic/<mix>.json``, each metric is read by
``bench/metrics/<metric>.py`` and the limits of the check are in
``bench/limits/<workload>.json``.

A configuration's module may also declare, for its per-layer metrics:
``SCOPES``, the model scopes (``jax.named_scope`` names in the program)
that it adds to ``bench.spans.DEFAULT_SCOPES``, each a key of the traced
run's ``device_by_scope``; ``KERNELS``, ``{scope: cost(batch, config) ->
(flops, bytes)}``, the work of the kernel the program runs under that scope
for one prepared batch (every byte of its operands and results), which
``bench/roofline.py`` sets against the scope's device time; and
``counters(batch, config) -> {name: count}``. In a traced run, costs and
counts are summed over the window's batches and reach the metrics as
``w.kernel_cost`` and ``w.counters``. A traced run fails where the step it
compiles holds none of the scopes, or lacks one of ``SCOPES``.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import gen, reference, spans, trace as trace_mod

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    model: object
    traffic: dict
    chips: int
    metrics: List[dict]          # end-to-end then per-layer entries
    limits: dict


def load_cell(workload: str, overrides: Optional[dict] = None,
              bench: Optional[dict] = None) -> Cell:
    """The cell ``workload`` as ``BENCHMARK.json`` (or ``bench``, its
    contents) describes it. ``overrides`` replace configuration or traffic
    keys (tests and calibration only)."""
    b = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    w = next((x for x in b["workloads"] if x["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    centry = next(x for x in b["configs"] if x["name"] == w["config"])
    cfile = ROOT / centry["file"]
    config = json.loads(cfile.read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json")
                        .read_text())["limits"]
    for k, v in (overrides or {}).get("config", {}).items():
        config[k] = v
    for k, v in (overrides or {}).get("traffic", {}).items():
        traffic[k] = v
    metrics = [dict(m, kind=kind) for kind in ("end_to_end", "per_layer")
               for m in b[kind]
               if workload in m.get("workloads", [workload])]
    return Cell(workload, config, _module(cfile.with_suffix(".py")),
                traffic, int(w["chips"]), metrics, limits)


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling (or
    fetching from the persistent cache), from its monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.events += 1


def model_scopes(model) -> Tuple[str, ...]:
    """The scopes a traced run splits device time by: the defaults and the
    configuration's ``SCOPES``, which hold every scope of its ``KERNELS``."""
    scopes = spans.DEFAULT_SCOPES + tuple(getattr(model, "SCOPES", ()))
    stray = set(getattr(model, "KERNELS", {})) - set(scopes)
    if stray:
        raise ValueError(f"KERNELS names scopes that are not in SCOPES: "
                         f"{sorted(stray)}")
    return scopes


def step_scopes(hlo_text: str, model) -> Dict[str, Dict[str, str]]:
    """``spans.op_scopes`` of the compiled step; raises where the step holds
    none of the scopes, or lacks one that the configuration's ``SCOPES``
    declares, since its device time would then read as unscoped."""
    scopes = spans.op_scopes(hlo_text, model_scopes(model))
    found = {sc for ops in scopes.values() for sc in ops.values()}
    missing = set(getattr(model, "SCOPES", ())) - found
    if not found or missing:
        lack = sorted(missing) if found else "any of the scopes"
        raise RuntimeError(f"the compiled step lacks {lack}: "
                           "harness.compiled_step no longer matches the "
                           "program's step")
    return scopes


class PrepRecorder:
    """The feed's ``prep_fn``: runs the configuration's prep and records,
    per batch in the order the trainer receives them, the rows' example
    keys and the batch's useful FLOPs; with ``per_layer``, also each of the
    configuration's ``KERNELS`` costs and its ``counters``."""

    def __init__(self, model, config: dict, seed: int,
                 per_layer: bool = False):
        self.model, self.config, self.seed = model, config, seed
        self.keys: List[List[tuple]] = []
        self.flops: List[float] = []
        self.kernels = getattr(model, "KERNELS", {}) if per_layer else {}
        self.count = getattr(model, "counters", None) if per_layer else None
        self.kernel_cost: List[Dict[str, Tuple[float, float]]] = []
        self.counters: List[Dict[str, float]] = []

    def __call__(self, raw: dict) -> dict:
        out = self.model.prep(raw, self.config, len(self.keys), self.seed)
        self.keys.append(list(zip(raw["user_id"].tolist(),
                                  raw["request_ts"].tolist(),
                                  raw["cand_item_id"].tolist())))
        self.flops.append(float(self.model.flops_per_row(out, self.config)
                                .sum()))
        if self.kernels:
            self.kernel_cost.append({
                scope: tuple(float(x) for x in cost(out, self.config))
                for scope, cost in self.kernels.items()})
        if self.count is not None:
            self.counters.append({k: float(v) for k, v in
                                  self.count(out, self.config).items()})
        return out

    def sums(self, first: int, last: int
             ) -> Tuple[Dict[str, Tuple[float, float]], Dict[str, float]]:
        """Each kernel's ``(flops, bytes)`` and each counter, summed over
        batches ``first`` to ``last`` (exclusive)."""
        cost: Dict[str, Tuple[float, float]] = {}
        for batch in self.kernel_cost[first:last]:
            for scope, (f, b) in batch.items():
                f0, b0 = cost.get(scope, (0.0, 0.0))
                cost[scope] = (f0 + f, b0 + b)
        counts: Dict[str, float] = {}
        for batch in self.counters[first:last]:
            for k, v in batch.items():
                counts[k] = counts.get(k, 0.0) + v
        return cost, counts


class TimedFeed:
    """What the trainer iterates: the ``Feed``'s batches, with the end of
    every step stamped (``record_train_step`` is called when a step has
    finished and its loss was read back) and the batches the check reads
    held. Once a window is open, iteration stops at its deadline."""

    def __init__(self, feed, sample: int, seed: int):
        import jax

        self._ann = jax.profiler.TraceAnnotation
        self.feed = feed
        self.delivered = 0
        self.step_ends: List[float] = []
        self.kept: Dict[int, object] = {}
        self._sample = sample
        self._rng = np.random.default_rng(abs(int(seed)))
        self._first = None          # first batch index of the window
        self.deadline: Optional[float] = None
        self.t0 = 0.0
        self._step_ann = None

    def open_window(self, seconds: float) -> None:
        self._first = self.delivered
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds

    def _keep(self, k: int, batch) -> None:
        if self._first is None:
            if k < 3:
                self.kept[k] = batch        # the steps the reference follows
            return
        i = k - self._first                 # reservoir over the window
        if i < self._sample:
            self.kept[k] = batch
            return
        j = int(self._rng.integers(0, i + 1))
        if j < self._sample:
            window = sorted(x for x in self.kept if x >= self._first)
            del self.kept[window[j]]
            self.kept[k] = batch

    def __iter__(self):
        while True:
            if (self.deadline is not None
                    and time.perf_counter() >= self.deadline):
                return
            with self._ann("bench.feed_fetch"):
                batch = self.feed.get()
            if batch is None:
                raise RuntimeError(
                    "the feed ran dry inside the run: raise the traffic "
                    "file's rows_per_s_cap")
            self._keep(self.delivered, batch)
            self.delivered += 1
            self._step_ann = self._ann("bench.train_step")
            self._step_ann.__enter__()
            yield batch

    def record_train_step(self, seconds: float) -> None:
        self.step_ends.append(time.perf_counter())
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None
        self.feed.record_train_step(seconds)


def _worker_busy(snap) -> float:
    w = snap.workers
    return 0.0 if w is None else w.busy_time_s


def compiled_step(trainer, batch):
    """The trainer's step compiled for batches shaped like ``batch``, as
    ``Trainer.step_hlo_text`` compiles it (from the persistent cache where
    the run filled it), for its HLO text and its ``memory_analysis()``."""
    import jax

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))

    mbs = jax.eval_shape(trainer._microbatches, batch)
    args = jax.tree.map(spec, (trainer.params, trainer.opt_state,
                               trainer.ef_state))
    return trainer._jit_step.lower(*args, mbs).compile()


def compiled_bytes(compiled) -> int:
    """Device bytes a compiled program needs: arguments, outputs and
    temporaries, an output that aliases an argument counted once."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed place in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# the trace's readings a traced run's result line carries under "breakdown"
BREAKDOWN = ("device_ops", "idle_gaps", "idle_split_s", "idle_by_span",
             "idle_elsewhere", "device_by_scope")


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        keep_trace: Optional[str] = None,
        say: Callable[[str], None] = print) -> dict:
    """One run; returns the result object that ``run.py`` prints."""
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"JAX sees {len(devices)} x {devices[0].platform}; "
                     f"the cell needs {cell.chips} TPU chip(s)")
    if cell.model.ENTRY != "trainer":
        raise ValueError(f"unknown entry kind {cell.model.ENTRY!r}")
    cache = use_compile_cache() if devices[0].platform == "tpu" else "off"
    say(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{cache}")
    clock = CompileClock()
    w = _run_trainer(cell, seed, seconds, trace, t_start, clock, devices,
                     keep_trace, say)
    values = {}
    for m in cell.metrics:
        if (m["kind"] == "per_layer") != trace:
            continue
        v = _module(BENCH / "metrics" / f"{m['name']}.py").read(w)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": w.peak_bytes}
    out = {"correct": w.correct, "attempted": w.steps,
           "failed": w.failed_steps, "metrics": values, "device": dev}
    if trace:
        dev["memory_compiled_bytes"] = w.compiled_bytes
    if trace and w.trace is not None:
        dev["busy_s"] = w.trace["busy_s"]
        dev["window_s"] = w.trace["window_s"]
        out["breakdown"] = {k: w.trace[k] for k in BREAKDOWN}
    out["checks"] = w.checks
    return out


def first_steps(cell: Cell, seed: int, min_window_s: float, say,
                per_layer: bool = False) -> SimpleNamespace:
    """Set-up up to and through the first three steps: the sim, the
    parameters, the ``Trainer`` over ``open_feed`` and the program's
    readings the check compares (the three losses, the first gradient from
    AdamW's first moment, as leaf norms and as leaves on the host, each
    leaf's change after step 3).
    The feed is sized for a window of ``min_window_s`` seconds; with
    ``per_layer`` its batches' kernel costs and counters are recorded."""
    import jax
    import jax.numpy as jnp

    from repro.data import open_feed
    from repro.train.optimizer import AdamWConfig
    from repro.train.train_loop import Trainer, TrainerConfig

    c, tr, model = cell.config, cell.traffic, cell.model
    t = time.perf_counter()
    sim = gen.build_sim(tr["sim"], seed)
    batch = int(tr["feed"]["batch_size"])
    n_examples = len(sim.examples)
    if n_examples % batch:
        raise ValueError(f"{n_examples} examples are not whole batches of "
                         f"{batch}: epochs would share batches")
    say(f"sim: {n_examples} examples in {time.perf_counter() - t:.1f}s")

    key = jax.random.PRNGKey(gen.seed32(seed))
    init = jax.jit(functools.partial(gen.init_params,
                                     shapes=model.param_shapes(c)))
    norms = jax.jit(lambda tree: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]))
    change = jax.jit(lambda a, b: norms(jax.tree.map(jnp.subtract, a, b)))
    opt = c["optimizer"]
    params = init(key)
    trainer = Trainer(model.loss_fn(model.model_config(c)), params,
                      TrainerConfig(opt=AdamWConfig(**opt),
                                    log_every=1 << 30))
    del params
    min_rows = (batch * (3 + int(tr["warmup_steps"])) + n_examples
                + int(math.ceil(min_window_s * tr["rows_per_s_cap"])))
    spec = gen.dataset_spec(tr["feed"], c["projection"], c["seq_len"], seed,
                            min_rows)
    rec = PrepRecorder(model, c, seed, per_layer)
    feed = open_feed(spec, sim, prep_fn=rec)
    tf = TimedFeed(feed, int(tr["sample_batches"]), seed)
    try:
        trainer.fit(tf, max_steps=1)
        grad = np.asarray(norms(trainer.opt_state.m)) / (1 - opt["beta1"])
        grad_leaves = [np.asarray(x) / np.float32(1 - opt["beta1"])
                       for x in jax.tree.leaves(trainer.opt_state.m)]
        trainer.fit(tf, max_steps=3)
        p0 = init(key)
        moved = np.asarray(change(trainer.params, p0))
        del p0
    except BaseException:
        feed.close(timeout=0.5)
        raise
    return SimpleNamespace(
        sim=sim, trainer=trainer, feed=feed, tf=tf, rec=rec, batch=batch,
        init=init, key=key,
        prog={"losses": [h["loss"] for h in trainer.history[:3]],
              "grad": grad, "grad_leaves": grad_leaves, "change": moved})


def _run_trainer(cell: Cell, seed: int, seconds: float, trace: bool,
                 t_start: float, clock: CompileClock, devices, keep_trace,
                 say) -> SimpleNamespace:
    import jax

    st = first_steps(cell, seed, seconds, say, per_layer=trace)
    trainer, feed, tf = st.trainer, st.feed, st.tf
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        trainer.fit(tf, max_steps=3 + int(cell.traffic["warmup_steps"]))
        n_setup = clock.events
        before = feed.snapshot()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # Python calls would swamp it
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        say(f"setup: {setup_s:.1f}s, compile {clock.seconds:.1f}s in "
            f"{n_setup} compiles")
        with jax.profiler.TraceAnnotation("bench.window"):
            tf.open_window(seconds)
            trainer.fit(tf)
        after = feed.snapshot()
        in_window = clock.events - n_setup
        if trace:
            jax.profiler.stop_trace()
        peak = _peak_bytes(devices)
    finally:
        feed.close(timeout=0.5)
    say(f"compiles inside the window: {in_window}")

    ends = np.asarray(tf.step_ends[-(tf.delivered - tf._first):])
    steps = len(ends)
    window_s = float(ends[-1] - tf.t0)
    intervals = np.diff(np.concatenate([[tf.t0], ends]))
    window_losses = [h["loss"] for h in trainer.history[-steps:]]
    summary = n_bytes = None
    if trace:
        step = compiled_step(trainer, next(iter(tf.kept.values())))
        n_bytes = compiled_bytes(step)
        path = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        reduced = trace_mod.from_xplane(path[0])
        reduced["scopes"] = step_scopes(step.as_text(), cell.model)
        del step
        if keep_trace:
            Path(keep_trace).write_text(json.dumps(
                trace_mod.crop(reduced, 3)))
        shutil.rmtree(tdir, ignore_errors=True)
        summary = spans.summarize(reduced)
    del trainer
    st.trainer = None
    gc.collect()            # the jitted step holds the trainer in a cycle

    t = time.perf_counter()
    checks = check(cell, st, seed)
    say(f"check: {time.perf_counter() - t:.1f}s; window {steps} steps in "
        f"{window_s:.2f}s")
    cb, ca = before.client, after.client
    kernel_cost, counters = st.rec.sums(tf._first, tf.delivered)
    return SimpleNamespace(
        seconds=window_s, steps=steps, rows=steps * st.batch,
        intervals=intervals, setup_s=setup_s, compiles_in_window=in_window,
        flops=float(sum(st.rec.flops[tf._first:tf.delivered])),
        chips=cell.chips, device_kind=devices[0].device_kind,
        peak_bytes=peak, compiled_bytes=n_bytes, trace=summary,
        kernel_cost=kernel_cost, counters=counters,
        starved_s=ca.starved_time_s - cb.starved_time_s,
        h2d_s=ca.h2d_time_s - cb.h2d_time_s,
        worker_busy_s=_worker_busy(after) - _worker_busy(before),
        failed_steps=int(sum(not np.isfinite(x) for x in window_losses)),
        correct=all(v["value"] <= v["limit"] for v in checks.values()),
        checks=checks)


def check(cell: Cell, st: SimpleNamespace, seed: int) -> dict:
    """The numbers compared, each with its limit (``bench/reference.py``),
    from what ``first_steps`` and the window left in ``st``."""
    values = dict(rows_and_epochs(cell, st, seed))
    batches = reference_batches(cell, st, seed)
    ref = reference.reference_steps(
        cell.model, cell.config, lambda: st.init(st.key), batches,
        cell.config["optimizer"], int(cell.config["reference_block_rows"]))
    values.update(reference.compare_training(st.prog, ref))
    out = {}
    for name, limit in cell.limits.items():
        v = float(values[name])
        out[name] = {"value": v if np.isfinite(v) else float("inf"),
                     "limit": float(limit)}
    return out


def _truth(cell: Cell, st: SimpleNamespace) -> reference.SourceOfTruth:
    c = cell.config
    if getattr(st, "truth", None) is None:
        st.truth = reference.SourceOfTruth(
            st.sim, int(cell.traffic["sim"]["lookback_days"])
            * reference.MS_PER_DAY, c["seq_len"],
            [t for ts in c["projection"].values() for t in ts])
    return st.truth


def rows_and_epochs(cell: Cell, st: SimpleNamespace, seed: int) -> dict:
    """``wrong_rows`` over the held batches and ``epoch_errors`` over every
    trained batch; releases the held batches."""
    c, model, tf, rec = cell.config, cell.model, st.tf, st.rec
    sot = _truth(cell, st)
    wrong = 0
    for k in sorted(tf.kept):
        raw = sot.raw_batch(rec.keys[k])
        want = model.prep(raw, c, k, seed)
        got = {n: np.asarray(a) for n, a in tf.kept[k].items()}
        wrong += reference.wrong_rows(got, want, raw["_ts"])
    tf.kept.clear()
    examples = [reference.row_key(e.user_id, e.request_ts,
                                  e.candidate["item_id"])
                for e in st.sim.examples]
    return {"wrong_rows": wrong,
            "epoch_errors": reference.epoch_errors(rec.keys[:tf.delivered],
                                                   examples)}


def reference_batches(cell: Cell, st: SimpleNamespace, seed: int) -> list:
    """The first three batches rebuilt from the source of truth."""
    sot = _truth(cell, st)
    return [cell.model.prep(sot.raw_batch(st.rec.keys[k]), cell.config, k,
                            seed) for k in range(3)]
