"""Program spans on the profiler's clock and model scopes in the compiled
step (DESIGN.md §13): a tiny ``Trainer.fit`` over a tiny ``open_feed``,
traced on the CPU backend, read back from its ``.xplane.pb``."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.projection import TenantProjection
from repro.data import DatasetSpec, SimSource, open_feed
from repro.dpp.featurize import FeatureSpec
from repro.dpp.worker import DPPWorker
from repro.models.recsys import BERT4RecConfig, bert4rec_loss, init_bert4rec
from repro.obs.spans import SpanTracker, stage
from repro.train.train_loop import Trainer, TrainerConfig

from conftest import make_sim

SEQ, VOCAB = 16, 64
CFG = BERT4RecConfig(embed_dim=16, n_blocks=1, n_heads=2, seq_len=SEQ,
                     item_vocab=VOCAB, compute_dtype=jnp.float32)
PROJ = TenantProjection(
    "t", SEQ, ("core",),
    traits_per_group={"core": ("timestamp", "item_id", "action_type")})
FEATURES = FeatureSpec(seq_len=SEQ, uih_traits=("item_id", "action_type"))


def _prep(raw):
    mask = raw["uih_mask"]
    return {"uih_item_id": (raw["uih_item_id"] % (VOCAB - 1) + 1
                            ).astype(np.int32),
            "uih_mask": mask, "mask_pos": mask}


class _Batches:
    """The feed's batches, anew for each ``fit`` (which stops a ``Feed``'s
    prefetch stage when it returns)."""

    def __init__(self, feed):
        self.feed = feed

    def __iter__(self):
        while (b := self.feed.get()) is not None:
            yield b


def _trainer():
    params = init_bert4rec(jax.random.PRNGKey(0), CFG)
    return Trainer(lambda p, b: bert4rec_loss(p, b, CFG), params,
                   TrainerConfig(log_every=1 << 30))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The host lines of a trace of four steps: ``[[name, start, end], ...]``
    per thread."""
    sim = make_sim(users=6, days=2, pin=False, capture_reference=False)
    # a buffer of one batch keeps the workers at work through the trace
    spec = DatasetSpec(tenant=PROJ, source=SimSource(min_rows=256),
                       features=FEATURES, batch_size=8, base_batch_size=4,
                       n_workers=2, prefetch_depth=2, buffer_batches=1)
    feed = open_feed(spec, sim, prep_fn=_prep)
    batches = _Batches(feed)
    trainer = _trainer()
    try:
        trainer.fit(batches, max_steps=1)       # compile outside the trace
        d = str(tmp_path_factory.mktemp("trace"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            trainer.fit(batches, max_steps=5)
        finally:
            jax.profiler.stop_trace()
    finally:
        feed.close(timeout=10.0)
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    return [[[e.name, e.start_ns, e.start_ns + e.duration_ns]
             for e in line.events if e.name.startswith("repro.")]
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines]


def _line(lines, name):
    return next(i for i, evs in enumerate(lines)
                if any(e[0] == name for e in evs))


def _names(evs):
    return {e[0] for e in evs}


def test_trainer_thread_spans(traced):
    trainer = traced[_line(traced, "repro.train.dispatch")]
    assert {"repro.train.step", "repro.train.dispatch",
            "repro.train.inputs", "repro.train.readback",
            "repro.feed.get"} <= _names(trainer)
    dispatch = [e for e in trainer if e[0] == "repro.train.dispatch"]
    inputs = [e for e in trainer if e[0] == "repro.train.inputs"]
    assert len(dispatch) == len(inputs) == 4
    for (_, s, e), (_, si, ei) in zip(dispatch, inputs):
        assert s <= si <= ei <= e           # inputs inside the dispatch


def test_worker_and_prefetch_threads_spans(traced):
    trainer = _line(traced, "repro.train.dispatch")
    workers = [i for i, evs in enumerate(traced)
               if {"repro.dpp.scan", "repro.dpp.featurize"} <= _names(evs)]
    prefetch = _line(traced, "repro.prefetch.h2d")
    assert workers and trainer not in workers
    assert prefetch != trainer and prefetch not in workers
    assert "repro.prefetch.prep" in _names(traced[prefetch])


def test_step_hlo_names_every_scope():
    sim = make_sim(users=4, days=2, pin=False, capture_reference=False)
    batch = _prep(DPPWorker(sim.materializer(), PROJ, FEATURES, sim.schema)
                  .process(sim.examples[:8]))
    names = re.findall(r'op_name="([^"]*)"',
                       _trainer().step_hlo_text(batch))
    for scope in ("embed", "encoder", "logits", "optimizer"):
        assert any(re.search(rf"(^|[/(]){scope}[)/]", n) for n in names), \
            scope


def test_counter_equals_sum_of_its_spans():
    sim = make_sim(users=6, days=2, pin=False, capture_reference=False)
    worker = DPPWorker(sim.materializer(), PROJ, FEATURES, sim.schema)
    tracker = SpanTracker(sample_every=1)
    items = []
    for seq, i in enumerate(range(0, 24, 4)):
        items.append(tracker.mint(seq))
        tracker.enter_item(seq)
        try:
            worker.process_jagged(sim.examples[i:i + 4])
        finally:
            tracker.exit_item()
    for stage_name, field in (("scan", "lookup_time_s"),
                              ("featurize", "featurize_time_s")):
        assert getattr(worker.stats, field) == pytest.approx(
            sum(sp.stage_s(stage_name) for sp in items), rel=1e-12)


def test_stage_that_raises_records_nothing():
    class Stats:
        t = 0.0

    stats, span = Stats(), SpanTracker(sample_every=1).mint(0)
    with stage("dpp", "scan", stats, "t", span=span) as st:
        pass
    assert stats.t == st.seconds and span.stages["scan"] == (st.t0, st.t1)
    with pytest.raises(KeyError):
        with stage("dpp", "featurize", stats, "t", span=span):
            raise KeyError
    assert stats.t == st.seconds and "featurize" not in span.stages
