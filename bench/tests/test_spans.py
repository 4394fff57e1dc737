"""The span reduction: device idle split by what the trainer thread was
inside, device time by model scope, each instant counted once; on a
hand-made trace with nested spans, nested device events and three threads
on lines of the same name, and on one recorded on a TPU v5e (the
fixture)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import spans, trace

FIXTURE = Path(__file__).parent / "fixtures" / "bert4rec_spans_trace.json.gz"


def _trace():
    # window [100, 300); busy 110-150 (a while holding two ops), 200-220,
    # 260-300 (an op straddling the end): idle 100-110, 150-200, 220-260
    ops = [["while.1", 110, 40], ["fusion.2", 115, 10], ["fusion.3", 130, 10],
           ["fusion.4", 200, 20], ["fusion.5", 260, 70]]
    trainer = [["bench.window", 100, 200],
               ["bench.feed_fetch", 100, 8], ["repro.feed.get", 100, 8],
               ["bench.train_step", 108, 97], ["repro.train.step", 108, 102],
               ["repro.train.dispatch", 108, 52],
               ["repro.train.inputs", 108, 4], ["repro.host.gc", 150, 5],
               ["repro.train.readback", 160, 35],
               ["bench.feed_fetch", 210, 40], ["repro.feed.get", 210, 40],
               ["repro.train.dispatch", 250, 6]]
    worker = [["repro.dpp.scan", 100, 30], ["repro.dpp.featurize", 150, 30]]
    other = [["repro.dpp.scan", 170, 60]]
    return {
        "planes": [
            {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": ops}]},
            {"name": "/host:CPU",
             "lines": [{"name": "python", "events": worker},
                       {"name": "python", "events": trainer},
                       {"name": "python", "events": other}]}],
        "modules": {"/device:TPU:0": [["jit__train_step(7)", 105, 195]]},
        "scopes": {"jit__train_step": {"while.1": "encoder",
                                       "fusion.2": "encoder",
                                       "fusion.3": "logits",
                                       "fusion.4": "optimizer"}}}


def _ns(d):
    return {k: round(v * 1e9) for k, v in d.items()}


def test_idle_split_is_exact_and_sums_to_idle():
    t = _trace()
    s = spans.summarize(t)
    split = _ns(s["idle_split_s"])
    assert split == {"dispatch": 18, "readback": 35, "feed": 38,
                     "unspanned": 9}
    base = trace.summarize(t)
    idle = base["window_s"] - base["busy_s"]
    assert sum(s["idle_split_s"].values()) == pytest.approx(idle, abs=1e-15)
    assert _ns(s["idle_by_span"]) == {
        "repro.feed.get": 38, "repro.train.readback": 35,
        "repro.train.dispatch": 11, "repro.host.gc": 5,
        "repro.train.step": 5, "none": 4, "repro.train.inputs": 2}


def test_other_threads_are_told_apart_by_line():
    s = spans.summarize(_trace())
    # the two workers' scans overlap the idle 100-110, 170-200, 220-230
    assert _ns(s["idle_elsewhere"]) == {"repro.dpp.scan": 50,
                                        "repro.dpp.featurize": 30}
    assert "repro.dpp.scan" not in s["idle_by_span"]


def test_device_by_scope_counts_each_instant_once():
    t = _trace()
    s = spans.summarize(t)
    assert _ns(s["device_by_scope"]) == {"encoder": 30, "unscoped": 40,
                                         "optimizer": 20, "logits": 10}
    assert sum(s["device_by_scope"].values()) == pytest.approx(
        trace.summarize(t)["busy_s"], abs=1e-15)
    del t["scopes"]
    assert _ns(spans.summarize(t)["device_by_scope"]) == {"unscoped": 100}


def test_span_seconds_union_per_thread_sum_over_threads():
    t = _trace()
    # a second feed.get on the trainer line, overlapping the first: one
    # thread's time under a name is counted once
    t["planes"][1]["lines"][1]["events"].append(["repro.feed.get", 104, 6])
    assert _ns(spans.summarize(t)["span_s"]) == {
        "repro.train.step": 102, "repro.dpp.scan": 90,
        "repro.train.dispatch": 58, "repro.feed.get": 50,
        "repro.train.readback": 35, "repro.dpp.featurize": 30,
        "repro.host.gc": 5, "repro.train.inputs": 4}


def test_trace_readings_are_kept_beside_the_spans():
    t = _trace()
    base, s = trace.summarize(t), spans.summarize(t)
    assert {k: v for k, v in s.items() if k in base and k != "idle_gaps"} \
        == {k: v for k, v in base.items() if k != "idle_gaps"}


def test_gaps_named_by_annotation_and_innermost_span():
    gaps = [(n, round(d * 1e9)) for n, d in spans.summarize(_trace())
            ["idle_gaps"]]
    assert gaps == [("bench.train_step > repro.train.readback", 50),
                    ("bench.feed_fetch > repro.feed.get", 40),
                    ("bench.feed_fetch > repro.feed.get", 10)]


def test_existing_readings_unchanged():
    t = _trace()
    base = trace.summarize(t)
    assert base["steps"] == 1
    assert round(base["busy_s"] * 1e9) == 100
    assert spans.summarize({"planes": t["planes"][1:]}) is None


def test_scopes_from_hlo_text():
    text = "\n".join([
        "HloModule jit__train_step, entry_computation_layout={()->()}",
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(_train_step)/while/body/transpose(jvp(logits))/mul"}',
        '  ROOT %while.2 = (f32[8]{0}) while(%t), metadata={op_name='
        '"jit(_train_step)/jvp(encoder)/while"}',
        '  %add.1 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/add"}',
        "HloModule jit_other, entry_computation_layout={()->()}",
        '  %fusion.7 = f32[8]{0} fusion(%p), metadata={op_name='
        '"jit(other)/optimizer/sub"}'])
    assert spans.op_scopes(text, spans.DEFAULT_SCOPES) == {
        "jit__train_step": {"fusion.7": "logits", "while.2": "encoder"},
        "jit_other": {"fusion.7": "optimizer"}}
    assert spans.scope_of("jit(s)/encoder/while/body/embed/gather",
                          spans.DEFAULT_SCOPES) == "embed"
    assert spans.scope_of("jit(s)/transpose(jvp(encoderx))/dot",
                          spans.DEFAULT_SCOPES) == ""
    # a scope the configuration adds is found inside a default one
    assert spans.scope_of("jit(s)/encoder/jvp(attn)/dot",
                          spans.DEFAULT_SCOPES + ("attn",)) == "attn"


def test_recorded_trace():
    """The first three window steps of ``bert4rec.short_seq`` traced on
    one TPU v5e (``run.py --trace 1 --keep-trace``)."""
    t = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    base, s = trace.summarize(t), spans.summarize(t)
    assert base["steps"] == 3
    idle = base["window_s"] - base["busy_s"]
    assert sum(s["idle_split_s"].values()) == pytest.approx(idle, rel=0.01)
    assert s["idle_split_s"]["dispatch"] > 0
    assert s["idle_split_s"]["readback"] > 0
    scoped = s["device_by_scope"]
    assert sum(scoped.values()) == pytest.approx(base["busy_s"], rel=1e-9)
    assert scoped["unscoped"] < 0.05 * base["busy_s"]
    assert scoped["logits"] > scoped["encoder"] > scoped["embed"] > 0
    # the workers' spans sit on lines of their own, all named alike
    assert {"repro.dpp.scan", "repro.dpp.featurize"} <= set(
        s["idle_elsewhere"])
    assert not set(s["idle_by_span"]) & {"repro.dpp.scan",
                                         "repro.dpp.featurize"}
    assert all(" > " in name for name, _ in s["idle_gaps"])
