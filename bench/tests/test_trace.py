"""The trace reduction: busy time as a union of device op intervals inside
the window, idle gaps named by the annotation over them, collective time;
on a hand-made trace and on one recorded on a TPU v5e (the fixture)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURES = Path(__file__).parent / "fixtures"
FIXTURE = FIXTURES / "bert4rec_trace.json.gz"
# trace.summarize's readings of both recorded traces as they were read when
# the trace kept the bench.* host events alone (of the second, which holds
# the program's spans too, those events only)
READINGS = json.loads((FIXTURES / "trace_readings.json").read_text())


def _trace(ops, host):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python", "events": host}]}]}


def test_hand_made_trace():
    # window [100, 200); ops overlap at 120-140; one all-reduce; one op
    # straddles the window's end
    ops = [["fusion.1", 110, 20], ["fusion.2", 120, 20],
           ["all-reduce.3", 150, 10], ["fusion.4", 190, 30],
           ["fusion.0", 10, 50]]
    host = [["bench.window", 100, 100], ["bench.feed_fetch", 140, 12],
            ["bench.train_step", 105, 50], ["bench.train_step", 160, 40]]
    s = trace.summarize(_trace(ops, host))
    assert s["window_s"] == pytest.approx(100e-9)
    # busy: 110-140 (30) + 150-160 (10) + 190-200 (10)
    assert s["busy_s"] == pytest.approx(50e-9)
    assert s["collective_s"] == pytest.approx(10e-9)
    assert s["steps"] == 2
    gaps = [(name, round(sec * 1e9)) for name, sec in s["idle_gaps"]]
    # gaps: 100-110, 140-150 (feed fetch over 140-152), 160-190
    assert gaps == [("bench.train_step", 30), ("bench.train_step", 10),
                    ("bench.feed_fetch", 10)]
    assert s["device_ops"][0][0] in {"fusion.1", "fusion.2"}


def test_program_spans_neither_count_steps_nor_name_gaps():
    ops = [["fusion.1", 110, 20]]
    host = [["bench.window", 100, 100], ["bench.train_step", 105, 20],
            ["repro.train.step", 100, 100], ["repro.feed.get", 130, 70]]
    s = trace.summarize(_trace(ops, host))
    assert s["steps"] == 1
    assert [name for name, _ in s["idle_gaps"]] == ["no annotation",
                                                    "bench.train_step"]


@pytest.mark.parametrize("name", sorted(READINGS))
def test_recorded_readings_unchanged(name):
    t = json.loads(gzip.decompress((FIXTURES / name).read_bytes()))
    s = trace.summarize(t)
    assert {k: s[k] for k in READINGS[name]} == READINGS[name]


def test_from_xplane_keeps_bench_and_program_events(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("repro.train.step"):
            with jax.profiler.TraceAnnotation("elsewhere.step"):
                jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    t = trace.from_xplane(str(path))
    names = {ev[0] for p in t["planes"] for ln in p["lines"]
             for ev in ln["events"]}
    assert names == {"bench.window", "repro.train.step"}
    assert t["modules"] == {}           # no TPU plane here


def test_no_window_or_no_device_op_reads_nothing():
    assert trace.summarize(_trace([["f", 0, 5]], [])) is None
    assert trace.summarize(_trace([["f", 0, 5]],
                                  [["bench.window", 10, 5]])) is None


def test_recorded_trace():
    """The first three window steps of ``bert4rec.short_seq`` traced on
    one TPU v5e (``run.py --trace 1 --keep-trace``)."""
    t = json.loads(gzip.decompress(FIXTURE.read_bytes()))
    s = trace.summarize(t)
    assert s["chips"] == 1 and s["steps"] >= 2
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["collective_s"] == 0
    # a busy union never exceeds the plain sum of op durations
    total = sum(d for p in t["planes"] if trace.DEVICE_PLANE.match(p["name"])
                for ln in p["lines"] for _, _, d in ln["events"])
    assert s["busy_s"] * 1e9 <= total
    assert s["idle_gaps"] and all(g[1] > 0 for g in s["idle_gaps"])
