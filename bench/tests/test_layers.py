"""What a configuration module declares for its per-layer metrics (``SCOPES``,
``KERNELS``, ``counters``) reaches the traced run's scope times, its
``w.kernel_cost`` and ``w.counters``, and ``bench.roofline.share``, with
nothing but new files: the configuration's module and metric files, here
written to a temporary directory, on a hand-made trace."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, roofline, spans

MODULE = '''
import numpy as np

ENTRY = "trainer"
SCOPES = ("attn",)
# attn moves 819 bytes and does 10 FLOPs a row (bytes bound); the encoder
# does 197,000 FLOPs and moves 1 byte a row (FLOPs bound)
KERNELS = {
    "attn": lambda b, c: (10.0 * b["n"].sum(), 819.0 * b["n"].sum()),
    "encoder": lambda b, c: (197000.0 * b["n"].sum(), 1.0 * b["n"].sum()),
}


def prep(raw, c, batch_index, seed):
    return {"n": raw["n"]}


def flops_per_row(batch, c):
    return np.ones(len(batch["n"]))


def counters(batch, c):
    return {"pairs": float((batch["n"] ** 2).sum()),
            "padded_pairs": float(len(batch["n"]) * c["max_len"] ** 2)}
'''
ROOFLINE_METRIC = '''
from bench import roofline


def read(w):
    s = roofline.share(w, "attn")
    return s and s.percent
'''
COUNTER_METRIC = '''
def read(w):
    c = w.counters
    return c["pairs"] / c["padded_pairs"] * 100.0 if c else None
'''
HLO = "\n".join([
    "HloModule jit__train_step, entry_computation_layout={()->()}",
    '  %custom-call.3 = f32[8]{0} custom-call(%p), custom_call_target='
    '"tpu_custom_call", metadata={op_name="jit(_train_step)/jvp(encoder)/'
    'attn/pallas_call"}',
    '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
    '"jit(_train_step)/transpose(jvp(encoder))/mul"}',
    '  %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name='
    '"jit(_train_step)/add"}'])


def _trace():
    # window [100, 200): attn 110-150, encoder 150-180, unscoped 185-195
    ops = [["custom-call.3", 110, 40], ["fusion.4", 150, 30],
           ["fusion.5", 185, 10]]
    host = [["bench.window", 100, 100], ["bench.train_step", 100, 98]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python", "events": host}]}],
        "modules": {"/device:TPU:0": [["jit__train_step(3)", 105, 95]]}}


def _files(tmp_path):
    for name, text in (("tinyk.py", MODULE),
                       ("attn_roofline.py", ROOFLINE_METRIC),
                       ("attn_pair_share.py", COUNTER_METRIC)):
        (tmp_path / name).write_text(text)
    return (harness._module(tmp_path / "tinyk.py"),
            harness._module(tmp_path / "attn_roofline.py"),
            harness._module(tmp_path / "attn_pair_share.py"))


def _window(model, per_layer=True):
    """Three prepared batches; the window holds the last two (18 rows'
    worth of ``n``)."""
    rec = harness.PrepRecorder(model, {"max_len": 4}, 1, per_layer)
    for n in ([1, 2], [3, 4], [5, 6]):
        rec({"n": np.asarray(n), "user_id": np.zeros(2, int),
             "request_ts": np.zeros(2, int), "cand_item_id": np.zeros(2, int)})
    return rec.sums(1, 3)


def _run_state(model):
    t = _trace()
    t["scopes"] = spans.op_scopes(HLO, harness.model_scopes(model))
    cost, counts = _window(model)
    return SimpleNamespace(
        trace=spans.summarize(t), kernel_cost=cost, counters=counts,
        chips=1, device_kind="TPU v5 lite")


def test_declared_scopes_kernels_and_counters_reach_the_metrics(tmp_path):
    model, roofline_metric, counter_metric = _files(tmp_path)
    assert harness.model_scopes(model) == spans.DEFAULT_SCOPES + ("attn",)
    w = _run_state(model)
    assert w.kernel_cost == {"attn": (180.0, 14742.0),
                             "encoder": (3546000.0, 18.0)}
    assert w.counters == {"pairs": 86.0, "padded_pairs": 64.0}
    # attn sits inside the encoder scope: the innermost scope counts
    assert {k: round(v * 1e9) for k, v in
            w.trace["device_by_scope"].items()} == {
        "attn": 40, "encoder": 30, "unscoped": 10}

    attn = roofline.share(w, "attn")
    assert attn.bound == "bytes"
    assert attn.percent == pytest.approx(14742 / 8.19e11 / 40e-9 * 100)
    assert attn.percent == pytest.approx(45.0)
    enc = roofline.share(w, "encoder")
    assert enc.bound == "flops"
    assert enc.percent == pytest.approx(60.0)
    assert roofline_metric.read(w) == pytest.approx(45.0)
    assert counter_metric.read(w) == pytest.approx(86 / 64 * 100)


def test_share_spreads_the_work_over_the_chips(tmp_path):
    model, _, _ = _files(tmp_path)
    w = _run_state(model)
    w.chips = 2         # the same per-chip time for twice the work
    w.kernel_cost = {k: (2 * f, 2 * b) for k, (f, b) in w.kernel_cost.items()}
    assert roofline.share(w, "attn").percent == pytest.approx(45.0)


def test_nothing_to_read_reads_nothing(tmp_path):
    model, roofline_metric, counter_metric = _files(tmp_path)
    w = _run_state(model)
    assert roofline.share(w, "logits") is None          # no kernel declared
    w.trace = None                                       # nothing traced
    assert roofline_metric.read(w) is None
    # an untraced run records no costs or counts, a module without
    # declarations none in any run
    assert _window(model, per_layer=False) == ({}, {})
    plain = SimpleNamespace(prep=model.prep, flops_per_row=model.flops_per_row)
    assert _window(plain) == ({}, {})
    assert harness.model_scopes(plain) == spans.DEFAULT_SCOPES
    assert counter_metric.read(SimpleNamespace(counters={})) is None


def test_kernel_outside_the_scopes_is_refused():
    model = SimpleNamespace(SCOPES=("attn",), KERNELS={"attn2": None})
    with pytest.raises(ValueError, match="attn2"):
        harness.model_scopes(model)


def test_traced_result_line_carries_the_breakdown(tmp_path, monkeypatch):
    model, _, _ = _files(tmp_path)
    w = _run_state(model)
    w.__dict__.update(peak_bytes=1, compiled_bytes=2, correct=True, steps=1,
                      failed_steps=0, checks={"x": {"value": 0, "limit": 0}})
    monkeypatch.setattr(harness, "_run_trainer", lambda *a, **k: w)
    metrics = [dict(name=n, unit="ms", kind="per_layer")
               for n in ("encoder_device_ms", "idle_dispatch_ms")]
    cell = harness.Cell("tinyk.cell", {}, model, {}, 1, metrics, {})
    out = harness.run(cell, 1, 1.0, True, 0.0, require_chip=False,
                      say=lambda s: None)
    assert out["device"]["memory_compiled_bytes"] == 2
    assert out["metrics"]["encoder_device_ms"]["value"] == pytest.approx(3e-5)
    assert out["metrics"]["idle_dispatch_ms"]["value"] == 0.0
    assert set(out["breakdown"]) == set(harness.BREAKDOWN)
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("hlo, lacks", [
    (HLO, None),
    (HLO.replace("attn/", ""), "attn"),
    ("\n".join(ln for ln in HLO.splitlines() if "op_name" not in ln),
     "any of the scopes"),
])
def test_step_without_the_scopes_fails_the_run(tmp_path, hlo, lacks):
    model, _, _ = _files(tmp_path)
    if lacks is None:
        assert harness.step_scopes(hlo, model) == spans.op_scopes(
            hlo, harness.model_scopes(model))
        return
    with pytest.raises(RuntimeError, match=lacks):
        harness.step_scopes(hlo, model)
