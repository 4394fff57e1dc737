"""CPU rehearsals of a whole run at tiny sizes: without a chip the command
refuses to print a result; past the chip check, a sound run is correct and
each fault planted in the timed path makes ``correct`` false."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.tiny import TINY, cell as tiny_cell

ROOT = harness.ROOT
WORKLOADS = sorted(TINY)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def _run(workload, seed=4_000_000_123):
    cell = tiny_cell(workload)
    return harness.run(cell, seed, 1.0, False, time.perf_counter(),
                       require_chip=False, say=lambda s: None)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_rows_per_s", "step_ms_p95",
                                   "setup_s"}
    assert list(out)[-1] == "checks"


def test_traced_run_past_the_chip_check():
    """The traced path on the CPU: the step's compiled bytes reach the
    device entry; with no TPU plane the trace reads nothing, so the
    per-layer metrics read from it are left out."""
    cell = tiny_cell(WORKLOADS[0])
    # step_mfu needs a chip's peak
    cell.metrics = [m for m in cell.metrics if m["name"] != "step_mfu"]
    out = harness.run(cell, 4_000_000_321, 1.0, True, time.perf_counter(),
                      require_chip=False, say=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["device"]["memory_compiled_bytes"] > 0
    assert set(out["metrics"]) == {"dpp_busy_us_per_row", "feed_wait_share",
                                   "h2d_ms_per_step"}
    assert "breakdown" not in out


def test_compiled_step_is_the_programs():
    cell = tiny_cell(WORKLOADS[0])
    st = harness.first_steps(cell, 4_000_000_333, 0.0, lambda s: None)
    try:
        batch = next(iter(st.tf.kept.values()))
        assert (harness.compiled_step(st.trainer, batch).as_text()
                == st.trainer.step_hlo_text(batch))
    finally:
        st.feed.close(timeout=0.5)


def _stuck_step(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.train.train_loop import Trainer

    orig = Trainer._train_step

    def stuck(self, params, opt_state, ef_state, mbs):
        _, _, ef, stats = orig(self, params, opt_state, ef_state, mbs)
        return params, opt_state, ef, stats

    monkeypatch.setattr(Trainer, "_train_step", stuck)


def _half_batch(monkeypatch, workload):
    """Half of each batch left out, the mean taken over the rest."""
    model = tiny_cell(workload).model
    orig = model.loss_fn
    shared = model.SHARED_KEYS

    def loss_fn(mcfg):
        loss = orig(mcfg)

        def half(params, batch):
            n = batch["uih_mask"].shape[0] // 2
            return loss(params, {k: v if k in shared else v[:n]
                                 for k, v in batch.items()})
        return half

    monkeypatch.setattr(harness, "_module", _patched_module(
        model, "loss_fn", loss_fn))


def _patched_module(model, name, value):
    orig = harness._module

    def load(path):
        mod = orig(path)
        if getattr(mod, "__name__", "") == getattr(model, "__name__", None):
            setattr(mod, name, value)
        return mod
    return load


def _altered_token(monkeypatch):
    """One UIH item id of every base batch altered where the DPP worker
    produces it."""
    import repro.dpp.worker as W

    orig = W.featurize_jagged

    def altered(examples, uihs, spec):
        jf = orig(examples, uihs, spec)
        arena = jf.values["item_id"]
        if len(arena):
            arena[len(arena) // 2] += 1
        return jf

    monkeypatch.setattr(W, "featurize_jagged", altered)


FAULTS = {"stuck_step": lambda mp, w: _stuck_step(mp),
          "half_batch": _half_batch,
          "altered_token": lambda mp, w: _altered_token(mp)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_caught(monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch, workload)
    out = _run(workload)
    assert not out["correct"], out["checks"]
    failed = [k for k, v in out["checks"].items() if v["value"] > v["limit"]]
    print(workload, fault, failed, json.dumps(out["checks"]))
    assert failed
