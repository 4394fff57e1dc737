"""``hstu_large.long_uih`` at tiny sizes on the CPU: a whole run through
``harness.run`` is correct, a traced run finds the attention's scope in
the compiled step, and the configuration's kernel cost, pair counters and
useful FLOPs agree with counts made by hand."""
import json
import time

import numpy as np
import pytest

from bench import harness

WORKLOAD = "hstu_large.long_uih"
TINY = {
    "config": {"seq_len": 32, "embed_dim": 16, "n_heads": 2, "dqk": 8,
               "dv": 8, "n_blocks": 2, "item_vocab": 300,
               "num_negatives": 16,
               "reference_block_rows": 4, "compute_dtype": "float32"},
    "traffic": {
        "sim": {"n_users": 8, "n_items": 500, "events_per_user_day": 4.0,
                "requests_per_user_day": 4, "lookback_days": 5,
                "request_day": 6},
        "feed": {"batch_size": 8, "base_batch_size": 4, "n_workers": 2,
                 "prefetch_depth": 2, "buffer_batches": 4,
                 "window_cache_size": 0},
        "rows_per_s_cap": 5000, "sample_batches": 3},
}


def _cell():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.load_cell(WORKLOAD, TINY, bench)


def test_sound_run_is_correct():
    out = harness.run(_cell(), 4_000_000_123, 1.0, False, time.perf_counter(),
                      require_chip=False, say=lambda s: None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_rows_per_s", "step_ms_p95",
                                   "setup_s"}


def test_traced_run_finds_the_attention_scope():
    """The compiled step holds ``hstu_attention`` (else the run raises);
    the pair fill is read from the counters, the trace-read metrics are
    left out without a TPU plane."""
    cell = _cell()
    cell.metrics = [m for m in cell.metrics if m["name"] != "step_mfu"]
    out = harness.run(cell, 4_000_000_321, 1.0, True, time.perf_counter(),
                      require_chip=False, say=lambda s: None)
    assert out["correct"], out["checks"]
    assert 0 < out["metrics"]["hstu_pair_fill"]["value"] <= 100
    assert "hstu_attention_device_ms" not in out["metrics"]


def _batch(lengths, seq_len):
    mask = np.zeros((len(lengths), seq_len), bool)
    for r, n in enumerate(lengths):
        mask[r, seq_len - n:] = True
    return {"uih_mask": mask}


CONF = {"seq_len": 1024, "embed_dim": 8, "n_heads": 2,
        "dqk": 3, "dv": 5, "n_blocks": 2, "num_negatives": 7,
        "compute_dtype": "bfloat16"}


@pytest.mark.parametrize("lengths,useful,computed", [
    # rows of 0, 1, 300 and 1,024 events in 1,024 positions, the kernel's
    # blocks of 256: it visits key blocks from (1024 - n) // 256 to each
    # query block
    ([0], 0, 0),
    ([1], 1, 256 ** 2 * 1),
    ([300], 45150, 256 ** 2 * 3),
    ([1024], 524800, 256 ** 2 * 10),
    ([0, 1, 300, 1024], 569951, 256 ** 2 * 14),
])
def test_counters_by_hand(lengths, useful, computed):
    model = _cell().model
    got = model.counters(_batch(lengths, 1024), CONF)
    assert got == {"pairs_useful": useful, "pairs_computed": computed}


def test_cost_by_hand():
    model = _cell().model
    batch = _batch([300, 1024], 1024)
    flops, n_bytes = model.cost(batch, CONF)
    hq, hv = 2 * 3, 2 * 5
    # 45,150 + 524,800 causal pairs; QK and AV, 2 FLOPs a multiply-add, x3
    assert flops == 3 * 2 * (45150 + 524800) * 2 * (hq + hv)
    rows = 2 * 1024
    tables = 1024 * 256 * 4 + 256 * 4                  # 4 tiles of 256 x 256
    scalars = rows * 8 + 2 * 4
    qkvo = rows * (2 * hq + 2 * hv) * 2                 # bfloat16
    grads = rows * (2 * hq + hv) * 4                     # float32
    assert n_bytes == 2 * (2 * (qkvo + tables + scalars) + grads + tables)


def test_flops_per_row_by_hand():
    model = _cell().model
    c = dict(CONF, num_negatives=7)
    got = model.flops_per_row(_batch([0, 1, 5], 16), c)
    d, hq, hv = 8, 6, 10
    proj = 2 * (d * (2 * hq + 2 * hv) + hv * d)          # per position
    attn = 2 * (hq + hv)                                 # per pair
    logit = 2 * d * 8                                    # gold + 7 negatives
    want = [0.0,
            3 * (2 * (1 * proj + 1 * attn) + 0 * logit),
            3 * (2 * (5 * proj + 15 * attn) + 4 * logit)]
    np.testing.assert_allclose(got, want)
