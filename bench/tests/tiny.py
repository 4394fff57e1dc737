"""Tiny sizes of the cells for CPU tests: every width cut, the same code
paths, computed in float32 (at these widths bfloat16's rounding would be
most of what the check reads)."""
import json

from bench import harness

TRAFFIC = {
    "sim": {"n_users": 8, "n_items": 500, "events_per_user_day": 4.0,
            "requests_per_user_day": 4, "lookback_days": 5,
            "request_day": 6},
    "feed": {"batch_size": 8, "base_batch_size": 4, "n_workers": 2,
             "prefetch_depth": 2, "buffer_batches": 4,
             "window_cache_size": 0},
    "rows_per_s_cap": 5000, "sample_batches": 3,
}
TINY = {
    "bert4rec.short_seq": {
        "config": {"seq_len": 16, "embed_dim": 16, "item_vocab": 300,
                   "reference_block_rows": 4, "compute_dtype": "float32"},
        "traffic": TRAFFIC},
}


def cell(workload: str) -> "harness.Cell":
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return harness.load_cell(workload, TINY[workload], bench)
