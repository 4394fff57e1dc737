"""Device-side late materialization (DESIGN §3): compact jagged payloads vs
host-dense batches.

Two claims, both ASSERTED (not just reported):

1. **byte identity** — the jagged-emission client + ``DeviceMaterializer``
   produce exactly the batches the host-dense path produces after
   ``jax.device_put`` (same keys, dtypes, values);
2. **the H2D payload is strictly smaller** — bytes scale with kept
   elements, not B*L*T.

Times (host stage, transfer stage) are reported but NOT asserted: a
wall-clock comparison on a shared host is not a claim, and off-TPU the
densify kernel runs in interpret mode, which is orders of magnitude off real
Pallas lowering.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmarks.common import BenchResult
from repro.core.versioning import TrainingExample
from repro.dpp.client import RebatchingClient
from repro.dpp.device_mat import DeviceMaterializer, jagged_batch_nbytes
from repro.dpp.featurize import FeatureSpec, featurize_jagged
from repro.roofline.analysis import materialization_roofline

TS0 = 3_000_000_000  # > 2^31: exercises the windowed delta-decode path


def _synth_features(n_batches: int, rows: int, seq_len: int, mean_len: int,
                    seed: int = 7):
    rng = np.random.default_rng(seed)
    spec = FeatureSpec(seq_len=seq_len,
                       uih_traits=("item_id", "action", "timestamp"),
                       candidate_fields=("item_id",), label_fields=("click",))
    feats = []
    for k in range(n_batches):
        exs, uihs = [], []
        for i in range(rows):
            ln = int(rng.integers(1, 2 * mean_len))
            uihs.append({
                "item_id": rng.integers(0, 50_000, ln).astype(np.int64),
                "action": rng.integers(0, 8, ln).astype(np.int32),
                "timestamp": TS0 + np.sort(
                    rng.integers(0, 10**6, ln)).astype(np.int64),
            })
            exs.append(TrainingExample(
                request_id=f"r{k}-{i}", user_id=i, request_ts=TS0 + i,
                label_ts=TS0 + i + 1,
                candidate={"item_id": np.int64(rng.integers(0, 50_000))},
                labels={"click": np.float32(rng.integers(0, 2))}))
        feats.append(featurize_jagged(exs, uihs, spec))
    return feats


def _client_path(feats, full_batch: int, emit_jagged: bool):
    """Push every base batch through a rebatching client; return the emitted
    full batches and the host-stage wall time (the featurize-tail cost the
    device path is meant to shrink)."""
    c = RebatchingClient(full_batch_size=full_batch, shuffle_seed=0,
                         emit_jagged=emit_jagged)
    t0 = time.perf_counter()
    for jf in feats:
        c.put_jagged(jf)
    c.close()
    out = []
    while True:
        b = c.get_full_batch()
        if b is None:
            break
        out.append(b)
    return out, time.perf_counter() - t0


def run(quick: bool = False) -> List[BenchResult]:
    import jax

    if quick:
        n_batches, rows, seq_len, mean_len, full_b = 12, 8, 1024, 32, 16
    else:
        n_batches, rows, seq_len, mean_len, full_b = 48, 16, 2048, 96, 64
    feats = _synth_features(n_batches, rows, seq_len, mean_len)

    # median-of-3 of the reported host-stage times
    host_dense_s, host_jag_s = [], []
    for _ in range(3):
        dense, td = _client_path(feats, full_b, emit_jagged=False)
        jag, tj = _client_path(feats, full_b, emit_jagged=True)
        host_dense_s.append(td)
        host_jag_s.append(tj)
    host_dense_s.sort()
    host_jag_s.sort()
    t_dense, t_jag = host_dense_s[1], host_jag_s[1]
    assert len(dense) == len(jag) and dense

    mat = DeviceMaterializer()
    dense_bytes = jag_bytes = 0
    t_xfer_dense = t_xfer_jag = 0.0
    arena_rows = 0
    for d, jg in zip(dense, jag):
        t0 = time.perf_counter()
        want = jax.device_put(d)
        jax.block_until_ready(want)
        t_xfer_dense += time.perf_counter() - t0
        dense_bytes += sum(v.nbytes for v in d.values())
        t0 = time.perf_counter()
        got = mat(jg)
        jax.block_until_ready(got)
        t_xfer_jag += time.perf_counter() - t0
        jag_bytes += jagged_batch_nbytes(jg)
        arena_rows += int(np.sum(np.minimum(jg["uih_len"], seq_len)))
        # byte identity: the device path IS the host path, just materialized
        # on the other side of the link (device_put sorts dict keys; the
        # materializer mirrors host insertion order, so compare per key)
        assert set(got) == set(d), (sorted(got), sorted(d))
        for k in d:
            assert got[k].dtype == want[k].dtype, (k, got[k].dtype)
            assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k
    n = len(dense)

    # the asserted claim besides byte identity: strictly fewer H2D bytes per
    # batch than the host-densify baseline
    assert jag_bytes < dense_bytes, (jag_bytes, dense_bytes)

    roof = materialization_roofline(
        batch=full_b, seq_len=seq_len, n_traits=3,
        arena_rows=arena_rows // n, itemsize=4, ts_lanes=1)
    return [BenchResult(
        "device_mat/late_materialization",
        1e6 * t_jag / n,
        {"host_dense_us_per_batch": round(1e6 * t_dense / n, 1),
         "host_jagged_us_per_batch": round(1e6 * t_jag / n, 1),
         "host_stage_speedup": round(t_dense / t_jag, 2),
         "h2d_dense_bytes_per_batch": dense_bytes // n,
         "h2d_compact_bytes_per_batch": jag_bytes // n,
         "h2d_savings_pct": round(100.0 * (1 - jag_bytes / dense_bytes), 1),
         "fill_pct": round(100.0 * roof.fill, 1),
         "xfer_dense_us_per_batch": round(1e6 * t_xfer_dense / n, 1),
         "xfer_jagged_interp_us_per_batch": round(1e6 * t_xfer_jag / n, 1),
         "roofline_t_host_us": round(1e6 * roof.t_host_path, 2),
         "roofline_t_device_us": round(1e6 * roof.t_device_path, 2),
         "roofline_device_wins": roof.device_wins},
    )]


if __name__ == "__main__":
    for r in run():
        print(r.csv())
