"""Trait-aware columnar codec: roundtrip + selective decoding + density wins."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to a fixed-examples sweep (see the shim)
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import events as ev
from repro.storage import columnar


SCHEMA = ev.default_schema()


def _random_batch(n: int, seed: int = 0) -> ev.EventBatch:
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, 10**9, size=n)).astype(np.int64)
    return {
        "timestamp": ts,
        "item_id": rng.integers(0, 50_000, size=n).astype(np.int64),
        "action_type": rng.integers(0, 8, size=n).astype(np.int32),
        "surface": rng.integers(0, 4, size=n).astype(np.int32),
        "watch_time_ms": rng.integers(0, 100_000, size=n).astype(np.int32),
        "like": (rng.random(n) < 0.05).astype(np.int8),
        "comment": (rng.random(n) < 0.01).astype(np.int8),
        "share": (rng.random(n) < 0.01).astype(np.int8),
        "category": rng.integers(0, 64, size=n).astype(np.int32),
        "creator_id": rng.integers(0, 5_000, size=n).astype(np.int64),
    }


@pytest.mark.parametrize("n", [0, 1, 7, 256, 1000])
def test_roundtrip_all_traits(n):
    batch = _random_batch(n)
    blob = columnar.encode_stripe(batch, SCHEMA)
    out = columnar.decode_stripe(blob, SCHEMA)
    assert set(out) == set(batch)
    for k in batch:
        np.testing.assert_array_equal(out[k], batch[k], err_msg=k)
        assert out[k].dtype == batch[k].dtype


def test_roundtrip_compressed():
    pytest.importorskip("zstandard")
    batch = _random_batch(512)
    blob = columnar.encode_stripe(batch, SCHEMA, compress=True)
    out = columnar.decode_stripe(blob, SCHEMA)
    for k in batch:
        np.testing.assert_array_equal(out[k], batch[k])


def test_selective_decode_only_requested():
    batch = _random_batch(128)
    blob = columnar.encode_stripe(batch, SCHEMA)
    out = columnar.decode_stripe(blob, SCHEMA, traits=("timestamp", "item_id"))
    assert set(out) == {"timestamp", "item_id"}
    np.testing.assert_array_equal(out["item_id"], batch["item_id"])


def test_selective_decode_touches_fewer_bytes():
    batch = _random_batch(1024)
    blob = columnar.encode_stripe(batch, SCHEMA)
    layout = columnar.StripeLayout.parse(blob)
    full = layout.decoded_bytes()
    partial = layout.decoded_bytes(("timestamp", "item_id"))
    assert 0 < partial < full


def test_density_aware_encodings_beat_raw():
    batch = _random_batch(4096)
    blob = columnar.encode_stripe(batch, SCHEMA)
    raw = sum(v.nbytes for v in batch.values())
    assert len(blob) < raw  # trait-aware codec must win on realistic densities
    # sparse flags should land in bitmaps, timestamps in deltas
    header, _ = columnar._read_header(blob)
    codecs = {c["name"]: c["codec"] for c in header["cols"]}
    assert codecs["like"] == "bitmap"
    assert codecs["timestamp"] == "delta"
    assert codecs["action_type"] == "dict"


def test_stripe_num_events():
    batch = _random_batch(77)
    blob = columnar.encode_stripe(batch, SCHEMA)
    assert columnar.stripe_num_events(blob) == 77


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=300),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_property_roundtrip_sparse_flag(n, density, seed):
    rng = np.random.default_rng(seed)
    arr = (rng.random(n) < density).astype(np.int8)
    payload, meta = columnar.encode_column(arr, ev.SPARSE_FLAG)
    out = columnar.decode_column(payload, meta, np.dtype(np.int8))
    np.testing.assert_array_equal(out, arr)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=300),
    lo=st.integers(min_value=-(2**40), max_value=2**40),
    span=st.integers(min_value=0, max_value=2**33),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_property_roundtrip_monotone(n, lo, span, seed):
    rng = np.random.default_rng(seed)
    arr = np.sort(rng.integers(lo, lo + span + 1, size=n)).astype(np.int64)
    payload, meta = columnar.encode_column(arr, ev.DENSE_MONOTONE)
    out = columnar.decode_column(payload, meta, np.dtype(np.int64))
    np.testing.assert_array_equal(out, arr)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=200),
    vocab=st.integers(min_value=1, max_value=10_000),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_property_roundtrip_categorical(n, vocab, seed):
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, vocab, size=n).astype(np.int32)
    payload, meta = columnar.encode_column(arr, ev.CATEGORICAL)
    out = columnar.decode_column(payload, meta, np.dtype(np.int32))
    np.testing.assert_array_equal(out, arr)


def test_checksum_changes_on_corruption():
    batch = _random_batch(64)
    c1 = columnar.stripe_checksum(batch)
    batch["item_id"] = batch["item_id"].copy()
    batch["item_id"][3] += 1
    assert columnar.stripe_checksum(batch) != c1
