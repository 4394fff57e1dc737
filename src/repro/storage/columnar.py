"""Trait-aware columnar encoding for UIH stripes (paper §4.1.2).

A stripe is a column-oriented matrix: rows = chronologically ordered events,
columns = typed traits. Encodings exploit per-trait density/value structure:

  * ``dense_monotone`` (timestamps): delta encoding + minimal bit-width packing
  * ``dense_id`` / ``dense_value``: frame-of-reference (min-offset) + bit-width
  * ``sparse_flag`` (like/comment/share): presence bitmap (packbits); raw int8
    fallback if the column is actually dense
  * ``categorical``: dictionary (unique values) + bit-width-packed codes

The serialized layout stores a msgpack header with *per-column byte offsets*, so
**selective decoding** (§4.1.2 "secondary-level projection") skips irrelevant
columns entirely at the byte level. An optional zstd pass compresses the column
payloads (off by default: the bit-level codecs already dominate, and benchmarks
measure both).

``StripeLayout`` is a stripe's header parsed once — where compaction encodes
the stripe (``encode_stripe_and_layout``) or when a stored stripe is first
loaded — so the store's read path decodes columns from the kept offsets and
codec parameters and never parses msgpack per read.

``StripeDecodeCache`` is the store-side block-cache analogue (§4.2.3) for the
batched read path: a bounded, thread-safe LRU of *decoded* columns keyed on
``(blob identity, traits)``, so a hot stripe touched by many requests of one
batch (same-user, same-day traffic) is decoded once and shared.
"""
from __future__ import annotations

import struct
import threading
import zlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import msgpack
import numpy as np

from repro.core import events as ev

MAGIC = b"UIHC"
VERSION = 1

_WIDTHS = (np.uint8, np.uint16, np.uint32, np.uint64)
# dtype instances, not types: numpy converts a type on every call
_WIDTH_OF_SIZE = {np.dtype(w).itemsize: np.dtype(w) for w in _WIDTHS}
_INT64 = np.dtype(np.int64)


def _pack_unsigned(arr: np.ndarray) -> Tuple[bytes, dict]:
    """Frame-of-reference + minimal byte-width packing of an integer column."""
    assert arr.ndim == 1
    if arr.size == 0:
        return b"", {"codec": "empty", "n": 0}
    lo = int(arr.min())
    shifted = (arr.astype(np.int64) - lo).astype(np.uint64)
    hi = int(shifted.max())
    for w in _WIDTHS:
        if hi <= np.iinfo(w).max:
            payload = shifted.astype(w).tobytes()
            return payload, {"codec": "for", "n": int(arr.size), "lo": lo,
                             "w": int(np.dtype(w).itemsize)}
    raise AssertionError("unreachable")


def _unpack_unsigned(payload: bytes, meta: dict, dtype: np.dtype) -> np.ndarray:
    return _unpack(payload, meta["codec"], meta.get("w"), meta.get("lo"), dtype)


def _unpack(payload: bytes, codec: str, w: Optional[int], lo: Optional[int],
            dtype: np.dtype) -> np.ndarray:
    if codec == "empty":
        return np.zeros(0, dtype=dtype)
    arr = np.frombuffer(payload, dtype=_WIDTH_OF_SIZE[w]).astype(_INT64)
    if lo:
        arr += lo
    return arr.astype(dtype, copy=False)


def encode_column(arr: np.ndarray, encoding: str) -> Tuple[bytes, dict]:
    n = int(arr.size)
    if n == 0:
        return b"", {"codec": "empty", "n": 0, "enc": encoding}

    if encoding == ev.DENSE_MONOTONE:
        base = int(arr[0])
        deltas = np.diff(arr.astype(np.int64), prepend=arr[0])  # deltas[0]=0
        payload, meta = _pack_unsigned(deltas)
        meta.update(enc=encoding, codec="delta", base=base, inner=meta["codec"])
        return payload, meta

    if encoding == ev.SPARSE_FLAG:
        nz = int(np.count_nonzero(arr))
        if nz * 8 < n:  # sparse enough for a presence bitmap to pay off
            bits = np.packbits(arr.astype(bool))
            return bits.tobytes(), {"codec": "bitmap", "n": n, "enc": encoding}
        return arr.astype(np.int8).tobytes(), {"codec": "raw8", "n": n, "enc": encoding}

    if encoding == ev.CATEGORICAL:
        uniq, codes = np.unique(arr, return_inverse=True)
        if uniq.size <= max(2, n // 4):  # dictionary pays off
            code_payload, code_meta = _pack_unsigned(codes.astype(np.int64))
            dict_payload, dict_meta = _pack_unsigned(uniq.astype(np.int64))
            header = {"codec": "dict", "n": n, "enc": encoding,
                      "codes": code_meta, "dict": dict_meta,
                      "split": len(code_payload)}
            return code_payload + dict_payload, header
        payload, meta = _pack_unsigned(arr.astype(np.int64))
        meta.update(enc=encoding)
        return payload, meta

    # DENSE_ID / DENSE_VALUE and any unknown encoding: frame-of-reference pack
    payload, meta = _pack_unsigned(arr.astype(np.int64))
    meta.update(enc=encoding)
    return payload, meta


def decode_column(payload: bytes, meta: dict, dtype: np.dtype) -> np.ndarray:
    codec = meta["codec"]
    if codec == "empty":
        return np.zeros(0, dtype=dtype)
    if codec == "delta":
        out = _unpack(payload, meta["inner"], meta.get("w"), meta.get("lo"),
                      _INT64)
        # deltas[0] is 0: seeded with the base, the running sum is the column
        out[0] += meta["base"]
        np.add.accumulate(out, out=out)  # np.cumsum, minus its dispatch cost
        return out.astype(dtype, copy=False)
    if codec == "bitmap":
        n = meta["n"]
        bits = np.unpackbits(np.frombuffer(payload, dtype=np.uint8), count=n)
        return bits.astype(dtype)
    if codec == "raw8":
        return np.frombuffer(payload, dtype=np.int8).astype(dtype)
    if codec == "dict":
        split = meta["split"]
        codes = _unpack_unsigned(payload[:split], meta["codes"], np.int64)
        dictionary = _unpack_unsigned(payload[split:], meta["dict"], np.int64)
        return dictionary[codes].astype(dtype)
    if codec == "for":
        return _unpack_unsigned(payload, meta, dtype)
    raise ValueError(f"unknown codec {codec}")


# ---------------------------------------------------------------------------
# Stripe-level encode/decode
# ---------------------------------------------------------------------------

def stripe_checksum(batch: ev.EventBatch) -> int:
    """Order-sensitive checksum over all columns (used for O2O validation)."""
    crc = 0
    for name in sorted(batch.keys()):
        crc = zlib.crc32(name.encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(batch[name]).tobytes(), crc)
    return crc & 0xFFFFFFFF


def encode_stripe_and_layout(
    batch: ev.EventBatch,
    schema: ev.TraitSchema,
    compress: bool = False,
) -> Tuple[bytes, "StripeLayout"]:
    """Encode an event batch into a self-describing stripe blob, and keep
    the header it wrote as the blob's ``StripeLayout``."""
    n = ev.batch_len(batch)
    cols: List[dict] = []
    payloads: List[bytes] = []
    offset = 0
    for name in batch.keys():
        spec = schema.spec(name)
        payload, meta = encode_column(batch[name], spec.encoding)
        meta["name"] = name
        meta["dtype"] = np.dtype(spec.dtype).str
        meta["off"] = offset
        meta["len"] = len(payload)
        offset += len(payload)
        cols.append(meta)
        payloads.append(payload)
    body = b"".join(payloads)
    if compress:
        import zstandard as zstd

        body = zstd.ZstdCompressor(level=3).compress(body)
    header = {"n": n, "cols": cols, "zstd": bool(compress),
              "crc": stripe_checksum(batch)}
    packed = msgpack.packb(header, use_bin_type=True)
    blob = MAGIC + struct.pack("<HI", VERSION, len(packed)) + packed + body
    return blob, StripeLayout(header, 10 + len(packed))


def encode_stripe(
    batch: ev.EventBatch,
    schema: ev.TraitSchema,
    compress: bool = False,
) -> bytes:
    """Encode an event batch into a self-describing stripe blob."""
    return encode_stripe_and_layout(batch, schema, compress)[0]


def _read_header(blob: bytes) -> Tuple[dict, int]:
    assert blob[:4] == MAGIC, "bad stripe magic"
    version, hlen = struct.unpack_from("<HI", blob, 4)
    assert version == VERSION
    header = msgpack.unpackb(blob[10 : 10 + hlen], raw=False)
    return header, 10 + hlen


def stripe_num_events(blob: bytes) -> int:
    header, _ = _read_header(blob)
    return header["n"]


class StripeLayout:
    """A stripe's header, parsed once: ``header`` is the dict ``_read_header``
    returns and ``body_off`` where the column payloads start; ``cols`` maps
    each column to its header entry, dtype and payload byte range, so a read
    decodes a column straight from the blob with no header parse."""

    __slots__ = ("header", "body_off", "cols")

    def __init__(self, header: dict, body_off: int):
        self.header = header
        self.body_off = body_off
        # a zstd body is inflated before the read: offsets count from it
        base = 0 if header["zstd"] else body_off
        self.cols: Dict[str, Tuple[dict, np.dtype, int, int]] = {
            m["name"]: (m, np.dtype(m["dtype"]), base + m["off"],
                        base + m["off"] + m["len"])
            for m in header["cols"]}

    @classmethod
    def parse(cls, blob: bytes) -> "StripeLayout":
        return cls(*_read_header(blob))

    def decoded_bytes(self, traits: Optional[Sequence[str]] = None) -> int:
        """Payload bytes a decode of ``traits`` (None = every column) reads."""
        if traits is None:
            return sum(m["len"] for m in self.header["cols"])
        cols = self.cols
        return sum(cols[t][0]["len"] for t in traits if t in cols)

    def decode(self, blob: bytes, traits: Sequence[str]) -> Tuple[np.ndarray, ...]:
        """The ``traits`` columns, in that order; only their bytes are read."""
        data = memoryview(blob)
        if self.header["zstd"]:
            import zstandard as zstd

            data = zstd.ZstdDecompressor().decompress(data[self.body_off:])
        try:
            specs = [self.cols[t] for t in traits]
        except KeyError as e:
            raise AssertionError(f"stripe missing trait {e}") from None
        return tuple(decode_column(data[lo:hi], meta, dtype)
                     for meta, dtype, lo, hi in specs)


def decode_stripe(
    blob: bytes,
    schema: ev.TraitSchema,
    traits: Optional[Sequence[str]] = None,
) -> ev.EventBatch:
    """Decode a stripe's columns into a batch, in the stripe's column order;
    ``traits`` enables byte-level selective decoding."""
    layout = StripeLayout.parse(blob)
    names = [m["name"] for m in layout.header["cols"]]
    if traits is not None:
        want = set(traits)
        missing = want - set(names)
        assert not missing, f"stripe missing traits {missing}"
        names = [t for t in names if t in want]
    return dict(zip(names, layout.decode(blob, names)))


class StripeDecodeCache:
    """Bounded LRU of decoded columns keyed on ``(blob identity, traits)``.

    The cache holds a reference to each cached blob, so ``id(blob)`` stays
    unique among live keys (an evicted entry drops its reference and the key
    with it). Entries are tuples of read-only arrays, shared by every caller.
    Thread-safe: the batched executor decodes from several shard threads
    concurrently.
    """

    def __init__(self, max_entries: int = 256):
        assert max_entries > 0
        self.max_entries = max_entries
        # key -> (blob ref, decoded columns)
        self._entries: "OrderedDict[Tuple[int, Tuple[str, ...]], Tuple[bytes, Tuple[np.ndarray, ...]]]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(
        self,
        blob: bytes,
        layout: StripeLayout,
        traits: Tuple[str, ...],
    ) -> Tuple[Tuple[np.ndarray, ...], bool]:
        """The ``traits`` columns of the stripe ``blob`` (laid out as
        ``layout``) + whether they were served from cache."""
        key = (id(blob), traits)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry[0] is blob:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry[1], True
        cols = layout.decode(blob, traits)
        for arr in cols:  # shared across callers: freeze, don't corrupt
            arr.flags.writeable = False
        with self._lock:
            self.misses += 1
            self._entries[key] = (blob, cols)
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
        return cols, False

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)
