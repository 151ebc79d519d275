"""ctypes bindings for the C++ stream engine (cpp/avro_engine.cc).

The engine is the perf twin of `ops.avro.AvroCodec`: one call decodes a
whole poll's worth of Confluent-framed Avro messages into columnar numpy
buffers (and encodes the other way).  Python stays the source of truth for
correctness (the pure codec is the test oracle; `tests/test_native.py`
cross-checks byte-for-byte); the engine is used automatically by the data
path when the shared library is present.

Build lazily on first use (`make -C iotml/cpp`, no external deps, a few
seconds) and fall back to the pure-Python codec when no toolchain exists —
saying so once on stderr, because the fallback decodes an order of
magnitude slower.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import List, Optional, Tuple

import numpy as np

from ..core.schema import RecordSchema
from ..obs import tracing

_TYPE_CODE = {"float": 0, "double": 1, "int": 2, "long": 3, "string": 4,
              "boolean": 5}
LABEL_STRIDE = 16  # fits "true"/"false"/"" labels with headroom

_CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "cpp")
_SO_PATH = os.path.join(_CPP_DIR, "build", "libiotml_stream.so")

_lib = None
_tried = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _CPP_DIR], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_SO_PATH)
    except Exception:
        return False


ENGINE_VERSION = 9  # must match iotml_engine_version() in avro_engine.cc


def _stale() -> bool:
    """A prebuilt .so from an older checkout must be rebuilt: `make` only
    triggers on mtime, so also compare against source files explicitly."""
    try:
        so_m = os.path.getmtime(_SO_PATH)
        for name in os.listdir(_CPP_DIR):
            if name.endswith((".cc", ".h")) or name == "Makefile":
                if os.path.getmtime(os.path.join(_CPP_DIR, name)) > so_m:
                    return True
    except OSError:
        return True
    return False


def load() -> Optional[ctypes.CDLL]:
    """The engine library, building it on first call; None if unavailable
    (reported once on stderr: the pure-Python fallback is correct but an
    order of magnitude slower, which a benchmark must not mistake for the
    data plane)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    # a span where the engine had to be BUILT (`make`, seconds, in a new
    # checkout or after an edit); loading a built one is milliseconds
    with tracing.phase("start", "engine", floor=0.5):
        _lib = _load()
    if _lib is None:
        print("iotml: native stream engine unavailable (build or load of "
              f"{_SO_PATH} failed); using the pure-Python codecs",
              file=sys.stderr, flush=True)
    return _lib


def _load() -> Optional[ctypes.CDLL]:
    if (not os.path.exists(_SO_PATH) or _stale()) and not _build() \
            and not os.path.exists(_SO_PATH):
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
        # version gate FIRST: touching a symbol a stale engine lacks would
        # raise AttributeError before the check meant to reject it
        lib.iotml_engine_version.restype = ctypes.c_int64
        if lib.iotml_engine_version() < ENGINE_VERSION:
            # stale binary and the rebuild failed (or produced an old ABI):
            # treat as unavailable rather than risk missing symbols
            return None
        lib.iotml_decode_batch.restype = ctypes.c_int64
        lib.iotml_decode_batch_nulls.restype = ctypes.c_int64
        lib.iotml_decode_batch_strict.restype = ctypes.c_int64
        lib.iotml_encode_batch.restype = ctypes.c_int64
        lib.iotml_json_decode_batch.restype = ctypes.c_int64
        lib.iotml_encode_batch_nulls.restype = ctypes.c_int64
        lib.iotml_format_rows_f32.restype = ctypes.c_int64
        lib.iotml_format_rows_f64.restype = ctypes.c_int64
        lib.iotml_frames_decode_columnar.restype = ctypes.c_int64
        # watermark-carrying decode (ABI 9): same walk, event-time
        # min/max out-params — the columnar plane's zero-cost watermark
        lib.iotml_frames_decode_columnar_ts.restype = ctypes.c_int64
        # write-path frame codec (ABI 8, frame_engine.cc)
        lib.iotml_frames_encode_columnar.restype = ctypes.c_int64
        lib.iotml_frames_encode_values.restype = ctypes.c_int64
        lib.iotml_frames_restamp.restype = ctypes.c_int64
        lib.iotml_frames_validate.restype = ctypes.c_int64
        return lib
    except (OSError, AttributeError):
        return None


def available() -> bool:
    return load() is not None


class NativeCodec:
    """Schema-compiled batch codec over the C++ engine."""

    def __init__(self, schema: RecordSchema):
        self.schema = schema
        self.types = np.array([_TYPE_CODE[f.avro_type] for f in schema.fields],
                              np.int8)
        self.nullable = np.array([1 if f.nullable else 0 for f in schema.fields],
                                 np.uint8)
        self.n_fields = len(schema.fields)
        self.n_strings = int((self.types == 4).sum())
        self.n_numeric = self.n_fields - self.n_strings
        # schema-constant inputs for the JSON batch parser: uppercase
        # column names (built once, not per poll batch on the hot path)
        names = [f.name.upper().encode() for f in schema.fields]
        self._json_names_blob = b"".join(names)
        self._json_name_offsets = np.zeros((len(names) + 1,), np.int64)
        np.cumsum([len(b) for b in names], out=self._json_name_offsets[1:])
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native stream engine unavailable")

    # ------------------------------------------------------------- decode
    def _decode_impl(self, messages: List[bytes], strip: int,
                     stride: int, want_nulls: bool, strict: bool = False):
        n = len(messages)
        if n == 0:
            empty = (np.zeros((0, self.n_numeric)),
                     np.zeros((0, self.n_strings), f"S{stride}"))
            return empty + ((np.zeros((0, self.n_fields), np.uint8),)
                            if want_nulls else ())
        blob = b"".join(messages)
        offsets = np.zeros((n + 1,), np.int64)
        np.cumsum([len(m) for m in messages], out=offsets[1:])
        numeric = np.empty((n, self.n_numeric), np.float64)
        labels = np.zeros((n, max(self.n_strings, 1)), f"S{stride}")
        args = [
            ctypes.c_char_p(blob),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(n),
            self.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            self.nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(self.n_fields),
            ctypes.c_int64(strip),
            numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            labels.ctypes.data_as(ctypes.c_char_p),
            ctypes.c_int64(stride),
        ]
        if want_nulls:
            nulls = np.zeros((n, self.n_fields), np.uint8)
            args.append(nulls.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
            rc = self._lib.iotml_decode_batch_nulls(*args)
        elif strict:
            rc = self._lib.iotml_decode_batch_strict(*args)
        else:
            rc = self._lib.iotml_decode_batch(*args)
        if rc != n:
            raise ValueError(f"malformed Avro message at row {-rc - 1}")
        out = (numeric, labels[:, : self.n_strings])
        return out + ((nulls,) if want_nulls else ())

    def decode_batch(self, messages: List[bytes], strip: int = 0,
                     stride: int = LABEL_STRIDE, strict: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """→ (numeric [n, n_numeric] float64, labels [n, n_strings]).

        Numeric columns are the schema's non-string fields in order — for
        the car schemas that is exactly the 18-sensor matrix.

        strict=True is the pass-through validation mode: it additionally
        rejects (ValueError) records the Python codec would reject
        (invalid UTF-8 strings, union branch outside {0,1}) or would
        canonicalize on re-encode (trailing bytes, non-minimal varints) —
        i.e. success guarantees forwarding the ORIGINAL bytes equals
        decode→re-encode, the fast-path parity contract."""
        return self._decode_impl(messages, strip, stride, want_nulls=False,
                                 strict=strict)

    def decode_batch_nulls(self, messages: List[bytes], strip: int = 0,
                           stride: int = LABEL_STRIDE):
        """decode_batch + per-field null bitmap [n, n_fields] (uint8).

        The columnar outputs cannot represent a null union distinctly
        (numeric null → 0.0, string null → ""); exact-semantics callers
        check the bitmap and fall back when any null is present.  The
        ENGINE_VERSION gate in load() guarantees the symbol exists."""
        return self._decode_impl(messages, strip, stride, want_nulls=True)

    # --------------------------------------------------------------- json
    def json_decode_batch(self, messages: List[bytes],
                          stride: int = LABEL_STRIDE):
        """Batch-parse flat JSON objects into the same columnar layout as
        decode_batch: → (numeric [n, n_numeric] float64, labels
        [n, n_strings] S-stride, nulls [n, n_fields] uint8, fallback [n]
        uint8).

        Missing columns and explicit JSON nulls on nullable columns set
        the null bitmap (the fleet's producer-named payloads make the
        KSQL-mangled columns permanently null — the hot case).  Rows the
        native parser cannot reproduce exactly (escapes, nested values,
        type mismatches, ints beyond 2^53, null on a non-nullable column)
        are flagged in `fallback` with undefined contents — the caller
        re-decodes those through json.loads.  Keys match schema column
        names case-insensitively (ASCII upper), like the Python leg's
        `{k.upper(): v}`."""
        n = len(messages)
        if n == 0:
            return (np.zeros((0, self.n_numeric)),
                    np.zeros((0, self.n_strings), f"S{stride}"),
                    np.zeros((0, self.n_fields), np.uint8),
                    np.zeros((0,), np.uint8))
        blob = b"".join(messages)
        offsets = np.zeros((n + 1,), np.int64)
        np.cumsum([len(m) for m in messages], out=offsets[1:])
        names_blob = self._json_names_blob
        name_offsets = self._json_name_offsets
        numeric = np.empty((n, self.n_numeric), np.float64)
        labels = np.zeros((n, max(self.n_strings, 1)), f"S{stride}")
        nulls = np.zeros((n, self.n_fields), np.uint8)
        fallback = np.zeros((n,), np.uint8)
        rc = self._lib.iotml_json_decode_batch(
            ctypes.c_char_p(blob),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(n),
            ctypes.c_char_p(names_blob),
            name_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            self.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            self.nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(self.n_fields),
            numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ctypes.c_int64(self.n_numeric),
            labels.ctypes.data_as(ctypes.c_char_p),
            ctypes.c_int64(self.n_strings),
            ctypes.c_int64(stride),
            nulls.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            fallback.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
        if rc < 0:
            raise ValueError("json batch decode rejected arguments")
        return numeric, labels[:, : self.n_strings], nulls, fallback

    # ------------------------------------------------------------- frames
    def frame_decoder(self, pinned_id_limit: Optional[int] = None
                      ) -> "FrameDecoder":
        """The store-frame columnar decoder compiled for this schema —
        the zero-copy pipeline's single decode entry point."""
        return FrameDecoder(self, pinned_id_limit=pinned_id_limit)

    # ------------------------------------------------------------- encode
    def encode_batch(self, numeric: np.ndarray, labels: Optional[np.ndarray],
                     schema_id: int = -1, stride: int = LABEL_STRIDE,
                     nulls: Optional[np.ndarray] = None) -> List[bytes]:
        """Columnar rows → list of (optionally framed) Avro messages.

        `nulls` ([n, n_fields] uint8) encodes branch 0 of the nullable
        union where set — the column slot's value is ignored for those
        fields.  A null flagged on a non-nullable field raises (no valid
        encoding exists)."""
        numeric = np.ascontiguousarray(numeric, np.float64)
        n = numeric.shape[0]
        if labels is None:
            labels = np.zeros((n, self.n_strings), f"S{stride}")
        labels = np.ascontiguousarray(labels.astype(f"S{stride}"))
        cap = n * (5 + self.n_fields * 20 + self.n_strings * stride) + 64
        out = np.empty((cap,), np.uint8)
        offsets = np.zeros((n + 1,), np.int64)
        args = [
            numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            labels.ctypes.data_as(ctypes.c_char_p),
            ctypes.c_int64(stride),
            ctypes.c_int64(n),
            self.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            self.nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(self.n_fields),
            ctypes.c_int64(schema_id),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(cap),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ]
        if nulls is not None:
            nulls = np.ascontiguousarray(nulls, np.uint8)
            args.append(nulls.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
            total = self._lib.iotml_encode_batch_nulls(*args)
        else:
            total = self._lib.iotml_encode_batch(*args)
        if total < 0:
            raise ValueError("encode rejected (overflow or impossible null)")
        raw = out.tobytes()
        return [raw[offsets[i]:offsets[i + 1]] for i in range(n)]

    def encode_frames(self, numeric: np.ndarray,
                      labels: Optional[np.ndarray],
                      timestamps: Optional[np.ndarray] = None,
                      keys=None, schema_id: int = 1,
                      nulls: Optional[np.ndarray] = None,
                      base_offset: int = 0,
                      stride: int = LABEL_STRIDE) -> bytes:
        """Columnar rows → ONE contiguous ready-to-append raw frame
        batch: Confluent-framed Avro values wrapped in the store's
        CRC32C frame, offsets stamped ``base_offset + i`` — the fused
        produce leg (a record is framed ONCE at conversion and never
        re-serialised; `Broker.produce_raw` appends these bytes
        segment-verbatim after restamping).  Byte parity with the
        python codec + store frame oracle is pinned by tests.

        `keys`: optional list of per-row key bytes (None entries = null
        key), or an ``S``-dtype array (all non-null) — the S-array form
        is passed as ONE fixed-stride block, zero per-record objects."""
        numeric = np.ascontiguousarray(numeric, np.float64)
        n = numeric.shape[0]
        if labels is None:
            labels = np.zeros((n, self.n_strings), f"S{stride}")
        labels = np.ascontiguousarray(labels.astype(f"S{stride}"))
        ts = np.zeros((n,), np.int64) if timestamps is None else \
            np.ascontiguousarray(timestamps, np.int64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        kargs = (None, None, ctypes.c_int64(0), None)
        key_bytes = 0
        if isinstance(keys, np.ndarray):
            keys = np.ascontiguousarray(keys)
            kargs = (keys.ctypes.data_as(u8p), None,
                     ctypes.c_int64(keys.dtype.itemsize), None)
            key_bytes = keys.nbytes
        elif keys is not None:
            kblob = b"".join(k or b"" for k in keys)
            koff = np.zeros((n + 1,), np.int64)
            np.cumsum([len(k or b"") for k in keys], out=koff[1:])
            knull = np.asarray([1 if k is None else 0 for k in keys],
                               np.uint8)
            kargs = (ctypes.c_char_p(kblob), koff.ctypes.data_as(i64p),
                     ctypes.c_int64(0), knull.ctypes.data_as(u8p))
            key_bytes = len(kblob)
        # worst case per row: frame head + value (5 + 20/field + strings)
        cap = n * (64 + 5 + self.n_fields * 20
                   + self.n_strings * stride) + key_bytes + 64
        out = ctypes.create_string_buffer(cap)
        nargs = None if nulls is None else np.ascontiguousarray(
            nulls, np.uint8).ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        rc = self._lib.iotml_frames_encode_columnar(
            numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            labels.ctypes.data_as(ctypes.c_char_p),
            ctypes.c_int64(stride), ctypes.c_int64(n),
            self.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            self.nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(self.n_fields), ctypes.c_int64(schema_id),
            nargs, *kargs,
            ts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ctypes.c_int64(int(base_offset)),
            ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(cap))
        if rc < 0:
            raise ValueError(
                "frame encode rejected (overflow or impossible null)")
        return out.raw[:rc]


#: flag bits reported by the frame decoder (frame_engine.cc FrameFlags)
FRAMES_STOP_TORN = 1     # torn/corrupt frame parked the scan (recovery)
FRAMES_STOP_SCHEMA = 2   # Confluent writer id != the pinned reader id

#: default bytes per row for message keys in columnar decode (matches
#: NativeKafkaBroker.KEY_STRIDE: MQTT-topic car keys fit with room)
KEY_STRIDE = 64


class FrameDecoder:
    """Columnar decoder over raw store-frame batches (frame_engine.cc).

    ONE decode entry point for the zero-copy data plane: live consume
    (`StreamConsumer.poll_into`) and timestamp-replay backfill both land
    here, over the same `[len|crc|attrs|offset|ts|key|value|headers]`
    frame bytes the segmented log persists and the wire's RAW_FETCH
    ships — so the two paths cannot drift.  Decodes into CALLER-OWNED
    preallocated float32/label/key buffers (`data.pipeline.DecodeRing`
    slots): zero per-record Python objects, zero per-chunk buffer churn.

    `pinned_id_limit` is the exclusive upper bound on positionally-safe
    Confluent writer ids (default: `stream.registry.RESERVED_ID_BASE`,
    the band where evolved writer schemas live): an evolved writer's
    frame — or a non-Confluent payload — stops the scan with
    `FRAMES_STOP_SCHEMA` and the caller resolves that chunk by name in
    Python instead of mis-reading it positionally.
    """

    def __init__(self, codec: NativeCodec,
                 pinned_id_limit: Optional[int] = None):
        from .registry import RESERVED_ID_BASE

        self.codec = codec
        self.pinned_id_limit = RESERVED_ID_BASE \
            if pinned_id_limit is None else int(pinned_id_limit)
        self._lib = codec._lib
        #: event-time bounds (ms) of the frames CONSUMED by the last
        #: decode_into call — decoded rows and skipped tombstones alike;
        #: -1 when that call consumed nothing.  The batch-granular
        #: watermark source (ISSUE 13): the frame head already carries
        #: every record's timestamp, so min/max costs nothing extra.
        self.last_ts_min = -1
        self.last_ts_max = -1

    @property
    def n_numeric(self) -> int:
        return self.codec.n_numeric

    @property
    def n_strings(self) -> int:
        return self.codec.n_strings

    def decode_into(self, buf, start_offset: int, out_numeric: np.ndarray,
                    out_labels: np.ndarray,
                    out_keys: Optional[np.ndarray] = None,
                    cap_rows: Optional[int] = None
                    ) -> Tuple[int, int, int, int]:
        """Decode raw frame bytes into the caller's column buffers.

        Args:
          buf: contiguous frame bytes (bytes/memoryview/bytearray) — a
            segment byte range, a RAW_FETCH payload, or the emulator's
            re-framed batch; may start below `start_offset` (skipped)
            and end mid-frame (ends the batch).
          start_offset: frames below this log offset are skipped.
          out_numeric: [cap, n_numeric] float32 C-contiguous.
          out_labels: [cap, n_strings] S-stride C-contiguous.
          out_keys: optional [cap] S-stride (message keys, truncated at
            stride-1 like the fused native path).
        Returns (rows, next_offset, flags, skipped_tombstones).
        """
        codec = self.codec
        cap = out_numeric.shape[0] if cap_rows is None \
            else min(int(cap_rows), out_numeric.shape[0])
        if out_labels.shape[0] < cap or \
                (out_keys is not None and out_keys.shape[0] < cap):
            raise ValueError("label/key buffers shorter than cap_rows")
        if isinstance(buf, (bytearray, memoryview)):
            buf = bytes(buf)  # borderline callers; the hot paths hand bytes
        c_buf = ctypes.cast(ctypes.c_char_p(buf),
                            ctypes.POINTER(ctypes.c_uint8))  # zero-copy
        next_off = ctypes.c_int64(start_offset)
        flags = ctypes.c_int64(0)
        skipped = ctypes.c_int64(0)
        ts_min = ctypes.c_int64(-1)
        ts_max = ctypes.c_int64(-1)
        label_stride = out_labels.dtype.itemsize
        rows = self._lib.iotml_frames_decode_columnar_ts(
            c_buf,
            ctypes.c_int64(len(buf)), ctypes.c_int64(int(start_offset)),
            codec.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            codec.nullable.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.c_int64(codec.n_fields),
            ctypes.c_int64(self.pinned_id_limit),
            out_numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out_labels.ctypes.data_as(ctypes.c_char_p),
            ctypes.c_int64(label_stride),
            out_keys.ctypes.data_as(ctypes.c_char_p)
            if out_keys is not None else None,
            ctypes.c_int64(out_keys.dtype.itemsize
                           if out_keys is not None else 0),
            ctypes.c_int64(cap), ctypes.byref(next_off),
            ctypes.byref(flags), ctypes.byref(skipped),
            ctypes.byref(ts_min), ctypes.byref(ts_max))
        if rows < 0:
            raise ValueError("frame decoder rejected arguments")
        self.last_ts_min = int(ts_min.value)
        self.last_ts_max = int(ts_max.value)
        return int(rows), int(next_off.value), int(flags.value), \
            int(skipped.value)
