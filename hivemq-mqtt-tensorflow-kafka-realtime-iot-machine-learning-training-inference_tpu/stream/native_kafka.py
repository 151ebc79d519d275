"""ctypes bindings for the C++ Kafka wire client (cpp/kafka_client.cc).

`NativeKafkaBroker` is the native twin of `kafka_wire.KafkaWireBroker`:
same `Broker` duck-type (produce / fetch / end_offset / commit / ...), but
every wire byte is handled in C++ — the role librdkafka played for the
reference's `tensorflow_io.kafka` ops (reference cardata-v3.py:46-47).

Beyond the duck-type it exposes the fused hot path `fetch_decode()`:
fetch + Confluent framing strip + columnar Avro decode in a single native
call, returning `(numeric [n, F], labels [n, S], next_offset)` ready for
`normalizer.np` + `jax.device_put` — the KafkaDataset-equivalent with zero
per-message Python objects.  `StreamConsumer.poll_decoded` and
`SensorBatches` use it automatically when the broker supports it.

The Python client (`kafka_wire.py`) is the correctness oracle;
`tests/test_native_kafka.py` cross-checks the two against the same wire
server.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from .broker import (CorruptMessageError, Message, OffsetOutOfRangeError,
                     SchemaIdMismatchError, TopicSpec)
from .kafka_wire import NotLeaderForPartitionError, ProducePartitionMixin
from .native import LABEL_STRIDE, NativeCodec, load

_ERR_NAMES = {1: "OFFSET_OUT_OF_RANGE", 3: "UNKNOWN_TOPIC_OR_PARTITION",
              6: "NOT_LEADER_FOR_PARTITION",
              16: "NOT_COORDINATOR",
              35: "UNSUPPORTED_VERSION", 36: "TOPIC_ALREADY_EXISTS",
              58: "SASL_AUTHENTICATION_FAILED"}


class KafkaProtocolError(RuntimeError):
    def __init__(self, rc: int, what: str):
        code = -rc - 1000
        name = _ERR_NAMES.get(code, str(code))
        super().__init__(f"{what}: kafka error {name}" if rc <= -1000
                         else f"{what}: transport error")
        self.code = code if rc <= -1000 else None


def _check(rc: int, what: str) -> int:
    if rc < 0:
        raise KafkaProtocolError(rc, what)
    return rc


_i64p = ctypes.POINTER(ctypes.c_int64)
_u8p = ctypes.POINTER(ctypes.c_uint8)


def _sig(lib) -> None:
    """Full argtypes for every entry point — without them ctypes passes
    Python ints as 32-bit c_int, truncating handle pointers and int64s."""
    c = ctypes
    lib.iotml_kafka_connect.restype = c.c_void_p
    lib.iotml_kafka_connect.argtypes = [
        c.c_char_p, c.c_int32, c.c_char_p, c.c_char_p, c.c_char_p, c.c_double]
    lib.iotml_kafka_close.restype = None
    lib.iotml_kafka_close.argtypes = [c.c_void_p]
    sigs = {
        "metadata": [c.c_void_p, c.c_char_p],
        "create_topic": [c.c_void_p, c.c_char_p, c.c_int32],
        # + optional cleanup.policy config entry (NULL = none)
        "create_topic_cfg": [c.c_void_p, c.c_char_p, c.c_int32, c.c_char_p],
        "list_offset": [c.c_void_p, c.c_char_p, c.c_int32, c.c_int64],
        "produce": [c.c_void_p, c.c_char_p, c.c_int32, c.c_char_p, _i64p,
                    c.c_char_p, _i64p, _u8p, _i64p, c.c_int64],
        # tombstone-capable produce: value_null flags ride after key_null
        "produce_nulls": [c.c_void_p, c.c_char_p, c.c_int32, c.c_char_p,
                          _i64p, c.c_char_p, _i64p, _u8p, _u8p, _i64p,
                          c.c_int64],
        "produce_raw": [c.c_void_p, c.c_char_p, c.c_int32, _u8p, c.c_int64],
        "fetch": [c.c_void_p, c.c_char_p, c.c_int32, c.c_int64, c.c_int64],
        "staged_bytes": [c.c_void_p, _i64p, _i64p],
        "staged_value_nulls": [c.c_void_p, _u8p],
        "high_watermark": [c.c_void_p],
        "take": [c.c_void_p, c.c_char_p, _i64p, c.c_char_p, _i64p, _u8p,
                 _i64p, _i64p],
        "fetch_decode": [c.c_void_p, c.c_char_p, c.c_int32, c.c_int64,
                         c.POINTER(c.c_int8), _u8p, c.c_int64, c.c_int64,
                         c.POINTER(c.c_double), c.c_char_p, c.c_int64,
                         c.c_int64, _i64p],
        "fetch_decode_keys": [c.c_void_p, c.c_char_p, c.c_int32, c.c_int64,
                              c.POINTER(c.c_int8), _u8p, c.c_int64,
                              c.c_int64, c.POINTER(c.c_double), c.c_char_p,
                              c.c_int64, c.c_char_p, c.c_int64, c.c_int64,
                              _i64p],
        "commit": [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int32, c.c_int64],
        "commit_many": [c.c_void_p, c.c_char_p, c.c_char_p,
                        c.POINTER(c.c_int32), _i64p, c.c_int64],
        "committed": [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int32],
    }
    for name, argtypes in sigs.items():
        fn = getattr(lib, f"iotml_kafka_{name}")
        fn.restype = ctypes.c_int64
        fn.argtypes = argtypes
    lib.iotml_kafka_set_pinned_id_limit.restype = None
    lib.iotml_kafka_set_pinned_id_limit.argtypes = [c.c_void_p, c.c_int64]


class NativeKafkaBroker(ProducePartitionMixin):
    """Kafka-protocol client over the C++ engine, Broker duck-typed."""

    def __init__(self, servers: str, client_id: str = "iotml-native",
                 sasl_username: Optional[str] = None,
                 sasl_password: Optional[str] = None,
                 timeout_s: float = 30.0,
                 key_stride: Optional[int] = None,
                 pinned_id_limit: Optional[int] = None):
        #: bytes per row reserved for message keys in fetch_decode_keys;
        #: raise it where per-entity consumers join on keys longer than
        #: the MQTT-topic defaults (a truncated key aliases two cars).
        #: None → the class default KEY_STRIDE (single source of truth)
        if key_stride is not None:
            self.KEY_STRIDE = int(key_stride)
        #: rows whose key filled the stride (possibly truncated by the
        #: engine — the engine writes at most stride-1 bytes)
        self.keys_maybe_truncated = 0
        # (topic, partition) → the high-water mark its newest fetch
        # response carried (see last_hwm)
        self._hwm: dict = {}
        lib = load()
        if lib is None:
            raise RuntimeError("native stream engine unavailable")
        _sig(lib)
        self._lib = lib
        # bootstrap list: first reachable server wins (standard
        # bootstrap.servers semantics, shared parser with KafkaWireBroker)
        from ..utils.net import parse_bootstrap

        self._h = None
        for host, port in parse_bootstrap(servers):
            self._h = lib.iotml_kafka_connect(
                host.encode(), port, client_id.encode(),
                sasl_username.encode() if sasl_username is not None else None,
                sasl_password.encode() if sasl_password is not None else None,
                ctypes.c_double(timeout_s))
            if self._h:
                break
        if not self._h:
            raise ConnectionError(
                f"native kafka connect to {servers} failed"
                + (" (SASL)" if sasl_username else ""))
        # Runtime guard on the fused strip=5 decode (ON by default):
        # writer-schema ids at/above the reserved band
        # (stream.registry.RESERVED_ID_BASE) mark EVOLVED schemas a
        # positional v1 decode would silently mis-read — fetch_decode
        # stops before such a frame and raises SchemaIdMismatchError so
        # the consumer resolves that chunk by name in Python.  Pass
        # pinned_id_limit=-1 to restore the legacy blind strip.
        if pinned_id_limit is None:
            from .registry import RESERVED_ID_BASE

            pinned_id_limit = RESERVED_ID_BASE
        self.pinned_id_limit = int(pinned_id_limit)
        lib.iotml_kafka_set_pinned_id_limit(self._h, self.pinned_id_limit)
        self._meta: Dict[str, int] = {}
        self._rr: Dict[str, int] = {}
        # One socket + one C-side staged buffer per handle: serialize every
        # native call, as the Python twin (kafka_wire.KafkaWireBroker) does.
        # RLock because create_topic/produce_many re-enter via topic().
        self._lock = threading.RLock()

    # ------------------------------------------------------------ metadata
    def topic(self, name: str) -> TopicSpec:
        with self._lock:
            n = self._meta.get(name)
            if not n:
                n = _check(self._lib.iotml_kafka_metadata(self._h, name.encode()),
                           f"metadata({name})")
                if n == 0:
                    raise KeyError(name)
                self._meta[name] = n
            return TopicSpec(name, n)

    def refresh_topic(self, name: str) -> Optional[int]:
        """Drop the cached partition count and re-query broker metadata.

        `topic()` caches positive lookups forever (the fused fetch hot path
        must not pay a metadata round-trip per poll), so partition growth is
        only visible through an explicit refresh — the group coordinator
        calls this on its rate-limited metadata sweep (metadata.max.age.ms
        analogue).  Returns the fresh count, or None while the topic does
        not exist (yet)."""
        with self._lock:
            self._meta.pop(name, None)
            n = _check(self._lib.iotml_kafka_metadata(self._h, name.encode()),
                       f"metadata({name})")
            if n == 0:
                return None
            self._meta[name] = n
            return n

    def create_topic(self, name: str, partitions: int = 1,
                     retention_messages: Optional[int] = None,
                     cleanup_policy: Optional[str] = None) -> TopicSpec:
        with self._lock:
            existed = _check(self._lib.iotml_kafka_create_topic_cfg(
                self._h, name.encode(), partitions,
                cleanup_policy.encode() if cleanup_policy else None),
                f"create_topic({name})")
            if existed:
                # the topic's real partition count may differ from the request —
                # refresh from metadata so the partitioner never routes out of
                # range
                self._meta.pop(name, None)
                return self.topic(name)
            self._meta[name] = partitions
            return TopicSpec(name, partitions)

    # ------------------------------------------------------------- produce
    def _partition_count_or_default(self, topic: str) -> int:
        try:
            return self.topic(topic).partitions
        except KeyError:
            return 1

    def produce_many(self, topic: str, entries, partition=None) -> int:
        """entries: [(key, value, timestamp_ms[, headers])] → offset of
        the last one.  Trailing record headers (trace context on the
        in-process broker) are dropped — the native log has no header
        column; traces end at the native-engine boundary by design."""
        with self._lock:
            by_part: Dict[int, list] = {}
            for key, value, ts, *_hdrs in entries:
                p = self._partition_for(topic, key) if partition is None \
                    else partition
                by_part.setdefault(p, []).append((key, value, ts))
            last = -1
            for p, ents in sorted(by_part.items()):
                # shared columnar layout (kafka_wire.columnar_kvt): one
                # definition of the (values, offsets, key-null) C ABI for
                # both native produce paths
                from .kafka_wire import columnar_kvt

                # tombstones (value None): framed through the null-aware
                # entry point so the delete marker crosses the wire as a
                # null value, never a spoofed empty payload
                vnull = None
                if any(v is None for _k, v, _t in ents):
                    vnull = np.asarray(
                        [1 if v is None else 0 for _k, v, _t in ents],
                        np.uint8)
                    ents = [(k, v if v is not None else b"", t)
                            for k, v, t in ents]
                values, voff, keys, koff, knull, ts = columnar_kvt(ents)
                if keys is None:
                    kargs = (None, None, None)
                else:
                    kargs = (ctypes.c_char_p(keys),
                             koff.ctypes.data_as(_i64p),
                             knull.ctypes.data_as(_u8p))
                if vnull is not None:
                    rc = self._lib.iotml_kafka_produce_nulls(
                        self._h, topic.encode(), p,
                        ctypes.c_char_p(values),
                        voff.ctypes.data_as(_i64p), *kargs,
                        vnull.ctypes.data_as(_u8p),
                        ts.ctypes.data_as(_i64p), len(ents))
                else:
                    rc = self._lib.iotml_kafka_produce(
                        self._h, topic.encode(), p, ctypes.c_char_p(values),
                        voff.ctypes.data_as(_i64p), *kargs,
                        ts.ctypes.data_as(_i64p), len(ents))
                if rc == -1006:
                    raise NotLeaderForPartitionError(topic, p)
                base = _check(rc, f"produce({topic}:{p})")
                last = max(last, base + len(ents) - 1)
            return last

    def produce_raw(self, topic: str, partition: int,
                    frames: bytes) -> int:
        """RAW_PRODUCE through the C++ client: the pre-framed batch
        bytes go straight onto the socket (no MessageSet re-encode, no
        per-record work).  Same error surface as the Python wire client:
        NotImplementedError on an extension-less server (pin back to
        classic), CorruptMessageError on whole-batch rejection,
        NotLeaderForPartitionError on a sharded bounce."""
        with self._lock:
            rc = self._lib.iotml_kafka_produce_raw(
                self._h, topic.encode(), partition,
                ctypes.cast(ctypes.c_char_p(frames), _u8p),
                ctypes.c_int64(len(frames)))
            if rc == -1035:
                raise NotImplementedError(
                    "server lacks the RAW_PRODUCE extension")
            if rc == -1002:
                raise CorruptMessageError(topic, partition, -1)
            if rc == -1006:
                raise NotLeaderForPartitionError(topic, partition)
            return _check(rc, f"produce_raw({topic}:{partition})")

    # --------------------------------------------------------------- fetch
    def _raise_out_of_range(self, rc: int, topic: str, partition: int,
                            offset: int) -> None:
        """proto error 1 (rc -1001): the broker trimmed past `offset`.
        The iotml wire server carries the earliest retained offset in
        the hwm slot for this error (real brokers send -1; consumers
        re-query begin_offset on 0), staged by the native client."""
        if rc == -1001:
            earliest = max(
                int(self._lib.iotml_kafka_high_watermark(self._h)), 0)
            raise OffsetOutOfRangeError(topic, partition, offset, earliest)
        if rc == -1006:
            # NOT_LEADER_FOR_PARTITION (cluster shard routing): same
            # typed signal as the Python wire client, so routing clients
            # treat both transports identically
            raise NotLeaderForPartitionError(topic, partition)

    def _note_hwm(self, topic: str, partition: int) -> None:
        """After a fetch that succeeded (caller holds the lock): keep
        the high-water mark the engine staged off the response."""
        self._hwm[(topic, partition)] = int(
            self._lib.iotml_kafka_high_watermark(self._h))

    def last_hwm(self, topic: str, partition: int) -> Optional[int]:
        """The high-water mark of (topic, partition) as its newest fetch
        response carried it, None before the first: consumer lag at no
        extra request (the contract of KafkaWireBroker.last_hwm)."""
        return self._hwm.get((topic, partition))

    def fetch(self, topic: str, partition: int, offset: int,
              max_messages: int = 1024) -> List[Message]:
        with self._lock:
            rc = self._lib.iotml_kafka_fetch(self._h, topic.encode(), partition,
                                             ctypes.c_int64(offset),
                                             ctypes.c_int64(max_messages))
            if rc == -1003:
                raise KeyError(topic)
            self._raise_out_of_range(rc, topic, partition, offset)
            n = _check(rc, f"fetch({topic}:{partition}@{offset})")
            self._note_hwm(topic, partition)
            if n == 0:
                return []
            vb, kb = ctypes.c_int64(), ctypes.c_int64()
            self._lib.iotml_kafka_staged_bytes(self._h, ctypes.byref(vb),
                                               ctypes.byref(kb))
            values = ctypes.create_string_buffer(max(vb.value, 1))
            keys = ctypes.create_string_buffer(max(kb.value, 1))
            voff = np.zeros((n + 1,), np.int64)
            koff = np.zeros((n + 1,), np.int64)
            knull = np.zeros((n,), np.uint8)
            vnull = np.zeros((n,), np.uint8)
            moff = np.zeros((n,), np.int64)
            ts = np.zeros((n,), np.int64)
            # value-null flags staged BEFORE take (take clears staging):
            # tombstones surface as Message.value None, never b""
            self._lib.iotml_kafka_staged_value_nulls(
                self._h, vnull.ctypes.data_as(_u8p))
            self._lib.iotml_kafka_take(
                self._h, values, voff.ctypes.data_as(_i64p), keys,
                koff.ctypes.data_as(_i64p), knull.ctypes.data_as(_u8p),
                moff.ctypes.data_as(_i64p), ts.ctypes.data_as(_i64p))
            vraw = values.raw
            kraw = keys.raw
            out = []
            for i in range(n):
                key = None if knull[i] else kraw[koff[i]:koff[i + 1]]
                value = None if vnull[i] else vraw[voff[i]:voff[i + 1]]
                out.append(Message(topic, partition, int(moff[i]),
                                   value, key, int(ts[i])))
            return out

    def _open(self) -> None:
        """Raise where `close()` has run: the consumer's read-ahead
        thread may still hold this client then, and the engine
        dereferences the handle it is given."""
        if not self._h:
            raise ConnectionError("native Kafka client is closed")

    def fetch_decode(self, topic: str, partition: int, offset: int,
                     codec: NativeCodec, strip: int = 5,
                     max_rows: int = 4096
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Fused native poll → (numeric [n, F] float64, labels [n, S] bytes,
        next_offset).  n == 0 means no data at `offset`."""
        with self._lock:
            self._open()
            numeric = np.empty((max_rows, codec.n_numeric), np.float64)
            labels = np.zeros((max_rows, max(codec.n_strings, 1)),
                              f"S{LABEL_STRIDE}")
            next_off = ctypes.c_int64(offset)
            rc = self._lib.iotml_kafka_fetch_decode(
                self._h, topic.encode(), partition, ctypes.c_int64(offset),
                codec.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
                codec.nullable.ctypes.data_as(_u8p),
                ctypes.c_int64(codec.n_fields), ctypes.c_int64(strip),
                numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                labels.ctypes.data_as(ctypes.c_char_p),
                ctypes.c_int64(LABEL_STRIDE), ctypes.c_int64(max_rows),
                ctypes.byref(next_off))
            if rc == -1999:
                raise SchemaIdMismatchError(topic, partition, offset)
            if rc <= -2000:
                raise ValueError(f"malformed Avro message at row {-(rc + 2000) - 1}")
            if rc == -1003:
                raise KeyError(topic)
            self._raise_out_of_range(rc, topic, partition, offset)
            n = _check(rc, f"fetch_decode({topic}:{partition}@{offset})")
            self._note_hwm(topic, partition)
            return (numeric[:n], labels[:n, : codec.n_strings],
                    int(next_off.value))

    #: default bytes per row for message keys in fetch_decode_keys
    #: (MQTT-topic keys like "vehicles/sensor/data/electric-vehicle-00042"
    #: fit with room; longer keys truncate at stride-1, zero-padded —
    #: pass key_stride= at construction to widen)
    KEY_STRIDE = 64

    def fetch_decode_keys(self, topic: str, partition: int, offset: int,
                          codec: NativeCodec, strip: int = 5,
                          max_rows: int = 4096
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                     int]:
        """`fetch_decode` + per-message keys: (numeric [n, F], labels
        [n, S], keys [n] S{KEY_STRIDE} bytes, next_offset).  The key is
        the record's routing identity (car id via the MQTT-topic key) —
        what per-entity consumers (car-health detection) join on."""
        with self._lock:
            self._open()
            numeric = np.empty((max_rows, codec.n_numeric), np.float64)
            labels = np.zeros((max_rows, max(codec.n_strings, 1)),
                              f"S{LABEL_STRIDE}")
            keys = np.zeros((max_rows,), f"S{self.KEY_STRIDE}")
            next_off = ctypes.c_int64(offset)
            rc = self._lib.iotml_kafka_fetch_decode_keys(
                self._h, topic.encode(), partition, ctypes.c_int64(offset),
                codec.types.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
                codec.nullable.ctypes.data_as(_u8p),
                ctypes.c_int64(codec.n_fields), ctypes.c_int64(strip),
                numeric.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                labels.ctypes.data_as(ctypes.c_char_p),
                ctypes.c_int64(LABEL_STRIDE),
                keys.ctypes.data_as(ctypes.c_char_p),
                ctypes.c_int64(self.KEY_STRIDE),
                ctypes.c_int64(max_rows), ctypes.byref(next_off))
            if rc == -1999:
                raise SchemaIdMismatchError(topic, partition, offset)
            if rc <= -2000:
                raise ValueError(
                    f"malformed Avro message at row {-(rc + 2000) - 1}")
            if rc == -1003:
                raise KeyError(topic)
            self._raise_out_of_range(rc, topic, partition, offset)
            n = _check(rc, f"fetch_decode_keys({topic}:{partition}@{offset})")
            self._note_hwm(topic, partition)
            # A key that fills the stride was possibly truncated by the
            # engine (it writes at most stride-1 bytes): two distinct car
            # keys sharing a stride-1-byte prefix would alias into one
            # detector entity — surface that instead of staying silent.
            nt = int(np.sum(np.char.str_len(keys[:n])
                            >= self.KEY_STRIDE - 1))
            if nt:
                if not self.keys_maybe_truncated:
                    import warnings

                    warnings.warn(
                        f"{nt} message key(s) filled KEY_STRIDE-1="
                        f"{self.KEY_STRIDE - 1} bytes and may be truncated"
                        " (keys sharing that prefix alias); construct"
                        " NativeKafkaBroker with a larger key_stride=",
                        RuntimeWarning, stacklevel=2)
                self.keys_maybe_truncated += nt
            return (numeric[:n], labels[:n, : codec.n_strings], keys[:n],
                    int(next_off.value))

    # ------------------------------------------------------------- offsets
    def end_offset(self, topic: str, partition: int = 0) -> int:
        with self._lock:
            return _check(self._lib.iotml_kafka_list_offset(
                self._h, topic.encode(), partition, ctypes.c_int64(-1)),
                f"end_offset({topic}:{partition})")

    def begin_offset(self, topic: str, partition: int = 0) -> int:
        with self._lock:
            return _check(self._lib.iotml_kafka_list_offset(
                self._h, topic.encode(), partition, ctypes.c_int64(-2)),
                f"begin_offset({topic}:{partition})")

    # ------------------------------------------------- consumer-group API
    def commit(self, group: str, topic: str, partition: int,
               next_offset: int) -> None:
        with self._lock:
            _check(self._lib.iotml_kafka_commit(
                self._h, group.encode(), topic.encode(), partition,
                ctypes.c_int64(next_offset)), f"commit({group},{topic})")

    def commit_many(self, group: str, topic: str, entries) -> None:
        """Commit [(partition, next_offset), ...] of one topic in ONE wire
        request (the per-partition loop cost a round trip each)."""
        entries = list(entries)
        if not entries:
            return
        with self._lock:
            parts = np.asarray([p for p, _ in entries], np.int32)
            offs = np.asarray([o for _, o in entries], np.int64)
            _check(self._lib.iotml_kafka_commit_many(
                self._h, group.encode(), topic.encode(),
                parts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                offs.ctypes.data_as(_i64p), len(entries)),
                f"commit_many({group},{topic})")

    def committed(self, group: str, topic: str,
                  partition: int) -> Optional[int]:
        with self._lock:
            off = self._lib.iotml_kafka_committed(
                self._h, group.encode(), topic.encode(), partition)
            if off < -1:  # -1 itself means "no committed offset"
                raise KafkaProtocolError(off, f"committed({group},{topic})")
            return None if off == -1 else off

    def committed_many(self, group: str, pairs):
        """Committed offsets for [(topic, partition), ...]; pairs with
        no committed offset are omitted (Broker/wire-client contract).
        The native library has no batched OffsetFetch entry point, so
        this loops — callers get the uniform duck-type either way."""
        out = {}
        for t, p in pairs:
            off = self.committed(group, t, p)
            if off is not None:
                out[(t, p)] = off
        return out

    def close(self) -> None:
        with self._lock:
            if getattr(self, "_h", None):
                self._lib.iotml_kafka_close(self._h)
                self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
