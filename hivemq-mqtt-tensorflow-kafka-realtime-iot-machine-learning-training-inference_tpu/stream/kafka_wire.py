"""Kafka wire protocol — TCP client and server for the stream layer.

The reference's entire data plane is the Kafka protocol: `KafkaDataset`
consumes `kafka:9071` with SASL/PLAIN (reference cardata-v3.py:7-15,46-47),
`KafkaOutputSequence` produces to it, topics are provisioned with
`kafka-topics --create` (reference `01_installConfluentPlatform.sh:180-183`).
This module implements the protocol subset those paths need, natively:

- `KafkaWireBroker` — a *client* exposing the same duck-type as
  `stream.broker.Broker` (produce / fetch / end_offset / commit / ...), so
  `StreamConsumer`, `SensorBatches`, `OutputSequence` and every CLI run
  unchanged against a real cluster: `Broker()` → `KafkaWireBroker("host:port")`
  is the whole migration.
- `KafkaWireServer` — a TCP front for the in-process `Broker` emulator
  speaking the same protocol, so the client (and any standard Kafka client)
  can be exercised end-to-end without a cluster — the same trick as
  `mqtt.wire.MqttServer`.

Protocol details (all big-endian, classic encoding — no flexible/tagged
fields): request header v1 (api_key, api_version, correlation_id,
client_id); MessageSet v1 entries (magic 1, CRC over magic..value) for
Produce v2 / Fetch v2; Metadata v1; ListOffsets v1; OffsetCommit v2 /
OffsetFetch v1 (simple-consumer group offsets, generation −1);
CreateTopics v0; ApiVersions v0; SaslHandshake v0 + raw PLAIN token frame
(the pre-KIP-152 exchange the reference's SASL_PLAIN config uses).
"""

from __future__ import annotations

import re
import socket
import socketserver
import struct
import threading
import zlib
from typing import Dict, List, Optional, Tuple

from ..chaos import faults as chaos
from ..obs import tracing as _tracing
from ..utils.net import recv_exact
from .broker import (Broker, CorruptMessageError, Message,
                     OffsetOutOfRangeError, TopicSpec)

# api keys
PRODUCE, FETCH, LIST_OFFSETS, METADATA = 0, 1, 2, 3
OFFSET_COMMIT, OFFSET_FETCH = 8, 9
FIND_COORDINATOR, JOIN_GROUP, HEARTBEAT, LEAVE_GROUP, SYNC_GROUP = \
    10, 11, 12, 13, 14
SASL_HANDSHAKE, API_VERSIONS, CREATE_TOPICS = 17, 18, 19
# Emulator-family protocol extension (key far outside Kafka's range,
# like the retention.messages config entry): a fetch whose response is
# the broker's RAW store-frame bytes — [len|crc|attrs|offset|ts|key|
# value|headers] frames verbatim (ops.framing.RawFrameBatch) — so the
# consumer's columnar decoder runs over ONE buffer with zero
# per-record work on either side of the socket.  Standard Kafka
# clients never send it; standard servers answer UNSUPPORTED_VERSION
# and the client falls back to classic FETCH.
RAW_FETCH = 64
# The write-path mirror of RAW_FETCH (ISSUE 12): a produce whose
# payload is PRE-FRAMED store frames (offsets unstamped) the broker
# appends segment-verbatim after whole-batch CRC validation + offset
# stamping.  NOT idempotent (caller-owns-redelivery, exactly like
# PRODUCE — deliberately absent from IDEMPOTENT_APIS); a corrupt batch
# answers Kafka CORRUPT_MESSAGE (2) with nothing appended, and servers
# without the extension answer UNSUPPORTED_VERSION so producing clients
# pin back to classic PRODUCE.
RAW_PRODUCE = 65
# Emulator-family admin extension (ISSUE 14): elastic reassignment
# verbs against a live cluster — `python -m iotml.cluster add-broker /
# drain-broker` connect to any broker's wire port and drive the
# controller's online reassignment (new replica bootstraps over
# RAW_FETCH, joins the ISR, leadership moves, the old replica
# retires).  Served only when the wire server carries an `admin` hook
# (the ClusterController); everyone else answers UNSUPPORTED_VERSION.
CLUSTER_ADMIN = 66

# error codes
ERR_NONE = 0
ERR_OFFSET_OUT_OF_RANGE = 1
ERR_CORRUPT_MESSAGE = 2
ERR_UNKNOWN_TOPIC = 3
ERR_NOT_LEADER_FOR_PARTITION = 6
ERR_REQUEST_TIMED_OUT = 7
ERR_NOT_ENOUGH_REPLICAS = 19
ERR_INVALID_REQUIRED_ACKS = 21
ERR_NOT_COORDINATOR = 16
ERR_ILLEGAL_GENERATION = 22
ERR_UNKNOWN_MEMBER_ID = 25
ERR_REBALANCE_IN_PROGRESS = 27
ERR_TOPIC_AUTHORIZATION_FAILED = 29
ERR_UNSUPPORTED_VERSION = 35
ERR_TOPIC_EXISTS = 36
ERR_SASL_AUTH_FAILED = 58
ERR_INVALID_CONFIG = 40
ERR_FENCED_LEADER_EPOCH = 74  # Kafka's own fencing error code
# Kafka's UNKNOWN_SERVER_ERROR: the CLUSTER_ADMIN handler answers it
# when a reassignment verb raises — named so clients can map it typed
# (the protocol-conformance pass rejects bare numeric codes)
ERR_UNKNOWN_SERVER = -1

_SUPPORTED = {PRODUCE: (2, 2), FETCH: (2, 2), LIST_OFFSETS: (1, 1),
              METADATA: (1, 1), OFFSET_COMMIT: (2, 2), OFFSET_FETCH: (1, 1),
              FIND_COORDINATOR: (0, 0), JOIN_GROUP: (0, 0),
              HEARTBEAT: (0, 0), LEAVE_GROUP: (0, 0), SYNC_GROUP: (0, 0),
              SASL_HANDSHAKE: (0, 0), API_VERSIONS: (0, 0),
              CREATE_TOPICS: (0, 0), RAW_FETCH: (0, 0),
              RAW_PRODUCE: (0, 0), CLUSTER_ADMIN: (0, 0)}

# APIs the client may auto-retry after a reconnect (see _request): a
# duplicate of any of these is invisible (pure reads) or a no-op
# (liveness signal).  Everything else — produce, offset-commit, topic
# creation, group membership changes — may have been APPLIED by the dead
# server before it died, so a blind retry double-applies; those surface
# ConnectionError and the caller owns redelivery.  The R2 lint
# (iotml.analysis) holds every _request call site to this list.
IDEMPOTENT_APIS = frozenset({FETCH, RAW_FETCH, METADATA, LIST_OFFSETS,
                             OFFSET_FETCH, API_VERSIONS, SASL_HANDSHAKE,
                             HEARTBEAT, FIND_COORDINATOR})


class SaslAuthError(ConnectionError):
    """The server explicitly REJECTED the credentials (handshake error
    or non-empty auth response) — as opposed to dying mid-handshake.
    Failover must not retry rejected credentials against every
    bootstrap server; connectivity errors it may."""


class NotLeaderForPartitionError(ConnectionError):
    """The addressed broker does not lead this (topic, partition).

    Kafka error 6: the cluster's partition map moved (shard failover,
    stale client metadata) and this broker — alive and reachable —
    refuses to serve a partition it doesn't own.  Routing clients
    (``iotml.cluster.ClusterClient``) catch it, refresh their cached
    metadata, and retry against the real leader; it subclasses
    ConnectionError so non-routing callers' existing redelivery loops
    treat it as the failover signal it is."""

    def __init__(self, topic: str, partition: int):
        super().__init__(
            f"broker is not the leader for {topic}:{partition}; refresh "
            f"metadata and route to the owning broker (Kafka error 6)")
        self.topic = topic
        self.partition = partition


class CoordinatorMovedError(ConnectionError):
    """A group/offset request landed on a broker that is not the group
    coordinator (Kafka error 16, NOT_COORDINATOR).  The caller
    re-discovers the coordinator via FIND_COORDINATOR and retries —
    cluster group state is pinned to exactly one broker."""


class NotEnoughReplicasError(ConnectionError):
    """An ``acks=all`` produce was refused because the in-sync-replica
    set is below ``min_isr`` — or the topic has no ISR configured at
    all on a quorum-enabled broker (Kafka error 19,
    NOT_ENOUGH_REPLICAS).  NOTHING was appended, so redelivery is safe;
    it subclasses ConnectionError because the condition is retriable
    (an evicted follower re-admits, a reassignment completes) and every
    existing redelivery loop already treats ConnectionError as the
    try-again signal."""


class ProduceTimedOutError(ConnectionError):
    """An ``acks=all`` produce was APPENDED on the leader but the
    quorum high-water mark did not reach it within the request timeout
    (Kafka error 7, REQUEST_TIMED_OUT).  The record is durable on the
    leader yet unacked — the caller redelivers (at-least-once, exactly
    Kafka's producer-timeout contract; consumers cannot have observed
    the unacked copy, it sits above the quorum HWM)."""


class FencedEpochError(ConnectionError):
    """A produce/commit was refused because the leadership epochs
    disagree — either this client slept through a failover (its epoch
    is stale) or it reached a RESURRECTED OLD LEADER (the server's
    epoch is stale).  Both directions protect the log from splitting.
    Subclasses ConnectionError so every existing redelivery loop
    (scorer rewind, replica reconnect) treats it as a failover signal;
    the client has already re-resolved topology before raising."""


# ---------------------------------------------------------- epoch carrier
# The fencing epoch rides the request header's client_id as a trailing
# `@e<N>` tag — the one header field the classic encoding lets us extend
# without changing a single wire type, so standard Kafka clients (no
# tag → unfenced legacy path) remain byte-compatible with the server.
_EPOCH_TAG_RE = re.compile(r"^(.*)@e(\d+)$")


def tag_client_id(client_id: str, epoch: Optional[int]) -> str:
    return client_id if epoch is None else f"{client_id}@e{int(epoch)}"


def parse_client_epoch(client_id: Optional[str]) -> Tuple[str, Optional[int]]:
    """(bare client id, stamped epoch or None) from a header client_id."""
    if not client_id:
        return client_id or "", None
    m = _EPOCH_TAG_RE.match(client_id)
    if m is None:
        return client_id, None
    return m.group(1), int(m.group(2))


# ------------------------------------------------------------- primitives
class _Writer:
    def __init__(self):
        self.buf = bytearray()

    def i8(self, v):  self.buf += struct.pack(">b", v); return self
    def i16(self, v): self.buf += struct.pack(">h", v); return self
    def i32(self, v): self.buf += struct.pack(">i", v); return self
    def i64(self, v): self.buf += struct.pack(">q", v); return self
    def u32(self, v): self.buf += struct.pack(">I", v); return self

    def string(self, s: Optional[str]):
        if s is None:
            return self.i16(-1)
        b = s.encode()
        self.i16(len(b))
        self.buf += b
        return self

    def bytes_(self, b: Optional[bytes]):
        if b is None:
            return self.i32(-1)
        self.i32(len(b))
        self.buf += b
        return self

    def array(self, items, fn):
        self.i32(len(items))
        for it in items:
            fn(self, it)
        return self


class _Reader:
    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def _unpack(self, fmt, size):
        (v,) = struct.unpack_from(fmt, self.buf, self.pos)
        self.pos += size
        return v

    def i8(self):  return self._unpack(">b", 1)
    def i16(self): return self._unpack(">h", 2)
    def i32(self): return self._unpack(">i", 4)
    def i64(self): return self._unpack(">q", 8)
    def u32(self): return self._unpack(">I", 4)

    def string(self) -> Optional[str]:
        n = self.i16()
        if n < 0:
            return None
        s = self.buf[self.pos:self.pos + n].decode()
        self.pos += n
        return s

    def bytes_(self) -> Optional[bytes]:
        n = self.i32()
        if n < 0:
            return None
        b = self.buf[self.pos:self.pos + n]
        self.pos += n
        return b

    def array(self, fn) -> list:
        n = self.i32()
        return [fn(self) for _ in range(max(n, 0))]


# ---------------------------------------------------------- message sets
# Native (C++) codec for the hot directions: the pure-Python loops below
# are the oracle and the fallback, but at platform rates (two consumers +
# a producer through one wire server = tens of thousands of records/s)
# the per-record Writer/Reader + crc32 work was a large slice of the
# server process's core.  Loaded lazily; byte parity is pinned by
# tests/test_kafka_wire.py.
_NATIVE_LIB = None
_NATIVE_TRIED = False


def _native_lib():
    global _NATIVE_LIB, _NATIVE_TRIED
    if not _NATIVE_TRIED:
        _NATIVE_TRIED = True
        try:
            import ctypes

            from .native import load

            lib = load()
            if lib is not None:
                c = ctypes
                i64p = c.POINTER(c.c_int64)
                u8p = c.POINTER(c.c_uint8)
                lib.iotml_msgset_encode.restype = c.c_int64
                lib.iotml_msgset_encode.argtypes = [
                    c.c_char_p, i64p, c.c_char_p, i64p, u8p, i64p, i64p,
                    c.c_int64, u8p, c.c_int64]
                lib.iotml_msgset_decode.restype = c.c_int64
                lib.iotml_msgset_decode.argtypes = [
                    c.c_char_p, c.c_int64, c.c_int64, i64p, i64p, i64p,
                    u8p, u8p, c.c_int64, i64p, u8p, u8p, c.c_int64]
                _NATIVE_LIB = lib
        except Exception:
            _NATIVE_LIB = None
    return _NATIVE_LIB


def _encode_message_set_py(entries) -> bytes:
    out = _Writer()
    for offset, key, value, ts in entries:
        body = _Writer()
        body.i8(1).i8(0).i64(ts)          # magic 1, attributes 0, timestamp
        body.bytes_(key).bytes_(value)
        msg = struct.pack(">I", zlib.crc32(bytes(body.buf))) + bytes(body.buf)
        out.i64(offset).i32(len(msg))
        out.buf += msg
    return bytes(out.buf)


def columnar_kvt(kvt_entries):
    """[(key, value, ts)] → (values, voff, keys, koff, knull, ts) arrays —
    the columnar layout both native produce paths (the C++ client's
    produce_many and the server-side msgset encoder) hand to the C ABI.
    keys/koff/knull are None when every key is None (callers pass NULL
    pointers, the all-unkeyed fast case)."""
    import numpy as np

    n = len(kvt_entries)
    values = b"".join(v for _, v, _ in kvt_entries)
    voff = np.zeros((n + 1,), np.int64)
    np.cumsum([len(v) for _, v, _ in kvt_entries], out=voff[1:])
    ts = np.asarray([t for _, _, t in kvt_entries], np.int64)
    if not any(k is not None for k, _, _ in kvt_entries):
        return values, voff, None, None, None, ts
    keys = b"".join(k or b"" for k, _, _ in kvt_entries)
    koff = np.zeros((n + 1,), np.int64)
    np.cumsum([len(k or b"") for k, _, _ in kvt_entries], out=koff[1:])
    knull = np.asarray([1 if k is None else 0 for k, _, _ in kvt_entries],
                       np.uint8)
    return values, voff, keys, koff, knull, ts


def encode_message_set(entries: List[Tuple[int, Optional[bytes],
                                           Optional[bytes], int]]) -> bytes:
    """entries: [(offset, key, value, timestamp_ms)] → MessageSet v1 bytes."""
    lib = _native_lib()
    # a null VALUE has no native representation on the encode side (the
    # server never stores them); fall back for exactness
    if lib is None or not entries or \
            any(v is None for _, _, v, _ in entries):
        return _encode_message_set_py(entries)
    import ctypes

    import numpy as np

    n = len(entries)
    values, voff, keys, koff, knull, ts = columnar_kvt(
        [(k, v, t) for _, k, v, t in entries])
    offs = np.asarray([o for o, _, _, _ in entries], np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    if keys is None:
        kargs = (None, None, None)
        keys_len = 0
    else:
        kargs = (ctypes.c_char_p(keys), koff.ctypes.data_as(i64p),
                 knull.ctypes.data_as(u8p))
        keys_len = len(keys)
    cap = len(values) + keys_len + 40 * n
    out = ctypes.create_string_buffer(cap)
    rc = lib.iotml_msgset_encode(
        ctypes.c_char_p(values), voff.ctypes.data_as(i64p), *kargs,
        ts.ctypes.data_as(i64p), offs.ctypes.data_as(i64p), n,
        ctypes.cast(out, u8p), cap)
    if rc < 0:
        return _encode_message_set_py(entries)
    return out.raw[:rc]


def _decode_message_set_py(buf: bytes):
    out = []
    r = _Reader(buf)
    while r.pos + 12 <= len(buf):
        offset = r.i64()
        size = r.i32()
        if r.pos + size > len(buf):
            break  # partial trailing message
        end = r.pos + size
        crc = r.u32()
        if zlib.crc32(buf[r.pos:end]) != crc:
            raise ValueError(f"message CRC mismatch at offset {offset}")
        magic = r.i8()
        r.i8()  # attributes (no compression support needed)
        ts = r.i64() if magic >= 1 else 0
        key = r.bytes_()
        value = r.bytes_()
        r.pos = end
        out.append((offset, key, value, ts))
    return out


def decode_message_set(buf: bytes) -> List[Tuple[int, Optional[bytes],
                                                 Optional[bytes], int]]:
    """MessageSet v1 bytes → [(offset, key, value, timestamp_ms)].  A
    truncated trailing entry (Kafka allows partial final messages in fetch
    responses) is dropped."""
    lib = _native_lib()
    if lib is None or len(buf) < 26:
        return _decode_message_set_py(buf)
    import ctypes

    import numpy as np

    max_n = len(buf) // 26 + 1  # 26 = min bytes per v1 record
    offs = np.zeros((max_n,), np.int64)
    ts = np.zeros((max_n,), np.int64)
    koff = np.zeros((max_n + 1,), np.int64)
    knull = np.zeros((max_n,), np.uint8)
    voff = np.zeros((max_n + 1,), np.int64)
    vnull = np.zeros((max_n,), np.uint8)
    keys = ctypes.create_string_buffer(max(len(buf), 1))
    values = ctypes.create_string_buffer(max(len(buf), 1))
    i64p = ctypes.POINTER(ctypes.c_int64)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    rc = lib.iotml_msgset_decode(
        ctypes.c_char_p(buf), len(buf), max_n,
        offs.ctypes.data_as(i64p), ts.ctypes.data_as(i64p),
        koff.ctypes.data_as(i64p), knull.ctypes.data_as(u8p),
        ctypes.cast(keys, u8p), len(buf),
        voff.ctypes.data_as(i64p), vnull.ctypes.data_as(u8p),
        ctypes.cast(values, u8p), len(buf))
    if rc < 0:
        # CRC/framing errors fall back so the Python decoder raises its
        # exact error text (the wire contract tests pin it)
        return _decode_message_set_py(buf)
    kraw = keys.raw
    vraw = values.raw
    return [(int(offs[i]), None if knull[i] else kraw[koff[i]:koff[i + 1]],
             None if vnull[i] else vraw[voff[i]:voff[i + 1]], int(ts[i]))
            for i in range(rc)]


def _req_header(api_key: int, api_version: int, corr: int,
                client_id: str) -> bytes:
    w = _Writer()
    w.i16(api_key).i16(api_version).i32(corr).string(client_id)
    return bytes(w.buf)


# ------------------------------------------------------------------ client
class ProducePartitionMixin:
    """Client-side keyed partitioner + produce conveniences shared by the
    Python and native (C++) wire clients.  One implementation so keyed
    records land on the same partition no matter which client produced them
    (per-key ordering is a cross-client invariant).  Subclasses provide
    `_partition_count_or_default(topic)` and `produce_many`, plus the
    `_rr` round-robin state dict.
    """

    def _partition_for(self, topic: str, key: Optional[bytes]) -> int:
        n = self._partition_count_or_default(topic)
        if key is None:
            self._rr[topic] = (self._rr.get(topic, -1) + 1) % n
            return self._rr[topic]
        return zlib.crc32(key) % n

    def produce(self, topic: str, value: bytes, key: Optional[bytes] = None,
                partition: Optional[int] = None, timestamp_ms: int = 0,
                headers: Optional[tuple] = None) -> int:
        # headers accepted for Broker duck-type parity and dropped: the
        # wire protocol (MessageSet v1) has no header slot
        return self.produce_many(topic, [(key, value, timestamp_ms)],
                                 partition=partition)

    def produce_batch(self, topic: str, values, key=None, partition=None) -> int:
        return self.produce_many(topic, [(key, v, 0) for v in values],
                                 partition=partition)


class KafkaWireBroker(ProducePartitionMixin):
    """Kafka-protocol client with the `Broker` emulator's duck-type.

    One socket, one lock: requests are serialized (the reference's data
    path is single-consumer per process too).  Metadata is cached for the
    client-side partitioner and refreshed on topic misses.
    """

    def __init__(self, servers: str, client_id: str = "iotml",
                 sasl_username: Optional[str] = None,
                 sasl_password: Optional[str] = None,
                 timeout_s: float = 30.0, topology=None,
                 epoch: Optional[int] = None,
                 acks: Optional[int] = None,
                 replica_id: int = -1):
        self.client_id = client_id
        #: default required_acks for produce paths (None = -1, the
        #: classic client default: quorum where the topic is
        #: replicated, leader-ack otherwise — Kafka RF-1 semantics).
        #: Per-call `acks=` overrides.
        self._acks = -1 if acks is None else int(acks)
        #: >= 0 marks this client as replica `replica_id`'s mirror leg:
        #: FETCH/RAW_FETCH carry the id, the leader tracks the fetch
        #: position in its ISR, and the quorum read barrier is bypassed
        #: (a follower exists to read the un-replicated tail).
        self._replica_id = int(replica_id)
        self._lock = threading.Lock()
        self._corr = 0
        # bootstrap list: try each server in order (a standard client's
        # bootstrap.servers semantics), keep the first that answers.  The
        # full list is retained for FAILOVER: a request that hits a dead
        # socket reconnects to the next reachable server and retries once
        # (see _request) — how a consumer survives a leader death when a
        # FollowerReplica serves the same topics on the second address.
        from ..utils.net import parse_bootstrap

        self._servers = list(parse_bootstrap(servers))
        self._servers_repr = servers
        self._timeout_s = timeout_s
        self._sasl_creds = ((sasl_username, sasl_password or "")
                            if sasl_username is not None else None)
        # supervised topology (iotml.supervise.Topology duck-type): when
        # given, every (re)connect re-resolves the ACTIVE leader + epoch
        # from it instead of walking the static bootstrap order, and the
        # epoch is stamped into each request's client id so the server
        # can fence a stale party (see FencedEpochError).
        self._topology = topology
        self._epoch = epoch
        self._sock = None
        self._connect_any()  # resolves topology first (its only caller)
        self._meta: Dict[str, int] = {}  # topic → partition count
        self._rr: Dict[str, int] = {}
        # high-water marks stashed off every classic fetch response —
        # the consumer-lag source that costs zero extra round trips
        # (ISSUE 13 satellite; see last_hwm)
        self._hwm: Dict[tuple, int] = {}

    # ------------------------------------------------------ epoch fencing
    @property
    def epoch(self) -> Optional[int]:
        return self._epoch

    def set_epoch(self, epoch: Optional[int]) -> None:
        """Stamp `epoch` into subsequent request headers (None = legacy
        unfenced client)."""
        self._epoch = epoch

    def set_replica_id(self, replica_id: int) -> None:
        """Mark this client as a replica's mirror leg: subsequent
        FETCH/RAW_FETCH requests carry `replica_id` so the leader's ISR
        tracker observes the fetch positions (and serves the tail)."""
        self._replica_id = int(replica_id)

    def _refresh_topology(self) -> None:
        """Re-resolve (servers, epoch) from the published topology.
        Caller must hold the lock (or be __init__, pre-threading)."""
        if self._topology is None:
            return
        from ..utils.net import parse_bootstrap

        servers, epoch = self._topology.resolve()
        self._servers = list(parse_bootstrap(",".join(servers)))
        self._servers_repr = ",".join(servers)
        self._epoch = epoch

    def _fenced(self, what: str) -> "FencedEpochError":
        """Build the fence error AFTER re-resolving topology and
        reconnecting, so the caller's retry (its redelivery loop) talks
        to the real leader at the current epoch instead of failing
        identically forever."""
        stale = self._epoch
        # lint-ok: R4 single-socket client by design (same contract as
        # _request): reconnect I/O is bounded by timeout_s and requests
        # are serialized over one connection anyway.
        with self._lock:
            try:
                self._connect_any()  # re-resolves topology first
            except OSError:
                # nothing reachable right now: the next request's
                # reconnect path retries; the fence error still stands
                pass
        return FencedEpochError(
            f"{what} fenced: leadership epoch mismatch (client was at "
            f"epoch {stale}, now {self._epoch}); topology re-resolved — "
            f"the caller owns redelivery")

    # ---------------------------------------------------------- transport
    def _connect_any(self) -> None:
        """Connect to the first reachable bootstrap server (+ SASL).
        Caller must hold the lock (or be __init__, pre-threading).

        An explicit SASL REJECTION raises immediately (the credentials
        are wrong everywhere — retrying them fleet-wide would spam auth
        failures); a server dying mid-handshake is connectivity and
        falls through to the next server.  Either way the dead/rejected
        socket is closed, never leaked."""
        # a supervised client re-reads the published topology on every
        # reconnect: after a promotion the first server tried is the new
        # leader (and the stamp below carries the new epoch), not
        # whatever the static bootstrap order said at construction
        self._refresh_topology()
        last_err: Optional[Exception] = None
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None
        for host, port in self._servers:
            try:
                sock = socket.create_connection((host, port),
                                                timeout=self._timeout_s)
            except OSError as e:
                last_err = e
                continue
            try:
                self._sock = sock
                if self._sasl_creds is not None:
                    self._sasl_plain_raw(*self._sasl_creds)
                return
            except SaslAuthError:
                self._sock = None
                try:
                    sock.close()
                except OSError:
                    pass
                raise
            except OSError as e:
                last_err = e
                self._sock = None
                try:
                    sock.close()
                except OSError:
                    pass
        raise last_err or \
            OSError(f"no reachable broker in {self._servers_repr!r}")

    def _recv_exact(self, n: int) -> bytes:
        return recv_exact(self._sock, n, "broker closed connection")

    def _send_frame(self, payload: bytes) -> None:
        act = chaos.point("kafka_wire.send")
        data = struct.pack(">i", len(payload)) + payload
        if act is not None and act.kind == "short_write":
            # a torn frame on the wire: the server sees a truncated
            # request and drops the connection, this client fails over
            self._sock.sendall(data[: len(data) // 2])
            raise OSError("chaos[kafka_wire.send]: short write")
        self._sock.sendall(data)

    def _recv_frame(self) -> bytes:
        chaos.point("kafka_wire.recv")
        (size,) = struct.unpack(">i", self._recv_exact(4))
        return self._recv_exact(size)

    def _exchange(self, api_key: int, api_version: int,
                  body: bytes) -> tuple:
        """One request/response on the current socket; caller holds the
        lock.  Returns (corr, resp bytes)."""
        self._corr += 1
        corr = self._corr
        self._send_frame(_req_header(
            api_key, api_version, corr,
            tag_client_id(self.client_id, self._epoch)) + body)
        return corr, self._recv_frame()

    def _request(self, api_key: int, api_version: int, body: bytes) -> _Reader:
        # lint-ok: R4 single-socket client by design: requests are
        # serialized over one connection and every socket op is bounded by
        # timeout_s, so a stalled broker parks callers for at most that.
        with self._lock:
            try:
                if self._sock is None:
                    # a previous reconnect found no reachable server and
                    # left no socket; try again now (the outage may be a
                    # restart in flight) instead of dying on a dead handle
                    self._connect_any()
                corr, resp = self._exchange(api_key, api_version, body)
            except OSError as e:
                # dead server: fail over across the bootstrap list, then
                # retry ONCE — but only IDEMPOTENT_APIS.  The dead server
                # may have applied the request before dying, so retrying
                # produce/commit would double-apply records/offsets; those
                # surface ConnectionError (on a now-reconnected client) and
                # the caller opts into redelivery explicitly.
                self._connect_any()
                if api_key not in IDEMPOTENT_APIS:
                    raise ConnectionError(
                        f"connection lost during non-idempotent request "
                        f"(api_key={api_key}); not auto-retried — the dead "
                        f"server may have applied it.  Reconnected; the "
                        f"caller decides whether to redeliver.") from e
                corr, resp = self._exchange(api_key, api_version, body)
        r = _Reader(resp)
        got = r.i32()
        if got != corr:
            raise ConnectionError(f"correlation id mismatch: {got} != {corr}")
        return r

    def _sasl_plain_raw(self, username: str, password: str) -> None:
        """SASL PLAIN on the current socket, no locking (used by
        _connect_any, which runs under the lock or from __init__)."""
        w = _Writer()
        w.string("PLAIN")
        corr, resp = self._exchange(SASL_HANDSHAKE, 0, bytes(w.buf))
        r = _Reader(resp)
        if r.i32() != corr:
            raise ConnectionError("correlation id mismatch in handshake")
        err = r.i16()
        mechanisms = r.array(lambda rd: rd.string())
        if err == ERR_SASL_AUTH_FAILED:
            # the server rejected the MECHANISM (not the credentials —
            # those are checked on the raw token exchange below)
            raise SaslAuthError(
                f"server rejected SASL mechanism PLAIN; offers "
                f"{mechanisms}")
        if err != ERR_NONE:
            raise SaslAuthError(
                f"SASL handshake failed ({err}); server offers {mechanisms}")
        token = b"\x00" + username.encode() + b"\x00" + password.encode()
        self._send_frame(token)   # raw token frame (pre-KIP-152)
        if self._recv_frame() != b"":
            raise SaslAuthError("SASL PLAIN authentication failed")

    # ------------------------------------------------------------ metadata
    def _metadata(self, topics: Optional[List[str]] = None) -> dict:
        w = _Writer()
        if topics is None:
            w.i32(-1)
        else:
            w.array(topics, lambda wr, t: wr.string(t))
        # lint-ok: P3 metadata reports existence per topic: unknown
        # topics carry ERR_UNKNOWN_TOPIC in their row and are simply
        # left out of the leaders map — absence IS the answer, not an
        # error to raise
        r = self._request(METADATA, 1, bytes(w.buf))

        def broker(rd):
            return (rd.i32(), rd.string(), rd.i32(), rd.string())

        def partition(rd):
            err, pid, leader = rd.i16(), rd.i32(), rd.i32()
            rd.array(lambda x: x.i32())  # replicas
            rd.array(lambda x: x.i32())  # isr
            return (err, pid, leader)

        def topic(rd):
            err = rd.i16()
            name = rd.string()
            rd.i8()  # is_internal
            parts = rd.array(partition)
            return (err, name, parts)

        brokers = r.array(broker)
        r.i32()  # controller id
        tops = r.array(topic)
        meta = {"brokers": brokers, "topics": {}, "leaders": {}}
        for err, name, parts in tops:
            if err == ERR_NONE:
                meta["topics"][name] = len(parts)
                self._meta[name] = len(parts)
                for perr, pid, leader in parts:
                    if perr == ERR_NONE:
                        # per-partition leader NODE ID (cluster servers
                        # publish the real owner; classic servers say 0)
                        meta["leaders"][(name, pid)] = leader
        return meta

    def cluster_metadata(self, topics: Optional[List[str]] = None) -> dict:
        """Raw metadata: {"brokers": [(node, host, port, rack)],
        "topics": {name: n_partitions},
        "leaders": {(topic, partition): node}} — what a routing client
        (iotml.cluster.ClusterClient) caches and refreshes on
        NOT_LEADER_FOR_PARTITION."""
        return self._metadata(topics)

    def find_coordinator(self, group: str) -> Tuple[int, str, int]:
        """(node_id, host, port) of the group coordinator — in a cluster
        the one broker holding membership + offset state for `group`."""
        w = _Writer()
        w.string(group)
        r = self._request(FIND_COORDINATOR, 0, bytes(w.buf))
        err = r.i16()
        node, host, port = r.i32(), r.string(), r.i32()
        if err != ERR_NONE:
            raise RuntimeError(f"find_coordinator({group}): error {err}")
        return node, host or "", port

    def topics(self) -> List[str]:
        return sorted(self._metadata()["topics"])

    def topic(self, name: str) -> TopicSpec:
        n = self._meta.get(name) or self._metadata([name])["topics"].get(name)
        if n is None:
            raise KeyError(name)
        return TopicSpec(name, n)

    def create_topic(self, name: str, partitions: int = 1,
                     retention_messages: Optional[int] = None,
                     retention_bytes: Optional[int] = None,
                     retention_ms: Optional[int] = None,
                     cleanup_policy: Optional[str] = None) -> TopicSpec:
        w = _Writer()
        # retention and cleanup.policy ride CreateTopics v0's standard
        # config entries — retention.bytes / retention.ms /
        # cleanup.policy are Kafka's own names; retention.messages is
        # the emulator-family extension
        cfgs = [(k, str(v)) for k, v in
                (("retention.messages", retention_messages),
                 ("retention.bytes", retention_bytes),
                 ("retention.ms", retention_ms),
                 ("cleanup.policy", cleanup_policy)) if v is not None]

        def one(wr, _):
            wr.string(name).i32(partitions).i16(1)
            wr.i32(0)  # replica assignment: none
            wr.array(cfgs, lambda cw, kv: cw.string(kv[0]).string(kv[1]))

        w.array([None], one)
        w.i32(10_000)  # timeout ms
        # retry-ok: not auto-retried; a lost CreateTopics surfaces
        # ConnectionError and re-issuing is safe (TOPIC_EXISTS handled below)
        r = self._request(CREATE_TOPICS, 0, bytes(w.buf))
        errs = r.array(lambda rd: (rd.string(), rd.i16()))
        existed = False
        for _, err in errs:
            if err == ERR_TOPIC_EXISTS:
                existed = True
            elif err == ERR_INVALID_CONFIG:
                # mirrors the in-process broker's validation contract
                raise ValueError(
                    f"create_topic({name}): broker rejected the config "
                    f"(negative retention?)")
            elif err != ERR_NONE:
                raise RuntimeError(f"create_topic({name}) failed: error {err}")
        if existed:
            # real partition count may differ from the request; trust metadata
            self._meta.pop(name, None)
            return self.topic(name)
        self._meta[name] = partitions
        return TopicSpec(name, partitions)

    # ------------------------------------------------------------- produce
    def _partition_count_or_default(self, topic: str) -> int:
        n = self._meta.get(topic)
        if n is None:
            n = self._metadata([topic])["topics"].get(topic, 1)
        return n

    def produce_many(self, topic: str, entries, partition=None,
                     acks: Optional[int] = None,
                     timeout_ms: int = 10_000) -> int:
        """entries: [(key, value, timestamp_ms[, headers])] → offset of the
        last one.  Record headers (the trace-context carrier on the
        in-process broker) are DROPPED here: MessageSet v1 has no header
        slot, so traces end at a wire-broker boundary by design.

        ``acks`` (default: the client's configured default, -1): -1
        acks at the quorum high-water mark on replicated topics
        (leader-only on unreplicated ones — Kafka RF-1), 1 acks at the
        leader append, 0 is fire-and-forget (the response is immediate
        and carries no delivery guarantee).  A quorum that cannot form
        raises NotEnoughReplicasError (nothing appended); a quorum that
        does not catch up within ``timeout_ms`` raises
        ProduceTimedOutError (appended, unacked — redeliver)."""
        by_part: Dict[int, list] = {}
        for key, value, ts, *_hdrs in entries:
            p = self._partition_for(topic, key) if partition is None else partition
            by_part.setdefault(p, []).append((0, key, value, ts))
        last = -1
        w = _Writer()
        w.i16(self._acks if acks is None else int(acks))
        w.i32(int(timeout_ms))

        def part_entry(wr, item):
            p, ents = item
            wr.i32(p).bytes_(encode_message_set(ents))

        def topic_entry(wr, _):
            wr.string(topic).array(sorted(by_part.items()), part_entry)

        w.array([None], topic_entry)
        # retry-ok: produce is NOT auto-retried (double-append risk);
        # ConnectionError reaches the producer, which owns redelivery
        r = self._request(PRODUCE, 2, bytes(w.buf))

        def part_resp(rd):
            p, err, base = rd.i32(), rd.i16(), rd.i64()
            rd.i64()  # log append time
            return (p, err, base)

        tops = r.array(lambda rd: (rd.string(), rd.array(part_resp)))
        for _, parts in tops:
            for p, err, base in parts:
                if err == ERR_FENCED_LEADER_EPOCH:
                    # stale party detected (this client OR a resurrected
                    # old leader): nothing was appended — re-resolve and
                    # hand redelivery back to the caller
                    raise self._fenced(f"produce to {topic}:{p}")
                if err == ERR_NOT_LEADER_FOR_PARTITION:
                    # sharded cluster: this broker no longer owns the
                    # partition — nothing appended THERE; the routing
                    # client refreshes its map and redelivers
                    raise NotLeaderForPartitionError(topic, p)
                if err == ERR_NOT_ENOUGH_REPLICAS:
                    raise NotEnoughReplicasError(
                        f"produce to {topic}:{p} refused: ISR below "
                        f"min_isr (or no ISR configured for acks=all); "
                        f"nothing appended — redeliver when the quorum "
                        f"re-forms")
                if err == ERR_REQUEST_TIMED_OUT:
                    raise ProduceTimedOutError(
                        f"produce to {topic}:{p} appended but the "
                        f"quorum HWM did not reach it in time; unacked "
                        f"— the caller redelivers (at-least-once)")
                if err == ERR_INVALID_REQUIRED_ACKS:
                    raise ValueError(
                        f"produce to {topic}:{p} refused: required_acks "
                        f"must be -1, 0 or 1; nothing appended")
                if err == ERR_UNKNOWN_TOPIC:
                    raise KeyError(topic)
                if err == ERR_TOPIC_AUTHORIZATION_FAILED:
                    raise PermissionError(
                        f"produce to {topic}:{p} refused: the topic is "
                        f"restricted to its owning engine "
                        f"(Broker.restrict_topic); nothing appended")
                if err != ERR_NONE:
                    raise RuntimeError(f"produce to {topic}:{p} failed: {err}")
                last = max(last, base + len(by_part[p]) - 1)
        return last

    def produce_raw(self, topic: str, partition: int,
                    frames: bytes, acks: Optional[int] = None,
                    timeout_ms: int = 10_000) -> int:
        """RAW_PRODUCE over the wire: ship a pre-framed batch the broker
        appends segment-verbatim (CRC-validated whole, offsets stamped
        server-side).  Returns the batch's base offset.

        Raises NotImplementedError against a server without the
        extension (producers pin back to classic produce — the
        UNSUPPORTED_VERSION fallback), CorruptMessageError when the
        server rejected the whole batch (nothing appended; re-frame and
        redeliver), NotLeaderForPartitionError on a sharded bounce, and
        ConnectionError on transport death — NOT auto-retried, the
        caller owns redelivery exactly like produce."""
        w = _Writer()
        w.string(topic).i32(partition).bytes_(frames)
        # trailing-optional required_acks + timeout (ISSUE 14): the
        # RAW_PRODUCE mirror of classic produce's acks field.  Old
        # servers never read past the frames blob; absent fields mean
        # the client default (-1, like classic produce).
        w.i16(self._acks if acks is None else int(acks))
        w.i32(int(timeout_ms))
        # retry-ok: RAW_PRODUCE is NOT auto-retried (double-append risk,
        # same stance as produce); ConnectionError reaches the producer
        r = self._request(RAW_PRODUCE, 0, bytes(w.buf))
        err = r.i16()
        if err == ERR_UNSUPPORTED_VERSION:
            raise NotImplementedError(
                "server lacks the RAW_PRODUCE extension")
        base = r.i64()
        r.i32()  # count
        if err == ERR_CORRUPT_MESSAGE:
            raise CorruptMessageError(topic, partition, int(base))
        if err == ERR_FENCED_LEADER_EPOCH:
            raise self._fenced(f"raw produce to {topic}:{partition}")
        if err == ERR_NOT_LEADER_FOR_PARTITION:
            raise NotLeaderForPartitionError(topic, partition)
        if err == ERR_NOT_ENOUGH_REPLICAS:
            raise NotEnoughReplicasError(
                f"raw produce to {topic}:{partition} refused: ISR "
                f"below min_isr; nothing appended — redeliver when the "
                f"quorum re-forms")
        if err == ERR_REQUEST_TIMED_OUT:
            raise ProduceTimedOutError(
                f"raw produce to {topic}:{partition} appended but "
                f"unacked within the timeout — the caller redelivers")
        if err == ERR_INVALID_REQUIRED_ACKS:
            raise ValueError(
                f"raw produce to {topic}:{partition} refused: "
                f"required_acks must be -1, 0 or 1; nothing appended")
        if err == ERR_UNKNOWN_TOPIC:
            raise KeyError(topic)
        if err == ERR_TOPIC_AUTHORIZATION_FAILED:
            raise PermissionError(
                f"raw produce to {topic}:{partition} refused: the "
                f"topic is restricted to its owning engine "
                f"(Broker.restrict_topic); nothing appended")
        if err != ERR_NONE:
            raise RuntimeError(
                f"raw produce to {topic}:{partition} failed: {err}")
        return base

    # --------------------------------------------------------------- fetch
    def fetch(self, topic: str, partition: int, offset: int,
              max_messages: int = 1024) -> List[Message]:
        w = _Writer()
        # replica id (-1 = consumer; >= 0 = a follower's mirror fetch,
        # observed by the leader's ISR tracker), max_wait 0ms, min_bytes 1
        w.i32(self._replica_id).i32(0).i32(1)

        def part(wr, _):
            wr.i32(partition).i64(offset).i32(4 << 20)

        w.array([None], lambda wr, _: (wr.string(topic),
                                       wr.array([None], part)))
        r = self._request(FETCH, 2, bytes(w.buf))
        r.i32()  # throttle

        out: List[Message] = []
        tops = r.array(lambda rd: (rd.string(), rd.array(
            lambda p: (p.i32(), p.i16(), p.i64(), p.bytes_()))))
        for tname, parts in tops:
            for pid, err, hwm, record_set in parts:
                if err == ERR_OFFSET_OUT_OF_RANGE:
                    # the server's log head was trimmed past this offset
                    # (retention/realignment).  Surfaced, not swallowed:
                    # the old `continue` made trimmed history look like
                    # an empty poll.  `hwm` rides the response as the
                    # earliest retained offset for this error.
                    raise OffsetOutOfRangeError(tname or topic, pid,
                                                offset, max(hwm, 0))
                if err == ERR_UNKNOWN_TOPIC:
                    raise KeyError(topic)
                if err == ERR_NOT_LEADER_FOR_PARTITION:
                    raise NotLeaderForPartitionError(tname or topic, pid)
                if err != ERR_NONE:
                    raise RuntimeError(f"fetch {topic}:{pid} failed: {err}")
                # the hwm already rides every fetch response: cache it so
                # consumer-lag needs no extra round trip (last_hwm)
                self._hwm[(tname or topic, pid)] = int(hwm)
                for off, key, value, ts in decode_message_set(record_set or b""):
                    if off >= offset and len(out) < max_messages:
                        # a null VALUE is a tombstone (compacted-topic
                        # delete marker): surfaced as None, not coerced
                        # to b"" — consumers of changelogs must be able
                        # to tell "deleted" from "empty"
                        out.append(Message(tname, pid, off, value,
                                           key, ts))
        return out

    def last_hwm(self, topic: str, partition: int) -> Optional[int]:
        """The newest high-water mark seen for (topic, partition) in a
        fetch response, None before the first classic fetch — the
        zero-round-trip consumer-lag source (StreamConsumer.record_lag
        falls back to end_offset when absent)."""
        return self._hwm.get((topic, partition))

    def fetch_raw(self, topic: str, partition: int, offset: int,
                  max_bytes: int = 1 << 20):
        """Raw-batch fetch over the wire: the broker's store-format
        frame bytes, verbatim, as one `RawFrameBatch` — the consumer's
        columnar decoder does ALL record work on one buffer (zero
        per-record objects client-side, zero MessageSet re-encode
        server-side for durable brokers).  Returns None at/after the
        log end or against a server without the RAW_FETCH extension
        (callers fall back to classic fetch)."""
        from ..ops.framing import RawFrameBatch

        w = _Writer()
        w.string(topic).i32(partition).i64(offset).i32(max_bytes)
        # trailing-optional replica id (ISSUE 14): a follower's raw
        # mirror fetch identifies itself so the leader's ISR tracker
        # observes the position and serves past the quorum HWM.  Old
        # servers never read past max_bytes.
        w.i32(self._replica_id)
        r = self._request(RAW_FETCH, 0, bytes(w.buf))
        err = r.i16()
        if err == ERR_UNSUPPORTED_VERSION:
            # pre-extension (or relay) server: the response carries no
            # further fields.  Raised — not None — so consumers DISABLE
            # the columnar path instead of mistaking it for log end.
            raise NotImplementedError(
                "server lacks the RAW_FETCH extension")
        aux = r.i64()  # start offset; earliest-retained for error 1
        blob = r.bytes_()
        # trailing-optional hwm (ISSUE 13 satellite): newer servers
        # append the partition high-water mark after the blob so the
        # COLUMNAR path feeds consumer-lag with zero extra round trips,
        # exactly like classic fetch.  Optional both directions: an
        # older server simply ends the response here, an older client
        # never reads past the blob.
        if err == ERR_NONE and r.pos + 8 <= len(r.buf):
            hwm = r.i64()
            if hwm >= 0:  # -1 = the server could not answer cheaply
                self._hwm[(topic, partition)] = hwm
        if not blob and err == ERR_NONE:
            return None  # log end
        if err == ERR_OFFSET_OUT_OF_RANGE:
            raise OffsetOutOfRangeError(topic, partition, offset,
                                        max(aux, 0))
        if err == ERR_UNKNOWN_TOPIC:
            raise KeyError(topic)
        if err == ERR_NOT_LEADER_FOR_PARTITION:
            raise NotLeaderForPartitionError(topic, partition)
        if err != ERR_NONE:
            raise RuntimeError(f"raw fetch {topic}:{partition}: {err}")
        if blob is None:
            return None
        return RawFrameBatch(topic, partition, int(aux), blob)

    # ------------------------------------------------------------- offsets
    def _list_offset(self, topic: str, partition: int, timestamp: int) -> int:
        w = _Writer()
        w.i32(-1)

        def part(wr, _):
            wr.i32(partition).i64(timestamp)

        w.array([None], lambda wr, _: (wr.string(topic),
                                       wr.array([None], part)))
        r = self._request(LIST_OFFSETS, 1, bytes(w.buf))
        tops = r.array(lambda rd: (rd.string(), rd.array(
            lambda p: (p.i32(), p.i16(), p.i64(), p.i64()))))
        for _, parts in tops:
            for pid, err, ts, off in parts:
                if err == ERR_NOT_LEADER_FOR_PARTITION:
                    raise NotLeaderForPartitionError(topic, pid)
                if err == ERR_UNKNOWN_TOPIC:
                    raise KeyError(topic)
                if err != ERR_NONE:
                    raise RuntimeError(f"list_offsets {topic}:{pid}: {err}")
                return off
        raise RuntimeError("empty ListOffsets response")

    def end_offset(self, topic: str, partition: int = 0) -> int:
        return self._list_offset(topic, partition, -1)

    def begin_offset(self, topic: str, partition: int = 0) -> int:
        return self._list_offset(topic, partition, -2)

    def offset_for_timestamp(self, topic: str, partition: int,
                             timestamp_ms: int) -> int:
        """Earliest offset with record timestamp >= `timestamp_ms` —
        ListOffsets by timestamp, the Broker replay-API duck-type."""
        return self._list_offset(topic, partition, max(int(timestamp_ms), 0))

    # ------------------------------------------------- consumer-group API
    def commit(self, group: str, topic: str, partition: int, next_offset: int):
        """Simple-consumer commit: the generation=-1, unfenced special case
        of `commit_fenced`."""
        if not self.commit_fenced(group, -1, "",
                                  [(topic, partition, next_offset)]):
            raise RuntimeError(f"offset commit {topic}:{partition} fenced")

    def committed(self, group: str, topic: str, partition: int) -> Optional[int]:
        w = _Writer()
        w.string(group)

        def part(wr, _):
            wr.i32(partition)

        w.array([None], lambda wr, _: (wr.string(topic),
                                       wr.array([None], part)))
        r = self._request(OFFSET_FETCH, 1, bytes(w.buf))
        tops = r.array(lambda rd: (rd.string(), rd.array(
            lambda p: (p.i32(), p.i64(), p.string(), p.i16()))))
        for _, parts in tops:
            for pid, off, _meta, err in parts:
                if err == ERR_NOT_COORDINATOR:
                    raise CoordinatorMovedError(
                        f"offset fetch {topic}:{pid}: broker is not the "
                        f"coordinator")
                if err != ERR_NONE:
                    raise RuntimeError(f"offset fetch {topic}:{pid}: {err}")
                return None if off < 0 else off
        return None

    def committed_many(self, group: str, pairs
                       ) -> Dict[Tuple[str, int], int]:
        """Committed offsets for [(topic, partition), ...] in ONE
        OffsetFetch round-trip (the per-partition committed() loop cost a
        wire request each — at replica-mirror rates that was hundreds of
        idle requests/s against the leader).  Pairs with no committed
        offset are omitted from the result."""
        by_topic: Dict[str, List[int]] = {}
        for t, p in pairs:
            by_topic.setdefault(t, []).append(p)
        w = _Writer()
        w.string(group)
        w.array(sorted(by_topic.items()), lambda wr, tp: (
            wr.string(tp[0]),
            wr.array(sorted(tp[1]), lambda pw, p: pw.i32(p))))
        r = self._request(OFFSET_FETCH, 1, bytes(w.buf))
        tops = r.array(lambda rd: (rd.string(), rd.array(
            lambda p: (p.i32(), p.i64(), p.string(), p.i16()))))
        out: Dict[Tuple[str, int], int] = {}
        for tname, parts in tops:
            for pid, off, _meta, err in parts:
                if err == ERR_NOT_COORDINATOR:
                    raise CoordinatorMovedError(
                        f"offset fetch {tname}:{pid}: broker is not the "
                        f"coordinator")
                if err != ERR_NONE:
                    raise RuntimeError(f"offset fetch {tname}:{pid}: {err}")
                if off >= 0:
                    out[(tname, pid)] = off
        return out

    def commit_many(self, group: str, topic: str, entries) -> None:
        """Commit [(partition, next_offset), ...] of one topic in ONE
        OffsetCommit request (StreamConsumer.commit's fast path) —
        delegates to the fenced path with the simple-consumer generation.
        Mirrors commit(): raises if the server fences the request, so a
        future server-side semantics change cannot silently drop offsets
        (today the server never fences generation -1)."""
        if not self.commit_fenced(group, -1, "",
                                  [(topic, p, off) for p, off in entries]):
            raise RuntimeError(f"batched offset commit {topic} fenced")

    def commit_fenced(self, group: str, generation: int, member_id: str,
                      positions) -> bool:
        """Generation-fenced OffsetCommit (v2 carries generation+member).

        Offset commits are per-partition in Kafka, so three outcomes:
        every partition rejected with ILLEGAL_GENERATION → the member is
        fenced, nothing written, returns False; every partition accepted →
        True; a *mix* → the accepted partitions ARE committed but the rest
        were refused (the member named partitions outside its assignment) —
        that is a caller bug, surfaced as RuntimeError naming them."""
        by_topic: dict = {}
        for t, p, off in positions:
            by_topic.setdefault(t, []).append((p, off))
        w = _Writer()
        w.string(group).i32(generation).string(member_id).i64(-1)
        w.array(sorted(by_topic.items()), lambda wr, tp: (
            wr.string(tp[0]),
            wr.array(tp[1], lambda pw, p: pw.i32(p[0]).i64(p[1])
                     .string(None))))
        # retry-ok: offset commits are NOT auto-retried (a stale commit
        # replayed after a rebalance could fence-bypass); callers re-commit
        # from their own cursors on ConnectionError
        r = self._request(OFFSET_COMMIT, 2, bytes(w.buf))
        tops = r.array(lambda rd: (rd.string(), rd.array(
            lambda p: (p.i32(), p.i16()))))
        results = [(t, pid, err) for t, parts in tops for pid, err in parts]
        errs = {err for _, _, err in results}
        if errs == {ERR_NONE}:
            return True
        if errs == {ERR_ILLEGAL_GENERATION}:
            return False  # fenced: nothing was written
        if errs == {ERR_NOT_COORDINATOR}:
            # the group's coordinator moved (cluster failover): nothing
            # written here — re-find the coordinator and re-commit
            raise CoordinatorMovedError(
                f"offset commit {sorted(by_topic)}: broker is not the "
                f"coordinator")
        if errs == {ERR_FENCED_LEADER_EPOCH}:
            # leadership-epoch fence (distinct from the generation fence
            # above: this is the whole SERVER relationship being stale,
            # not one group member) — nothing written, caller re-commits
            # from its own cursors against the re-resolved leader
            raise self._fenced(f"offset commit {sorted(by_topic)}")
        bad = [(t, pid) for t, pid, err in results if err != ERR_NONE]
        raise RuntimeError(
            f"partial offset commit: partitions {bad} refused (outside this "
            f"member's assignment?); the rest were committed")

    # ------------------------------------------- group membership (wire)
    def join_group(self, group: str, topics, member_id: str = "",
                   session_timeout_ms: int = 10_000):
        """JoinGroup v0 with the standard consumer subscription metadata.
        Returns (generation, member_id, leader_id, members) where `members`
        is [(member_id, [topics])] — non-empty only for the elected leader
        (real brokers hand the leader everyone's subscriptions so it can
        compute the assignment client-side)."""
        meta = _Writer()
        meta.i16(0)
        meta.array(list(topics), lambda wr, t: wr.string(t))
        meta.bytes_(b"")
        w = _Writer()
        w.string(group).i32(session_timeout_ms).string(member_id)
        w.string("consumer")
        w.array([("range", bytes(meta.buf))],
                lambda wr, p: (wr.string(p[0]), wr.bytes_(p[1])))
        # retry-ok: join mutates membership (may create a member id); the
        # coordinator adapter's join loop retries with its member id, so a
        # lost response never leaks a zombie member past session timeout
        r = self._request(JOIN_GROUP, 0, bytes(w.buf))
        err = r.i16()
        if err == ERR_NOT_COORDINATOR:
            raise CoordinatorMovedError(
                f"join group {group}: broker is not the coordinator")
        if err != ERR_NONE:
            raise RuntimeError(f"join group {group}: error {err}")
        generation = r.i32()
        r.string()  # protocol
        leader = r.string()
        mid = r.string()
        members = []
        for other_id, blob in r.array(lambda rd: (rd.string(), rd.bytes_())):
            sub = []
            if blob:
                mr = _Reader(blob)
                try:
                    mr.i16()
                    sub = mr.array(lambda rd: rd.string())
                except struct.error:
                    sub = []
            members.append((other_id, sub))
        return generation, mid, leader, members

    def sync_group(self, group: str, generation: int, member_id: str,
                   assignments: Optional[dict] = None):
        """SyncGroup v0 → [(topic, partition), ...] assignment.

        `assignments` (leader only): {member_id: [(topic, [partitions])]}
        serialized in the standard ConsumerProtocolAssignment format — real
        brokers store-and-forward it to each member (our server computes
        assignment itself and ignores it, same response either way)."""
        w = _Writer()
        w.string(group).i32(generation).string(member_id)

        def one(wr, item):
            other_id, tps = item
            aw = _Writer()
            aw.i16(0)
            aw.array(sorted(tps), lambda xw, tp: (
                xw.string(tp[0]),
                aw_array_parts(xw, tp[1])))
            aw.bytes_(b"")
            wr.string(other_id).bytes_(bytes(aw.buf))

        def aw_array_parts(xw, parts):
            xw.array(sorted(parts), lambda pw, p: pw.i32(p))

        w.array(sorted((assignments or {}).items()), one)
        # retry-ok: sync is generation-fenced server-side; callers rejoin
        # on ConnectionError rather than replay a possibly-stale sync
        r = self._request(SYNC_GROUP, 0, bytes(w.buf))
        err = r.i16()
        blob = r.bytes_() or b""
        if err == ERR_NOT_COORDINATOR:
            raise CoordinatorMovedError(
                f"sync group {group}: broker is not the coordinator")
        if err == ERR_UNKNOWN_MEMBER_ID:
            raise RuntimeError(
                f"sync group {group}: member {member_id!r} unknown to "
                f"the coordinator — rejoin the group")
        if err == ERR_ILLEGAL_GENERATION:
            raise RuntimeError(
                f"sync group {group}: generation {generation} fenced by "
                f"a newer rebalance — rejoin the group")
        if err != ERR_NONE:
            raise RuntimeError(f"sync group {group}: error {err}")
        if not blob:
            return []  # coordinator had nothing for us (yet)
        ar = _Reader(blob)
        ar.i16()  # version
        pairs = []
        for topic, parts in ar.array(lambda rd: (rd.string(),
                                                 rd.array(lambda p: p.i32()))):
            pairs.extend((topic, p) for p in parts)
        return pairs

    def heartbeat_group(self, group: str, generation: int,
                        member_id: str) -> bool:
        w = _Writer()
        w.string(group).i32(generation).string(member_id)
        r = self._request(HEARTBEAT, 0, bytes(w.buf))
        err = r.i16()
        if err == ERR_NOT_COORDINATOR:
            raise CoordinatorMovedError(
                f"heartbeat {group}: broker is not the coordinator")
        if err in (ERR_UNKNOWN_MEMBER_ID, ERR_REBALANCE_IN_PROGRESS):
            # both mean "this generation is over": the caller rejoins —
            # same False signal either way, not worth distinct raises
            return False
        return err == ERR_NONE

    def leave_group(self, group: str, member_id: str) -> None:
        w = _Writer()
        w.string(group).string(member_id)
        # retry-ok: a lost leave is self-healing (session timeout expires
        # the member); not worth retrying against a possibly-new leader
        err = self._request(LEAVE_GROUP, 0, bytes(w.buf)).i16()
        if err == ERR_NOT_COORDINATOR:
            # surfaced typed so the cluster router's _coordinated wrapper
            # re-finds the coordinator instead of silently dropping the
            # leave (the session would only expire by timeout)
            raise CoordinatorMovedError(
                f"leave group {group}: broker is not the coordinator")

    # ----------------------------------------------------- cluster admin
    def cluster_admin(self, command: str, args: Optional[dict] = None,
                      ) -> dict:
        """CLUSTER_ADMIN extension: drive a live controller's elastic
        reassignment (`add-broker` / `drain-broker` / `status`) from
        another process.  Returns the controller's JSON report; raises
        NotImplementedError against a broker with no controller
        attached, RuntimeError with the controller's error text
        otherwise."""
        import json as _json

        w = _Writer()
        w.string(command)
        w.bytes_(_json.dumps(args or {}).encode())
        # retry-ok: admin verbs MUTATE cluster membership (a replayed
        # add-broker boots a second node); a ConnectionError surfaces
        # and the operator re-checks `status` before re-issuing
        r = self._request(CLUSTER_ADMIN, 0, bytes(w.buf))
        err = r.i16()
        if err == ERR_UNSUPPORTED_VERSION:
            raise NotImplementedError(
                "broker has no cluster controller attached "
                "(CLUSTER_ADMIN unsupported)")
        blob = r.bytes_() or b"{}"
        doc = _json.loads(blob.decode() or "{}")
        if err == ERR_UNKNOWN_SERVER:
            # the verb itself raised controller-side; the response body
            # carries the operator-facing error text
            raise RuntimeError(
                f"cluster admin {command!r} failed: "
                f"{doc.get('error', 'unknown server error')}")
        if err != ERR_NONE:
            raise RuntimeError(
                f"cluster admin {command!r} failed: "
                f"{doc.get('error', f'error {err}')}")
        return doc

    # --------------------------------------------------- api versions
    def api_versions(self) -> Dict[int, Tuple[int, int]]:
        """ApiVersions v0 → {api_key: (min_version, max_version)} — the
        server's supported-api table, the wire-level capability probe
        (a client can ask before using the raw columnar apis)."""
        r = self._request(API_VERSIONS, 0, b"")
        err = r.i16()
        ranges = r.array(lambda rd: (rd.i16(), rd.i16(), rd.i16()))
        if err != ERR_NONE:
            raise RuntimeError(f"api_versions failed: error {err}")
        return {k: (lo, hi) for k, lo, hi in ranges}

    def close(self) -> None:
        # _sock is None when the last reconnect attempt found no
        # reachable server (_connect_any clears it before trying) — a
        # replica losing its leader hits exactly this at stop()
        if self._sock is not None:
            self._sock.close()


class RemoteGroupCoordinator:
    """GroupCoordinator-shaped adapter over the wire protocol.

    Gives `stream.group.GroupConsumer` elastic membership against a broker
    in ANOTHER process: join/heartbeat/leave/fenced_commit ride JoinGroup/
    SyncGroup/Heartbeat/LeaveGroup/OffsetCommit requests, with membership
    state living broker-side — the missing piece that makes the reference's
    scalable-Deployment story (SURVEY §2.7) work across processes, exactly
    as Kafka's own coordinator does."""

    def __init__(self, client: "KafkaWireBroker", group_id: str,
                 session_timeout_ms: int = 10_000):
        self.broker = client
        self.group_id = group_id
        self.session_timeout_ms = session_timeout_ms

    def join(self, topics, member_id=None):
        mid = member_id or ""
        last_err = None
        for _ in range(5):  # a peer joining between Join and Sync bumps the
            generation, mid, leader, members = self.broker.join_group(
                self.group_id, topics, mid,  # generation: rejoin
                session_timeout_ms=self.session_timeout_ms)
            assignments = None
            if mid == leader and members:
                # elected leader: compute the range assignment client-side
                # and submit it in SyncGroup — the standard protocol flow a
                # real broker requires (ours computes server-side and gets
                # the same answer)
                assignments = self._leader_assign(members)
            try:
                assignment = self.broker.sync_group(
                    self.group_id, generation, mid, assignments)
                return mid, generation, assignment
            except RuntimeError as e:
                last_err = e
        raise last_err

    def _leader_assign(self, members):
        """RangeAssignor over the members' subscriptions, as
        {member_id: [(topic, [partitions])]}."""
        from .group import range_assign

        topic_parts: dict = {}
        for _mid, topics in members:
            for t in topics:
                if t not in topic_parts:
                    try:
                        topic_parts[t] = self.broker.topic(t).partitions
                    except KeyError:
                        continue  # subscribe-before-create: nothing yet
        flat = range_assign([m for m, _ in members], topic_parts)
        subscribed = {m: set(ts) for m, ts in members}
        out = {}
        for m, tps in flat.items():
            by_topic: dict = {}
            for t, p in tps:
                if t in subscribed.get(m, ()):
                    by_topic.setdefault(t, []).append(p)
            out[m] = sorted(by_topic.items())
        return out

    def heartbeat(self, member_id: str, generation: int) -> bool:
        return self.broker.heartbeat_group(self.group_id, generation,
                                           member_id)

    def fenced_commit(self, member_id: str, generation: int,
                      positions) -> bool:
        if not positions:
            # nothing to write, but the fencing signal must still be real:
            # a heartbeat verifies membership at this generation (the local
            # coordinator checks the same thing under its lock)
            return self.heartbeat(member_id, generation)
        return self.broker.commit_fenced(self.group_id, generation,
                                         member_id, positions)

    def leave(self, member_id: str) -> None:
        self.broker.leave_group(self.group_id, member_id)


# ------------------------------------------------------------------ server
class _KafkaConn(socketserver.BaseRequestHandler):
    """One client connection to the wire server."""

    def setup(self):
        with self.server._conn_lock:      # type: ignore[attr-defined]
            self.server._live_conns.add(self.request)

    def finish(self):
        with self.server._conn_lock:      # type: ignore[attr-defined]
            self.server._live_conns.discard(self.request)

    def _recv_exact(self, n: int) -> bytes:
        return recv_exact(self.request, n)

    def handle(self):
        broker: Broker = self.server.broker  # type: ignore[attr-defined]
        creds = self.server.credentials      # type: ignore[attr-defined]
        authed = creds is None
        sasl_pending = False
        try:
            while True:
                (size,) = struct.unpack(">i", self._recv_exact(4))
                frame = self._recv_exact(size)
                if sasl_pending:
                    # raw PLAIN token: [authzid] \0 user \0 password
                    parts = frame.split(b"\x00")
                    ok = len(parts) == 3 and \
                        (parts[1].decode(), parts[2].decode()) == creds
                    if not ok:
                        return  # auth failure: drop connection
                    authed, sasl_pending = True, False
                    self.request.sendall(struct.pack(">i", 0))
                    continue
                r = _Reader(frame)
                api_key, api_version, corr = r.i16(), r.i16(), r.i32()
                # the client id's trailing @e<N> tag carries the client's
                # leadership epoch (absent for standard/legacy clients)
                _cid, client_epoch = parse_client_epoch(r.string())
                w = _Writer()
                w.i32(corr)
                lo_hi = _SUPPORTED.get(api_key)
                if lo_hi is None or not lo_hi[0] <= api_version <= lo_hi[1]:
                    w.i16(ERR_UNSUPPORTED_VERSION)
                elif api_key == SASL_HANDSHAKE:
                    mech = r.string()
                    if mech == "PLAIN":
                        w.i16(ERR_NONE)
                        sasl_pending = not authed
                    else:
                        w.i16(ERR_SASL_AUTH_FAILED)
                    w.array(["PLAIN"], lambda wr, m: wr.string(m))
                elif not authed:
                    return  # protocol requests before auth: drop
                elif api_key == API_VERSIONS:
                    w.i16(ERR_NONE)
                    w.array(sorted(_SUPPORTED.items()),
                            lambda wr, kv: wr.i16(kv[0]).i16(kv[1][0])
                            .i16(kv[1][1]))
                else:
                    self._dispatch(broker, api_key, r, w,
                                   client_epoch=client_epoch)
                resp = bytes(w.buf)
                self.request.sendall(struct.pack(">i", len(resp)) + resp)
        except (ConnectionError, OSError, struct.error):
            pass

    @staticmethod
    def _valid_part(broker: Broker, topic: str, pid: int) -> bool:
        """Guard every broker access: an out-of-range partition must come
        back as Kafka error 3, not an IndexError that kills the connection."""
        return topic in broker.topics() and \
            0 <= pid < broker.topic(topic).partitions

    @staticmethod
    def _mark_raw_batch(frames: bytes, stage: str, topic: str,
                        pid: int, at_or_after=None) -> None:
        """Record the broker-process hop of a wire-carried batch trace
        (ISSUE 13): a sampled RAW batch carries its context in the
        first frame's headers — decode it and mark `stage`, so a
        cross-process reconstruction shows the MQTT→bridge→shard→
        consumer path through THIS broker.  One bounded first-frame
        parse, only under tracing; any malformed bytes are simply not a
        trace (the produce/fetch path itself validates separately).
        ``at_or_after`` gates re-served batch heads on the fetch side
        exactly like StreamConsumer._extract_batch_trace."""
        from ..ops.framing import first_frame_headers

        try:
            hdrs = first_frame_headers(frames, at_or_after=at_or_after)
        except (ValueError, struct.error):
            return
        ctx = _tracing.from_headers(hdrs)
        if ctx is not None:
            _tracing.mark_batch(ctx, stage, topic, pid)

    def _epoch_mismatch(self, client_epoch: Optional[int]) -> bool:
        """True when the fencing epochs disagree.  A stamped epoch below
        the server's means the CLIENT slept through a failover; above it
        means THIS SERVER is a resurrected old leader — either way the
        log-mutating request must be refused, or the log splits.
        Unstamped (legacy/standard-Kafka) clients pass unfenced."""
        server_epoch = self.server.epoch     # type: ignore[attr-defined]
        return client_epoch is not None and client_epoch != server_epoch

    @staticmethod
    def _produce_error_resp(w: _Writer, tops, err: int) -> None:
        """Serialize a classic PRODUCE response answering `err` for
        every partition of every topic — the one writer behind the
        retiring / invalid-acks / epoch-fence early returns (a future
        response-shape change must land in exactly one place)."""
        resp = [(tname, [(pid, err, -1) for pid, _ in parts])
                for tname, parts in tops]
        w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
            t[1], lambda pw, p: pw.i32(p[0]).i16(p[1]).i64(p[2])
            .i64(-1))))
        w.i32(0)  # throttle

    def _not_coordinator(self) -> bool:
        """True when this broker is part of a cluster whose group
        coordinator is pinned to a DIFFERENT node: group membership and
        offset state must live in exactly one place, so every other
        broker answers NOT_COORDINATOR (16) and the client re-finds."""
        cluster = self.server.cluster        # type: ignore[attr-defined]
        return cluster is not None and \
            cluster.coordinator()[0] != cluster.node_id

    # ------------------------------------------------------------ handlers
    def _dispatch(self, broker: Broker, api_key: int, r: _Reader, w: _Writer,
                  client_epoch: Optional[int] = None):
        cluster = self.server.cluster          # type: ignore[attr-defined]
        if api_key == METADATA:
            n = r.i32()
            names = [r.string() for _ in range(max(n, 0))] if n >= 0 else None
            if names is None or n == 0:
                names = broker.topics()
            if cluster is not None:
                # cluster mode: the broker list is the WHOLE cluster and
                # every partition names its owning node — the map routing
                # clients cache (refreshed on NOT_LEADER_FOR_PARTITION)
                rows = list(cluster.brokers())
                my_id = cluster.node_id
            else:
                host, port = self.server.server_address[:2]  # type: ignore
                rows = [(0, host, port)]
                my_id = 0
            w.array(rows, lambda wr, b: wr.i32(b[0]).string(b[1])
                    .i32(b[2]).string(None))
            w.i32(my_id if cluster is None else rows[0][0])  # controller id

            def topic_entry(wr, name):
                known = name in broker.topics()
                wr.i16(ERR_NONE if known else ERR_UNKNOWN_TOPIC)
                wr.string(name).i8(0)
                parts = range(broker.topic(name).partitions) if known else []

                def part_entry(pw, p):
                    leader = my_id if cluster is None else \
                        cluster.leader_node(name, p)
                    pw.i16(ERR_NONE).i32(p).i32(-1 if leader is None
                                                else leader)
                    pw.array([leader if leader is not None else 0],
                             lambda x, v: x.i32(v))  # replicas
                    pw.array([leader if leader is not None else 0],
                             lambda x, v: x.i32(v))  # isr

                wr.array(list(parts), part_entry)

            w.array(names, topic_entry)
        elif api_key == PRODUCE:
            # required_acks is PARSED AND HONORED (ISSUE 14; it was
            # read-and-discarded before): 1 acks at the leader append,
            # -1 (acks=all) acks only once the batch is below the
            # quorum high-water mark, 0 answers immediately with no
            # delivery guarantee (errors masked — fire-and-forget).
            acks = r.i16()
            timeout_ms = r.i32()

            def part(rd):
                return (rd.i32(), rd.bytes_())

            tops = r.array(lambda rd: (rd.string(), rd.array(part)))
            if self.server.retiring:       # type: ignore[attr-defined]
                # reassignment step-down: leadership moved — answer
                # NOT_LEADER so every producer (epoch-stamped or
                # legacy) re-routes; nothing may land in a retired log
                self._produce_error_resp(w, tops,
                                         ERR_NOT_LEADER_FOR_PARTITION)
                return
            if acks not in (-1, 0, 1):
                self._produce_error_resp(w, tops,
                                         ERR_INVALID_REQUIRED_ACKS)
                return
            if self._epoch_mismatch(client_epoch):
                # fence BEFORE touching the broker: a stale-epoch produce
                # must append nothing anywhere
                self._produce_error_resp(w, tops,
                                         ERR_FENCED_LEADER_EPOCH)
                return
            repl = getattr(broker, "replication", None)
            resp = []
            for tname, parts in tops:
                presp = []
                for pid, record_set in parts:
                    entries = decode_message_set(record_set or b"")
                    if tname not in broker.topics() and cluster is None:
                        # cluster topics are provisioned cluster-wide by
                        # the controller/client fan-out; a single-broker
                        # auto-create here would fork the topic spec
                        broker.create_topic(tname, partitions=max(pid + 1, 1))
                    if not self._valid_part(broker, tname, pid):
                        presp.append((pid, ERR_UNKNOWN_TOPIC, -1))
                        continue
                    quorum = acks == -1 and repl is not None
                    if quorum:
                        # acks=all durability checks BEFORE any append:
                        # a topic with no ISR configured on a quorum-
                        # enabled broker is an explicit error, and an
                        # ISR below min_isr refuses (nothing appended —
                        # redelivery is safe).  A broker with NO
                        # replication state keeps Kafka's RF-1 shape:
                        # ISR = {leader}, acks=all == acks=1.
                        if not repl.covers(tname) or \
                                repl.isr_size(tname, pid) < repl.min_isr:
                            presp.append(
                                (pid, ERR_NOT_ENOUGH_REPLICAS, -1))
                            continue
                    try:
                        # bulk append under one broker lock — the
                        # per-message produce loop was a per-record cost
                        # in the server's hottest handler.  Null values
                        # pass through intact: a produced tombstone must
                        # land in the log as a tombstone, or compaction
                        # could never delete a key written over the wire.
                        # The returned LAST offset anchors both the
                        # response base and the quorum target: a
                        # re-read of end_offset could include a
                        # concurrent producer's later batch and make
                        # this request wait on (or time out over)
                        # records that are not its own.
                        last = broker.produce_many(
                            tname, [(key, value, ts)
                                    for _, key, value, ts in entries],
                            partition=pid)
                        base = last - len(entries) + 1 if entries \
                            else broker.end_offset(tname, pid)
                    except NotLeaderForPartitionError:
                        # sharded broker, unowned partition: Kafka error
                        # 6 — the client refreshes metadata and re-routes
                        presp.append(
                            (pid, ERR_NOT_LEADER_FOR_PARTITION, -1))
                        continue
                    except PermissionError:
                        # engine-owned topic (Broker.restrict_topic): an
                        # external client may not write the AVRO leg —
                        # the exclusivity trusted_passthrough relies on
                        presp.append(
                            (pid, ERR_TOPIC_AUTHORIZATION_FAILED, -1))
                        continue
                    if quorum and entries:
                        # block this handler thread until THIS batch is
                        # below the quorum HWM (followers fetch on their
                        # own connections/threads, so the wait starves
                        # nothing).  A timeout means APPENDED-UNACKED:
                        # the caller redelivers, Kafka's own contract.
                        if not repl.wait_replicated(
                                tname, pid, last + 1,
                                timeout_s=min(max(timeout_ms, 0) / 1000.0,
                                              30.0)):
                            presp.append(
                                (pid, ERR_REQUEST_TIMED_OUT, base))
                            continue
                    presp.append((pid, ERR_NONE, base))
                resp.append((tname, presp))
            if acks == 0:
                # fire-and-forget: the append already ran; the answer
                # carries no delivery information by definition (real
                # Kafka sends NO response at all for acks=0 — this
                # family's strict request/response framing keeps the
                # turn, masked)
                resp = [(tname, [(pid, ERR_NONE, -1)
                                 for pid, _err, _base in presp])
                        for tname, presp in resp]
            w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
                t[1], lambda pw, p: pw.i32(p[0]).i16(p[1]).i64(p[2])
                .i64(-1))))
            w.i32(0)  # throttle
        elif api_key == FETCH:
            # replica id >= 0 marks a FOLLOWER's mirror fetch (Kafka's
            # own field, finally load-bearing — ISSUE 14): the leader
            # observes the fetch position into its ISR tracker and
            # serves past the quorum HWM (a follower exists to read the
            # un-replicated tail); consumers (-1) are bounded by it.
            rid = r.i32()
            r.i32()  # max wait
            r.i32()  # min bytes

            def part(rd):
                return (rd.i32(), rd.i64(), rd.i32())

            tops = r.array(lambda rd: (rd.string(), rd.array(part)))
            repl = getattr(broker, "replication", None)
            resp = []
            for tname, parts in tops:
                presp = []
                for pid, offset, max_bytes in parts:
                    if not self._valid_part(broker, tname, pid):
                        presp.append((pid, ERR_UNKNOWN_TOPIC, -1, b""))
                        continue
                    try:
                        if rid >= 0:
                            if repl is not None:
                                repl.observe_fetch(rid, tname, pid,
                                                   offset)
                            # relay brokers have no fetch_tail: they
                            # carry no replication state either, so the
                            # plain fetch is already unbounded there
                            msgs = getattr(broker, "fetch_tail",
                                           broker.fetch)(
                                tname, pid, offset, 4096)
                        else:
                            msgs = broker.fetch(tname, pid, offset, 4096)
                    except NotLeaderForPartitionError:
                        presp.append((pid, ERR_NOT_LEADER_FOR_PARTITION,
                                      -1, b""))
                        continue
                    except OffsetOutOfRangeError as e:
                        # Kafka error 1; the hwm slot carries the
                        # earliest retained offset so the client's
                        # auto-reset needs no second round trip
                        presp.append((pid, ERR_OFFSET_OUT_OF_RANGE,
                                      e.earliest, b""))
                        continue
                    hwm = broker.end_offset(tname, pid)
                    if rid < 0 and repl is not None:
                        # consumers see the QUORUM hwm (their readable
                        # frontier), not the leader log end — consumer
                        # lag measures against what they may read
                        ceil = repl.fetch_ceiling(tname, pid)
                        if ceil is not None:
                            hwm = ceil
                    ms = encode_message_set(
                        [(m.offset, m.key, m.value, m.timestamp_ms)
                         for m in msgs])[:max(max_bytes, 0) or None]
                    presp.append((pid, ERR_NONE, hwm, ms))
                resp.append((tname, presp))
            w.i32(0)  # throttle
            w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
                t[1], lambda pw, p: pw.i32(p[0]).i16(p[1]).i64(p[2])
                .bytes_(p[3]))))
        elif api_key == RAW_FETCH:
            # emulator-family extension: one partition, the broker's raw
            # store-frame bytes verbatim — no MessageSet re-encode, no
            # per-record server work (durable brokers serve the
            # segment's own disk bytes)
            tname = r.string()
            pid = r.i32()
            offset = r.i64()
            max_bytes = r.i32()
            # trailing-optional replica id (ISSUE 14): a follower's
            # zero-copy mirror fetch — observed into the ISR, served
            # past the quorum HWM.  Old clients simply end the request
            # here and stay consumers.
            rid = r.i32() if r.pos + 4 <= len(r.buf) else -1
            repl = getattr(broker, "replication", None)
            fetch_raw = getattr(broker, "fetch_raw", None)
            valid = self._valid_part(broker, tname, pid)
            if valid and rid >= 0 and fetch_raw is not None:
                # observe only VALIDATED partitions (a replica with a
                # stale topic view must not seed a garbage part state
                # that poisons the every-partition ISR intersection)
                if repl is not None:
                    repl.observe_fetch(rid, tname, pid, offset)
                fetch_raw = getattr(broker, "fetch_raw_tail", fetch_raw)
            if not valid:
                w.i16(ERR_UNKNOWN_TOPIC).i64(-1).bytes_(None)
            elif fetch_raw is None:  # relay broker without raw reads
                w.i16(ERR_UNSUPPORTED_VERSION)
            else:
                try:
                    raw = fetch_raw(tname, pid, offset,
                                    max_bytes=max(max_bytes, 4096))
                except NotImplementedError:
                    # a RELAY broker (wire client / cluster route) whose
                    # upstream lacks the extension: same downgrade
                    # answer as a pre-extension server, so the client
                    # pins back to classic FETCH instead of dying on a
                    # severed connection
                    w.i16(ERR_UNSUPPORTED_VERSION)
                except NotLeaderForPartitionError:
                    w.i16(ERR_NOT_LEADER_FOR_PARTITION).i64(-1).bytes_(None)
                except OffsetOutOfRangeError as e:
                    w.i16(ERR_OFFSET_OUT_OF_RANGE).i64(e.earliest)
                    w.bytes_(None)
                else:
                    # cheap for local (in-memory/durable) brokers; a
                    # RELAY broker (wire client backing this server)
                    # must not pay an upstream round trip per fetch —
                    # its own fetch_raw just cached the upstream's
                    # trailing hwm, so answer from that cache (-1 =
                    # genuinely absent)
                    if hasattr(broker, "_request"):
                        lh = getattr(broker, "last_hwm", None)
                        hwm = lh(tname, pid) if lh is not None else None
                        hwm = -1 if hwm is None else hwm
                    elif rid < 0 and repl is not None and \
                            repl.fetch_ceiling(tname, pid) is not None:
                        # consumers' columnar lag measures against the
                        # quorum hwm — their readable frontier
                        hwm = repl.fetch_ceiling(tname, pid)
                    else:
                        hwm = broker.end_offset(tname, pid)
                    if raw is None:
                        w.i16(ERR_NONE).i64(offset).bytes_(b"")
                    else:
                        if _tracing.ENABLED:
                            # broker-process hop of a wire-carried batch
                            # trace: one first-frame parse per raw fetch
                            # (batch-granular), so the trace CLI sees
                            # the shard the batch crossed
                            self._mark_raw_batch(raw.data,
                                                 "wire_raw_fetch",
                                                 tname, pid,
                                                 at_or_after=offset)
                        w.i16(ERR_NONE).i64(raw.start_offset)
                        w.bytes_(raw.data)
                    # trailing-optional hwm: consumer lag for the
                    # columnar path at zero extra round trips (older
                    # clients never read past the blob)
                    w.i64(hwm)
        elif api_key == RAW_PRODUCE:
            # write-path mirror of RAW_FETCH: a pre-framed batch the
            # broker appends segment-verbatim (CRCs validated WHOLE,
            # offsets stamped into the frame heads server-side).  A
            # corrupt batch answers CORRUPT_MESSAGE with nothing
            # appended — no torn/partial appends ever reach a segment.
            tname = r.string()
            pid = r.i32()
            frames = r.bytes_() or b""
            # trailing-optional required_acks + timeout (ISSUE 14): the
            # RAW_PRODUCE mirror of classic produce's field.  Absent
            # (old clients) means -1, the classic client default.
            acks = r.i16() if r.pos + 2 <= len(r.buf) else -1
            timeout_ms = r.i32() if r.pos + 4 <= len(r.buf) else 10_000
            repl = getattr(broker, "replication", None)
            quorum = acks == -1 and repl is not None
            produce_raw = getattr(broker, "produce_raw", None)
            if self.server.retiring:       # type: ignore[attr-defined]
                # reassignment step-down, same answer as classic
                w.i16(ERR_NOT_LEADER_FOR_PARTITION).i64(-1).i32(0)
            elif self._epoch_mismatch(client_epoch):
                # fence BEFORE touching the broker, like classic produce
                w.i16(ERR_FENCED_LEADER_EPOCH).i64(-1).i32(0)
            elif produce_raw is None:
                # relay broker without raw appends: same downgrade as a
                # pre-extension server — clients pin back to classic
                w.i16(ERR_UNSUPPORTED_VERSION)
            elif acks not in (-1, 0, 1):
                w.i16(ERR_INVALID_REQUIRED_ACKS).i64(-1).i32(0)
            else:
                if tname not in broker.topics() and cluster is None:
                    broker.create_topic(tname, partitions=max(pid + 1, 1))
                if not self._valid_part(broker, tname, pid):
                    w.i16(ERR_UNKNOWN_TOPIC).i64(-1).i32(0)
                elif quorum and (not repl.covers(tname) or
                                 repl.isr_size(tname, pid) <
                                 repl.min_isr):
                    # same pre-append refusal as classic acks=all:
                    # nothing lands, redelivery is safe
                    w.i16(ERR_NOT_ENOUGH_REPLICAS).i64(-1).i32(0)
                else:
                    if _tracing.ENABLED:
                        self._mark_raw_batch(frames, "wire_raw_produce",
                                             tname, pid)
                    nframes = None
                    if quorum:
                        # the quorum wait must target THIS batch's own
                        # last offset, not an end_offset re-read that
                        # may include a concurrent producer's later
                        # batch (the same race fixed on classic
                        # produce): count the frames before the append
                        # — one validation walk, quorum path only; a
                        # corrupt batch falls through to produce_raw's
                        # own whole-batch rejection
                        from ..ops import framing as _fr

                        try:
                            nframes = _fr.validate_frame_batch(
                                frames)["count"]
                        except _fr.CorruptFrameError:
                            nframes = None
                    try:
                        base = produce_raw(tname, pid, frames)
                    except NotImplementedError:
                        w.i16(ERR_UNSUPPORTED_VERSION)
                    except CorruptMessageError as e:
                        w.i16(ERR_CORRUPT_MESSAGE).i64(e.index).i32(0)
                    except NotLeaderForPartitionError:
                        w.i16(ERR_NOT_LEADER_FOR_PARTITION).i64(-1).i32(0)
                    except PermissionError:
                        # engine-owned topic without the owner's grant
                        w.i16(ERR_TOPIC_AUTHORIZATION_FAILED).i64(-1)
                        w.i32(0)
                    else:
                        count = nframes if nframes is not None else \
                            broker.end_offset(tname, pid) - base
                        if quorum and count and not repl.wait_replicated(
                                tname, pid, base + count,
                                timeout_s=min(max(timeout_ms, 0)
                                              / 1000.0, 30.0)):
                            # appended-unacked: the producer redelivers
                            w.i16(ERR_REQUEST_TIMED_OUT).i64(base)
                            w.i32(count)
                        else:
                            w.i16(ERR_NONE).i64(base)
                            w.i32(count)
        elif api_key == LIST_OFFSETS:
            r.i32()  # replica

            def part(rd):
                return (rd.i32(), rd.i64())

            tops = r.array(lambda rd: (rd.string(), rd.array(part)))
            resp = []
            for tname, parts in tops:
                presp = []
                for pid, ts in parts:
                    try:
                        if not self._valid_part(broker, tname, pid):
                            presp.append((pid, ERR_UNKNOWN_TOPIC, -1, -1))
                        elif ts == -2:
                            presp.append((pid, ERR_NONE, -1,
                                          broker.begin_offset(tname, pid)))
                        elif ts >= 0:
                            # ListOffsets by timestamp: the replay cursor
                            # (earliest offset with record ts >= requested)
                            presp.append((pid, ERR_NONE, -1,
                                          broker.offset_for_timestamp(
                                              tname, pid, ts)))
                        else:
                            presp.append((pid, ERR_NONE, -1,
                                          broker.end_offset(tname, pid)))
                    except NotLeaderForPartitionError:
                        presp.append((pid, ERR_NOT_LEADER_FOR_PARTITION,
                                      -1, -1))
                resp.append((tname, presp))
            w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
                t[1], lambda pw, p: pw.i32(p[0]).i16(p[1]).i64(p[2])
                .i64(p[3]))))
        elif api_key == OFFSET_COMMIT:
            group = r.string()
            generation = r.i32()
            member = r.string()
            r.i64()  # retention

            def part(rd):
                return (rd.i32(), rd.i64(), rd.string())

            tops = r.array(lambda rd: (rd.string(), rd.array(part)))
            if self._not_coordinator():
                # cluster group/offset state is pinned to ONE broker:
                # a commit accepted here would fork the offset table
                resp = [(tname, [(pid, ERR_NOT_COORDINATOR)
                                 for pid, _, _ in parts])
                        for tname, parts in tops]
            elif self._epoch_mismatch(client_epoch):
                # stale-epoch commit: writing it would let a zombie
                # fence-bypass the promoted log's offset streams
                resp = [(tname, [(pid, ERR_FENCED_LEADER_EPOCH)
                                 for pid, _, _ in parts])
                        for tname, parts in tops]
            # generation == -1: simple consumer, no fencing (the classic
            # path).  A real generation routes through the group coordinator
            # so a member fenced by a rebalance cannot clobber offsets.
            elif generation >= 0:
                coord = self.server.group_coordinator(group)
                positions = [(t, pid, off)
                             for t, parts in tops for pid, off, _ in parts]
                done = coord.fenced_commit_detailed(member, generation,
                                                    positions)
                if done is None:  # fenced: nothing written
                    resp = [(t, [(pid, ERR_ILLEGAL_GENERATION)
                                 for pid, _, _ in parts])
                            for t, parts in tops]
                else:  # per-partition: unowned partitions error out loudly
                    resp = [(t, [(pid, ERR_NONE if (t, pid) in done
                                  else ERR_ILLEGAL_GENERATION)
                                 for pid, _, _ in parts])
                            for t, parts in tops]
            else:
                for tname, parts in tops:
                    # one batched commit per topic: a durable broker
                    # fsyncs its offsets file ONCE per request, not once
                    # per partition (the client batched for a reason)
                    broker.commit_many(group, tname,
                                       [(pid, off) for pid, off, _ in parts])
                resp = [(tname, [(pid, ERR_NONE) for pid, _, _ in parts])
                        for tname, parts in tops]
            w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
                t[1], lambda pw, p: pw.i32(p[0]).i16(p[1]))))
        elif api_key == OFFSET_FETCH:
            group = r.string()
            tops = r.array(lambda rd: (rd.string(),
                                       rd.array(lambda p: p.i32())))
            err = ERR_NOT_COORDINATOR if self._not_coordinator() \
                else ERR_NONE
            resp = []
            for tname, parts in tops:
                presp = []
                for pid in parts:
                    off = None if err else broker.committed(group, tname,
                                                            pid)
                    presp.append((pid, -1 if off is None else off))
                resp.append((tname, presp))
            w.array(resp, lambda wr, t: (wr.string(t[0]), wr.array(
                t[1], lambda pw, p: pw.i32(p[0]).i64(p[1]).string(None)
                .i16(err))))
        elif api_key == FIND_COORDINATOR:
            r.string()  # group id — ONE coordinator per cluster (pinned)
            if cluster is not None:
                node, host, port = cluster.coordinator()
                w.i16(ERR_NONE).i32(node).string(host).i32(port)
            else:
                # advertise the address the client actually connected to,
                # not the bind address (0.0.0.0 would be unconnectable)
                host = self.request.getsockname()[0]
                w.i16(ERR_NONE).i32(0).string(host).i32(self.server.port)
        elif api_key == JOIN_GROUP and self._not_coordinator():
            w.i16(ERR_NOT_COORDINATOR).i32(-1).string("").string("")
            w.string("")
            w.array([], lambda wr, x: None)
        elif api_key == SYNC_GROUP and self._not_coordinator():
            r.string()
            w.i16(ERR_NOT_COORDINATOR).bytes_(b"")
        elif api_key in (HEARTBEAT, LEAVE_GROUP) and \
                self._not_coordinator():
            w.i16(ERR_NOT_COORDINATOR)
        elif api_key == JOIN_GROUP:
            group = r.string()
            session_timeout_ms = r.i32()
            member = r.string()
            r.string()  # protocol type ("consumer")
            protocols = r.array(lambda rd: (rd.string(), rd.bytes_()))
            # subscription topics from the standard consumer protocol
            # metadata: version i16, topics array<str>, userdata bytes
            topics = []
            if protocols:
                meta = _Reader(protocols[0][1] or b"")
                try:
                    meta.i16()
                    topics = meta.array(lambda rd: rd.string())
                except struct.error:
                    topics = []
            coord = self.server.group_coordinator(
                group, session_timeout_ms / 1000.0)
            mid, gen, _assigned = coord.join(topics, member or None)
            members = coord.members()
            leader = members[0] if members else mid
            # echo a protocol the client actually offered (a client errors
            # out if told a protocol it never proposed); assignment itself
            # is computed server-side regardless (see class docstring)
            proto = protocols[0][0] if protocols else "range"
            w.i16(ERR_NONE).i32(gen).string(proto).string(leader).string(mid)
            # standard flow: the elected leader receives every member's
            # subscription metadata so it can compute the assignment
            # client-side (our SyncGroup computes server-side regardless,
            # and ignores what the leader submits — same answer)
            rows = []
            if mid == leader:
                for other_id, subs in sorted(coord.subscriptions().items()):
                    mw = _Writer()
                    mw.i16(0)
                    mw.array(list(subs), lambda wr2, t: wr2.string(t))
                    mw.bytes_(b"")
                    rows.append((other_id, bytes(mw.buf)))
            w.array(rows, lambda wr, x: (wr.string(x[0]), wr.bytes_(x[1])))
        elif api_key == SYNC_GROUP:
            group = r.string()
            generation = r.i32()
            member = r.string()
            r.array(lambda rd: (rd.string(), rd.bytes_()))  # leader's (unused)
            coord = self.server.group_coordinator(group)
            # one atomic coordinator call: check + assignment under one lock
            verdict, assigned = coord.sync(member, generation)
            if verdict == "unknown_member":
                w.i16(ERR_UNKNOWN_MEMBER_ID).bytes_(b"")
            elif verdict == "illegal_generation":
                w.i16(ERR_ILLEGAL_GENERATION).bytes_(b"")
            else:
                by_topic: dict = {}
                for t, p in assigned:
                    by_topic.setdefault(t, []).append(p)
                aw = _Writer()
                aw.i16(0)  # ConsumerProtocolAssignment version
                aw.array(sorted(by_topic.items()), lambda wr, tp: (
                    wr.string(tp[0]),
                    wr.array(sorted(tp[1]), lambda pw, p: pw.i32(p))))
                aw.bytes_(b"")  # userdata
                w.i16(ERR_NONE).bytes_(bytes(aw.buf))
        elif api_key == HEARTBEAT:
            group = r.string()
            generation = r.i32()
            member = r.string()
            coord = self.server.group_coordinator(group)
            verdict = coord.heartbeat_verdict(member, generation)
            w.i16({"ok": ERR_NONE,
                   "unknown_member": ERR_UNKNOWN_MEMBER_ID,
                   "rebalance_in_progress": ERR_REBALANCE_IN_PROGRESS}
                  [verdict])
        elif api_key == LEAVE_GROUP:
            group = r.string()
            member = r.string()
            self.server.group_coordinator(group).leave(member)
            w.i16(ERR_NONE)
        elif api_key == CLUSTER_ADMIN:
            # elastic reassignment verbs (ISSUE 14): served only when a
            # controller is attached (`server.admin`); the verbs run IN
            # this handler thread — the CLI waits for the reassignment
            # report, other connections keep serving (threading server)
            import json as _json

            command = r.string()
            blob = r.bytes_() or b"{}"
            admin = getattr(self.server, "admin", None)
            if admin is None:
                w.i16(ERR_UNSUPPORTED_VERSION)
            else:
                try:
                    doc = admin.admin_command(
                        command or "",
                        _json.loads(blob.decode() or "{}"))
                    w.i16(ERR_NONE)
                    w.bytes_(_json.dumps(doc, default=str).encode())
                except Exception as e:  # noqa: BLE001 - the operator
                    # gets the error text, the connection stays up
                    w.i16(ERR_UNKNOWN_SERVER)
                    w.bytes_(_json.dumps(
                        {"error": f"{type(e).__name__}: {e}"}).encode())
        elif api_key == CREATE_TOPICS:
            def topic(rd):
                name = rd.string()
                parts = rd.i32()
                rd.i16()  # replication factor
                rd.array(lambda x: (x.i32(), x.array(lambda y: y.i32())))
                cfgs = rd.array(lambda x: (x.string(), x.string()))
                return (name, parts, cfgs)

            tops = r.array(topic)
            r.i32()  # timeout
            resp = []
            for name, parts, cfgs in tops:
                if name in broker.topics():
                    resp.append((name, ERR_TOPIC_EXISTS))
                else:
                    # retention configs carried the standard way (the
                    # names Kafka itself uses); unknown keys are ignored
                    # like a permissive broker's defaults path
                    try:
                        ret = {}
                        for k, v in cfgs:
                            if k == "cleanup.policy" and v is not None:
                                # create_topic validates the value
                                # (ValueError → INVALID_CONFIG below)
                                ret["cleanup_policy"] = v
                                continue
                            field = {"retention.messages":
                                     "retention_messages",
                                     "retention.bytes": "retention_bytes",
                                     "retention.ms": "retention_ms"}.get(k)
                            if field is None or v is None:
                                continue
                            value = int(v)  # non-integer → INVALID_CONFIG
                            if value == -1:
                                # Kafka's documented 'unlimited' sentinel
                                # for retention.*: explicit unlimited (0),
                                # which on a durable broker OVERRIDES the
                                # store-wide default (None would inherit)
                                value = 0
                            ret[field] = value
                        broker.create_topic(name, partitions=max(parts, 1),
                                            **ret)
                    except ValueError:
                        # unparseable or negative retention: answer
                        # INVALID_CONFIG instead of killing the connection
                        resp.append((name, ERR_INVALID_CONFIG))
                        continue
                    resp.append((name, ERR_NONE))
            w.array(resp, lambda wr, t: wr.string(t[0]).i16(t[1]))


class KafkaWireServer(socketserver.ThreadingTCPServer):
    """TCP Kafka-protocol front for the in-process Broker.

    `with KafkaWireServer(broker) as s:` serves on an ephemeral localhost
    port (`s.port`).  Pass `credentials=(user, password)` to require the
    SASL/PLAIN exchange the reference's cluster config mandates
    (gcp.yaml:29-32).
    """

    allow_reuse_address = True
    daemon_threads = True

    def __init__(self, broker: Broker, host: str = "127.0.0.1",
                 port: int = 0,
                 credentials: Optional[Tuple[str, str]] = None,
                 epoch: int = 0, cluster=None):
        super().__init__((host, port), _KafkaConn)
        self.broker = broker
        self.credentials = credentials
        self.port = self.server_address[1]
        #: cluster view (iotml.cluster duck-type: node_id, brokers(),
        #: leader_node(topic, partition), coordinator()) — None for the
        #: classic single-broker server.  With a view, Metadata carries
        #: per-partition leaders, unowned partitions answer
        #: NOT_LEADER_FOR_PARTITION, and group/offset APIs are pinned to
        #: the view's coordinator node.
        self.cluster = cluster
        #: cluster admin hook (iotml.cluster.ClusterController duck-
        #: type: admin_command(command, args) -> dict) — None answers
        #: CLUSTER_ADMIN with UNSUPPORTED_VERSION.
        self.admin = None
        #: reassignment step-down (ISSUE 14): True once leadership has
        #: moved off this server but its sockets are still draining —
        #: every write answers NOT_LEADER_FOR_PARTITION (truthful: it
        #: no longer leads) so even UNSTAMPED legacy producers re-route
        #: instead of split-writing into a retired log; reads keep
        #: serving through the grace window.
        self.retiring = False
        #: leadership fencing epoch this server believes it serves at.
        #: Promotion bumps it (FollowerReplica.promote); a restarted old
        #: leader comes back with its stale value and fences itself
        #: against epoch-stamped produce/commit traffic.
        self.epoch = int(epoch)
        self._thread: Optional[threading.Thread] = None
        self._coordinators: dict = {}
        self._coord_lock = threading.Lock()
        self._live_conns: set = set()
        self._conn_lock = threading.Lock()

    def set_epoch(self, epoch: int) -> None:
        if epoch < self.epoch:
            raise ValueError(f"epoch must be monotonic: have {self.epoch}, "
                             f"got {epoch}")
        self.epoch = int(epoch)

    def group_coordinator(self, group_id: str,
                          session_timeout_s: Optional[float] = None):
        """Broker-side GroupCoordinator for a group (created on first use).
        The session timeout is fixed by the first member that names one."""
        from .group import GroupCoordinator

        with self._coord_lock:
            coord = self._coordinators.get(group_id)
            if coord is None:
                coord = GroupCoordinator(
                    self.broker, group_id,
                    session_timeout_s=session_timeout_s or 10.0)
                self._coordinators[group_id] = coord
            return coord

    def start(self) -> "KafkaWireServer":
        from ..supervise.registry import register_thread

        self._thread = register_thread(threading.Thread(
            target=self.serve_forever, daemon=True,
            name=f"iotml-kafka-wire-{self.port}"))
        self._thread.start()
        return self

    def __enter__(self) -> "KafkaWireServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()
        self.server_close()

    def kill(self) -> None:
        """Simulate abrupt broker death (failover tests / drills):
        `shutdown()` alone only stops the accept loop — established
        handler threads keep serving their sockets, which a dead process
        would not.  This severs every live client connection too, so
        clients observe exactly what a crashed leader looks like."""
        self.shutdown()
        with self._conn_lock:
            conns = list(self._live_conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        self.server_close()
