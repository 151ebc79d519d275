"""Unbounded stream → fixed-shape batches (the tf.data pipeline, TPU-first).

The reference builds its input pipeline in-graph:
KafkaDataset → substr(5) → decode_avro → normalize → filter(y=="false")
→ zip(x,x) → batch(100) → take(100)   (cardata-v3.py:197-218).

A TPU pipeline must deliver *static shapes* — XLA compiles one program per
shape, and an unbounded stream with data-dependent filtering produces ragged
batches.  The design here:

- decode + normalize happen host-side in columnar numpy (C++ engine later),
- filtering (label == "false") happens host-side *before* batching, so the
  device only ever sees dense [B, F] blocks,
- the tail batch is zero-padded to B with a validity mask `n_valid`, so the
  jitted step never sees a new shape and never recompiles.

`SensorBatches` mirrors the reference knobs (batch_size, take, skip) and its
per-epoch re-read semantics via `reset()`.  A bounded take re-entered on
one cursor (a loop of jobs) reads one take ahead: see `_take_ended`.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Iterator, List, Optional

import numpy as np

from ..core.normalize import Normalizer, CAR_NORMALIZER
from ..core.schema import KSQL_CAR_SCHEMA, RecordSchema
from ..obs import metrics as obs_metrics
from ..obs import tracing
from ..ops.avro import AvroCodec, needs_resolution
from ..ops.framing import strip_frame
from ..stream.consumer import StreamConsumer


@dataclasses.dataclass
class Batch:
    """One fixed-shape batch. x is [B, F] float32; rows >= n_valid are padding.

    `first_index` is the global record index of row 0 within this stream view
    (after filtering/skip) — the index OutputSequence keys write-back on.
    """

    x: np.ndarray
    n_valid: int
    first_index: int
    labels: Optional[np.ndarray] = None  # object array of strings, if kept
    y: Optional[np.ndarray] = None  # supervised target (windowed/LSTM path)
    keys: Optional[np.ndarray] = None  # [B] S-bytes message keys, if kept

    @property
    def mask(self) -> np.ndarray:
        m = np.zeros((self.x.shape[0],), np.float32)
        m[: self.n_valid] = 1.0
        return m


class SensorBatches:
    """Iterable of fixed-shape sensor batches off a StreamConsumer.

    Args mirror the reference pipeline:
      batch_size: rows per batch (reference: 100; LSTM: 1).
      take: max batches per epoch (reference: 100), None = to EOF.
      skip: batches to skip first (reference predict path: skip(100)).
      only_normal: keep rows with label "false" only (training filter,
        cardata-v3.py:212); False keeps everything (predict path).
      window: if set, emit [B, window, F] sliding windows (LSTM path,
        window(look_back, shift=1) — reference LSTM cardata-v1.py:184-190)
        together with next-step targets y [B, 1, F].
      pad_tail: zero-pad the final ragged batch (True) or drop it (False —
        the reference's drop_remainder-free batch() keeps ragged tails; we
        pad by default because static shapes are the TPU contract).

    A bounded take (`take` set) re-entered on one cursor reads one take
    ahead: from the second full take in a row on, the consumer fetches
    the next take's records while the caller trains on this one's
    (`_take_ended`); its positions stay the delivered ones.
    """

    def __init__(self, consumer: StreamConsumer,
                 schema: RecordSchema = KSQL_CAR_SCHEMA,
                 normalizer: Normalizer = CAR_NORMALIZER,
                 batch_size: int = 100,
                 take: Optional[int] = None,
                 skip: int = 0,
                 only_normal: bool = False,
                 window: Optional[int] = None,
                 pad_tail: bool = True,
                 keep_labels: bool = False,
                 keep_keys: bool = False,
                 exclude_key_marker: Optional[bytes] = None,
                 poll_chunk: int = 4096,
                 cache: bool = False):
        self.consumer = consumer
        self.schema = schema
        self.codec = AvroCodec(schema)
        self.normalizer = normalizer
        self.batch_size = batch_size
        self.take = take
        self.skip = skip
        self.only_normal = only_normal
        self.window = window
        self.pad_tail = pad_tail
        self.keep_labels = keep_labels
        # keep_keys threads each record's MESSAGE KEY (the car's routing
        # identity: MQTT topic → bridge key → KSQL pass-through) into
        # Batch.keys — what per-entity consumers (car-health detection)
        # join on.  Batched path only; the windowed path has no per-row
        # key semantics (a window spans records).
        self.keep_keys = keep_keys
        # exclude_key_marker drops every record whose message key
        # contains the marker BEFORE batching — the canary firewall
        # (obs.canary.CANARY_KEY_MARKER): synthetic probe records ride
        # the real ingest path but must never be scored into user-facing
        # prediction topics.  Exclusion needs the keys even when the
        # caller doesn't (keep_keys=False), so key capture is forced
        # internally and the keys are shed again after filtering.
        self.exclude_key_marker = exclude_key_marker
        self._capture_keys = keep_keys or exclude_key_marker is not None
        self.poll_chunk = poll_chunk
        # cache=True decodes the stream once and replays batches from host
        # memory on later epochs.  The reference re-reads Kafka every epoch
        # only because KafkaDataset cannot cache (python-scripts/
        # README.md:114-117); over an immutable log slice the two are
        # semantically identical, so this is a pure throughput feature.
        self.cache = cache
        self._cached = None
        self.records_seen = 0  # pre-filter record count this epoch
        # skip applies once to the stream head (reference skip(100) targets
        # the offset-slice, cardata-v3.py:274), not once per drain — a
        # continuous scorer re-entering __iter__ must not re-skip new data.
        self._skipped = 0
        # Rows still wanted by the current bounded iteration (None =
        # unbounded): `take` callers must not poll past what they will
        # batch — over-polled rows would advance the consumer cursor and
        # be skipped for good.  Updated by __iter__ between chunks, read
        # by the poll loop to cap each fetch.
        self._need_rows: Optional[int] = None
        # The `max_messages` of the current bounded iteration's
        # `poll_decoded` calls (None outside one, and from the first
        # consumer call of another kind on), and how many bounded
        # iterations in a row emitted their whole `take`: what
        # `_take_ended` hands the consumer's read-ahead.
        self._take_polls: Optional[list] = None
        self._full_takes = 0
        # Trace contexts FORKED from consumed record headers, marked
        # `consume` at decode and held (bounded drop-oldest) for the
        # pipeline closer — the train step / scorer calls take_traces()
        # and closes each with its e2e span.  Forked, not shared: every
        # consumer group of a topic polls the same header object, and
        # closing it directly would let the first pipeline steal the
        # trace from the others (train-then-serve over one topic is the
        # demo's normal shape).  _seen_traces dedups epoch re-reads of
        # the same records within THIS batcher (bounded; a continuous
        # cursor never re-reads, only epoch loops do).  Empty and
        # untouched when tracing is off.  The pending bound must cover a
        # full drain at full sampling (a deep-backlog drain holds every
        # fork until the closing commit); past it the oldest forks drop
        # — counted into iotml_trace_spans_dropped_total, best-effort —
        # rather than growing without bound under a reader that never
        # closes (e.g. an evaluation-only pass over the stream).
        self._pending_traces: collections.deque = collections.deque(
            maxlen=65536)
        self._seen_traces: set = set()
        self._seen_traces_cap = 65536
        # Mixed-schema (evolution) decode path, built lazily on the
        # first chunk that actually carries a non-v1 writer id
        self._resolving = None
        # Native (C++) columnar decode when the engine is built; the pure
        # codec is the fallback and the test oracle.
        self._native = None
        try:
            from ..stream.native import NativeCodec

            self._native = NativeCodec(schema)
            # label column index among the schema's string fields
            strings = [f.name for f in schema.fields if f.avro_type == "string"]
            self._label_col = strings.index(schema.label_field) \
                if schema.label_field in strings else None
        except Exception:
            self._native = None
        # Zero-copy columnar raw-batch pipeline (ISSUE 10): raw store
        # frames decoded by the ONE FrameDecoder into a ring of
        # reusable preallocated column buffers.  Engaged for durable
        # and wire brokers (where the frames already exist as bytes);
        # the in-memory emulator would pay a re-framing encode per
        # record, so it keeps the fused/legacy paths.  Built lazily on
        # the first chunk; tri-state None=untried / ring / False=off.
        self._ring = None
        self._framedec = None

    # ------------------------------------------------------------ core
    def _native_labels(self, lab: np.ndarray, n: int) -> np.ndarray:
        """Label column out of the native decoder's fixed-stride bytes."""
        return (lab[:, self._label_col].astype("U")
                if self._label_col is not None
                else np.full((n,), "", object))

    def _emit_chunk(self, num: np.ndarray, labels, keys=None) -> tuple:
        """Shared tail of every decode path: normalize + account."""
        xs = self.normalizer.np(num)
        self.records_seen += len(xs)
        obs_metrics.records_consumed.inc(len(xs))
        return xs, np.asarray(labels), keys

    def _poll_limit(self) -> int:
        """Per-poll fetch cap: the configured chunk, bounded by what the
        current iteration still needs (see _need_rows)."""
        if self._need_rows is None:
            return self.poll_chunk
        return max(1, min(self.poll_chunk, self._need_rows))

    def _take_ended(self, full: bool) -> None:
        """An iteration is over; `full`: it emitted its whole `take`.
        From the second full take in a row on this object — a loop of
        jobs on one cursor, not a one-shot job, which must not fetch a
        record it will not train — the consumer is asked to run the
        polls this take made once more, ahead of the next take and
        while the caller computes (`StreamConsumer.read_ahead`: an exact
        replay below `positions()`, so what is delivered, committed and
        checkpointed is what it was).  Unbounded drains never ask."""
        polls, self._take_polls = self._take_polls, None
        self._need_rows = None
        self._full_takes = self._full_takes + 1 if full else 0
        ahead = getattr(self.consumer, "read_ahead", None)
        if polls and self._full_takes >= 2 and ahead is not None:
            ahead(polls, self._native, strip=5,
                  with_keys=self._capture_keys)

    def _columnar_ready(self) -> bool:
        """Whether the zero-copy raw-batch path applies to this broker:
        native engine built, consumer/broker expose the raw duck-type,
        and the frames already exist as bytes (durable store or a wire
        hop) — the in-memory emulator would pay a per-record re-framing
        encode, so it keeps the fused/legacy paths."""
        if self._ring is False or self._native is None:
            return False
        broker = self.consumer.broker
        if getattr(self.consumer, "poll_into", None) is None or \
                getattr(broker, "fetch_raw", None) is None:
            return False
        durable = getattr(broker, "durable", None)
        if tracing.ENABLED and durable is not None:
            # record headers — the trace-context carrier — exist only on
            # the in-process broker, and the columnar path never
            # materialises them: a TRACED session keeps the header-
            # carrying message path there (the chaos/obs span-log
            # invariants read those spans).  Wire brokers drop headers
            # either way, so they stay columnar.
            return False
        return durable is None or bool(durable)

    def _columnar_chunks(self):
        """The zero-copy hot path: raw frame batches → FrameDecoder →
        ring slots — zero per-record Python objects from socket/disk to
        the normalized block.  The SAME `poll_into` entry serves live
        consume and timestamp-replay backfill (a backfill is a seek
        plus this), so the two cannot drift.

        Runtime guard (no more silent v1 pinning): the decoder verifies
        every value's Confluent header and STOPS at a frame whose
        writer id sits in the evolved-schema band — `poll_into` then
        reports ``fallback=True`` and ONE chunk is taken through the
        resolving Python path below before columnar resumes."""
        from . import pipeline as pl

        if self._ring is None:
            rows = max(int(self.poll_chunk), 1)
            self._ring = pl.DecodeRing(
                rows, self._native.n_numeric, self._native.n_strings,
                with_keys=self._capture_keys)
            self._framedec = self._native.frame_decoder()
        max_bytes = pl.raw_batch_bytes()
        while True:
            slot = self._ring.next_slot()
            # `fetch`: the wire and, fused into the same native call,
            # the frame decode (one span per consumer call, never per
            # record; its loop is the enclosing phase's)
            with tracing.phase(None, "fetch"):
                res = self.consumer.poll_into(
                    self._framedec, slot.x, slot.labels, slot.keys,
                    max_rows=min(self._poll_limit(), self._ring.rows),
                    max_bytes=max_bytes)
            if res is None:
                # broker lost raw support (wire server downgrade):
                # permanently hand back to the legacy paths
                self._ring = False
                return
            n, fallback = res
            if tracing.ENABLED:
                # batch-granular wire traces (ISSUE 13): poll_into
                # extracted any first-frame trace contexts — queue them
                # for the pipeline closer exactly like record traces,
                # so the scorer/train step closes them with e2e spans
                take = getattr(self.consumer, "take_batch_traces", None)
                if take is not None:
                    pending = self._pending_traces
                    for ctx in take():
                        if len(pending) == pending.maxlen:
                            tracing.spans_dropped.inc()
                        pending.append(ctx)
            if n:
                keys = slot.keys[:n].copy() if self._capture_keys else None
                yield self._emit_chunk(
                    slot.x[:n], self._native_labels(slot.labels[:n], n),
                    keys)
            if fallback:
                # evolved writer (or legacy-only bytes) at the cursor:
                # decode ONE chunk via the resolving message path, then
                # resume columnar
                msgs = self._poll_msgs()
                if msgs:
                    yield self._decode_msgs(msgs)
                continue
            if n == 0:
                return  # log end: same contract as an empty poll()

    def _poll_msgs(self):
        """One message-list poll, as a `fetch` phase."""
        self._take_polls = None  # not a take of poll_decoded calls alone
        with tracing.phase(None, "fetch"):
            return self.consumer.poll(self._poll_limit())

    def _decode_msgs(self, msgs):
        """Message-list decode (the fallback/oracle leg), as a `decode`
        phase: the only leg on which decode is a call of its own (the
        native legs fuse it into the fetch)."""
        with tracing.phase(None, "decode"):
            return self._decode_chunk(msgs)

    def _decode_chunk(self, msgs):
        """Trace forking, schema-evolution resolution, native-or-pure
        codec."""
        label_f = self.schema.label_field
        if any(m.value is None for m in msgs):
            # tombstones (compaction delete markers) carry no payload:
            # skipped here exactly like the columnar decoder skips them
            # natively — and the schema-guard fallbacks route tombstone-
            # bearing chunks through THIS leg, so it must not choke
            msgs = [m for m in msgs if m.value is not None]
            if not msgs:
                empty = np.zeros((0, self.schema.num_sensors))
                return self._emit_chunk(
                    empty, np.full((0,), "", object),
                    np.zeros((0,), "S64") if self._capture_keys else None)
        if tracing.ENABLED:
            # the zero-copy paths have no per-message Python objects
            # (and no headers) — traces ride this decode path only
            pending, overflowed = self._pending_traces, 0
            for m in msgs:
                if m.headers:
                    ctx = tracing.from_headers(m.headers)
                    if ctx is None \
                            or ctx.trace_id in self._seen_traces:
                        continue  # epoch re-read: trace once
                    if len(self._seen_traces) < self._seen_traces_cap:
                        self._seen_traces.add(ctx.trace_id)
                    # fork: this pipeline closes its own copy; the
                    # shared header object stays open for other
                    # consumer groups of the same topic
                    fork = ctx.fork()
                    fork.mark("consume")
                    if len(pending) == pending.maxlen:
                        overflowed += 1
                    pending.append(fork)
            if overflowed:
                tracing.spans_dropped.inc(overflowed)
        n = len(msgs)
        keys = None
        if self._capture_keys:
            # vectorized truncation: numpy clips each key to the S63
            # itemsize in C (matching the native paths' stride-1 cut),
            # then widens to the shared S64 stride — no per-record
            # slicing in Python
            keys = np.asarray([m.key or b"" for m in msgs],
                              dtype="S63").astype("S64")
        if any(needs_resolution(m.value) for m in msgs):
            # schema evolution on a live topic: at least one record
            # in this chunk was written under a newer schema — the
            # positional v1 decode (python AND native) would mis-
            # read it, so the whole chunk takes the name-resolving
            # path projected onto the reader schema.  Rare by
            # construction (only during a fleet's rolling upgrade),
            # so the fast paths stay untouched for v1-only chunks.
            if self._resolving is None:
                from ..ops.avro import ResolvingCodec

                self._resolving = ResolvingCodec(self.schema)
            cols = self._resolving.decode_batch_framed(
                [m.value for m in msgs])
            num = self.codec.sensor_matrix(cols)
            labels = cols[label_f] if label_f \
                else np.full((n,), "", object)
        elif self._native is not None:
            num, lab = self._native.decode_batch(
                [m.value for m in msgs], strip=5)
            labels = self._native_labels(lab, n)
        else:
            raw = [strip_frame(m.value) for m in msgs]
            cols = self.codec.decode_batch(raw)
            num = self.codec.sensor_matrix(cols)  # [n, F] float64
            labels = cols[label_f] if label_f \
                else np.full((n,), "", object)
        return self._emit_chunk(num, labels, keys)

    def _decoded_chunks(self):
        """Yield (xs [n, F] float32 normalized, labels [n] str,
        keys [n] bytes | None) per poll."""
        if self._columnar_ready():
            # Zero-copy columnar path: raw frame batches + the ONE
            # frame decoder + ring buffers (see _columnar_chunks).
            yield from self._columnar_chunks()
            if self._ring is not False:
                return
            # else: raw support vanished mid-stream; fall through
        fused_attr = "fetch_decode_keys" if self._capture_keys \
            else "fetch_decode"
        if self._native is not None and \
                getattr(self.consumer.broker, fused_attr, None) is not None:
            # Fused wire path: broker-side fetch + framing strip + Avro
            # decode in one C++ call (NativeKafkaBroker.fetch_decode) —
            # no per-message Python objects.  The old v1-only
            # LIMITATION is now a RUNTIME GUARD: the engine verifies
            # each frame's Confluent id against the evolved-schema band
            # before its strip=5 decode and raises SchemaIdMismatchError
            # at an evolved frame — that chunk detours through the
            # resolving Python path below, then the fused loop resumes.
            from ..stream.broker import SchemaIdMismatchError

            while True:
                limit = self._poll_limit()
                if self._take_polls is not None:
                    self._take_polls.append(limit)
                try:
                    with tracing.phase(None, "fetch"):
                        res = self.consumer.poll_decoded(
                            self._native, strip=5, max_messages=limit,
                            with_keys=self._capture_keys)
                except SchemaIdMismatchError:
                    msgs = self._poll_msgs()
                    if msgs:
                        yield self._decode_msgs(msgs)
                    continue
                num, lab = res[0], res[1]
                if len(num) == 0:
                    return
                yield self._emit_chunk(num,
                                       self._native_labels(lab, len(num)),
                                       res[2] if self._capture_keys else None)
        while True:
            msgs = self._poll_msgs()
            if not msgs:
                return
            yield self._decode_msgs(msgs)

    def _filtered_chunks(self):
        marker = self.exclude_key_marker
        for xs, labels, keys in self._decoded_chunks():
            if marker is not None and keys is not None and len(keys):
                # canary firewall: reserved-id records never batch
                keep = np.char.find(keys, marker) == -1
                if not keep.all():
                    xs, labels, keys = xs[keep], labels[keep], keys[keep]
            if marker is not None and not self.keep_keys:
                keys = None  # captured for the filter only
            if self.only_normal:
                keep = labels == "false"
                xs, labels = xs[keep], labels[keep]
                if keys is not None:
                    keys = keys[keep]
            if len(xs):
                yield xs, labels, keys

    def __iter__(self) -> Iterator[Batch]:
        if self.window:
            yield from self._windowed_iter()
            return
        B = self.batch_size
        parts: list = []  # pending (xs, labels, keys) chunks
        have = 0
        emitted = 0
        # index counts post-skip rows only, matching the reference's
        # OutputCallback `index = batch * batch_size` which starts at 0
        # after the skip slice (cardata-v3.py:243-249).
        index = 0

        def assemble():
            nonlocal parts, have
            if len(parts) > 1:
                xs = np.concatenate([p[0] for p in parts])
                labels = np.concatenate([p[1] for p in parts])
                keys = np.concatenate([p[2] for p in parts]) \
                    if parts[0][2] is not None else None
            else:
                xs, labels, keys = parts[0]
            parts = []
            have = 0
            return xs, labels, keys

        def emit(xs, labels, keys, lo):
            n_valid = min(B, len(xs) - lo)
            x = xs[lo:lo + n_valid].astype(np.float32, copy=True)
            if n_valid < B:
                x = np.concatenate([x, np.zeros((B - n_valid, x.shape[1]),
                                                np.float32)])
            lab = None
            if self.keep_labels:
                lab = np.empty((B,), object)
                lab[:n_valid] = labels[lo:lo + n_valid]
                lab[n_valid:] = ""
            ks = None
            if keys is not None:
                ks = np.zeros((B,), keys.dtype)
                ks[:n_valid] = keys[lo:lo + n_valid]
            return Batch(x, n_valid, 0, lab,
                         keys=ks)  # first_index patched by caller

        chunks = self._filtered_chunks()
        full = False
        self._take_polls = [] if self.take else None
        try:
            while True:
                if self.take:
                    # cap polling at what this bounded iteration can still
                    # batch: rows polled past the `take` boundary would
                    # advance the cursor and be lost to the caller
                    needed = (self.take - emitted
                              + max(self.skip - self._skipped, 0))
                    self._need_rows = needed * B - have
                try:
                    chunk = next(chunks)
                except StopIteration:
                    break
                parts.append(chunk)
                have += len(chunk[0])
                if have < B:
                    continue
                xs, labels, keys = assemble()
                lo = 0
                while len(xs) - lo >= B:
                    if self._skipped < self.skip:
                        self._skipped += 1
                    else:
                        b = emit(xs, labels, keys, lo)
                        b.first_index = index
                        yield b
                        emitted += 1
                        index += B
                        if self.take and emitted >= self.take:
                            full = True
                            return
                    lo += B
                if lo < len(xs):
                    parts = [(xs[lo:], labels[lo:],
                              keys[lo:] if keys is not None else None)]
                    have = len(xs) - lo
            if have and self.pad_tail and self._skipped >= self.skip and \
                    (not self.take or emitted < self.take):
                xs, labels, keys = assemble()
                b = emit(xs, labels, keys, 0)
                b.first_index = index
                yield b
        finally:
            self._take_ended(full)

    def _windowed_iter(self) -> Iterator[Batch]:
        """Sliding windows x=[B,T,F] with next-step targets y=[B,1,F].

        Reproduces dataset.window(look_back, shift=1) zipped with
        dataset.skip(look_back) (reference LSTM cardata-v1.py:184-190): the
        window starting at record i is paired with record i+look_back.

        Vectorized: windows materialize per decoded CHUNK via a strided
        view + one transpose-copy, not a Python ring per row — the row
        loop was the LSTM ingest bottleneck (10k windows ≈ seconds of
        pure interpreter time).
        """
        from numpy.lib.stride_tricks import sliding_window_view

        T = self.window
        B = self.batch_size
        carry = None          # last T rows: windows spanning chunk joints
        pend_x: list = []     # [n, T, F] window chunks awaiting batching
        pend_y: list = []     # [n, 1, F]
        have = 0
        emitted = 0
        index = 0

        def emit(wx, wy, lo):
            n_valid = min(B, len(wx) - lo)
            x = np.zeros((B, T, wx.shape[2]), np.float32)
            y = np.zeros((B, 1, wx.shape[2]), np.float32)
            x[:n_valid] = wx[lo:lo + n_valid]
            y[:n_valid] = wy[lo:lo + n_valid]
            return Batch(x, n_valid, 0, y=y)

        chunks = self._filtered_chunks()
        full = False
        self._take_polls = [] if self.take else None
        try:
            while True:
                if self.take:
                    needed = (self.take - emitted
                              + max(self.skip - self._skipped, 0))
                    # rows already in `carry` count toward the T lookahead
                    # a window needs — re-adding the full T every chunk
                    # would over-poll (and so permanently skip, for
                    # cursor-resuming callers) up to T-1 rows per round
                    covered = 0 if carry is None else len(carry)
                    self._need_rows = needed * B - have + max(T - covered,
                                                              0)
                try:
                    xs, _labels, _keys = next(chunks)
                except StopIteration:
                    break
                buf = xs.astype(np.float32, copy=False)
                if carry is not None and len(carry):
                    buf = np.concatenate([carry, buf])
                n_w = len(buf) - T  # windows with a next-step target
                if n_w <= 0:
                    carry = buf
                    continue
                # [n_w, T, F]: strided view (axis order [n, F, T]) then one
                # transpose-copy; y is the row T steps after each window
                wins = np.ascontiguousarray(
                    sliding_window_view(buf, T, axis=0)[:n_w]
                    .transpose(0, 2, 1))
                ys = buf[T: T + n_w][:, None, :]
                carry = buf[n_w:]
                pend_x.append(wins)
                pend_y.append(ys)
                have += n_w
                if have < B:
                    continue
                wx = np.concatenate(pend_x) if len(pend_x) > 1 else pend_x[0]
                wy = np.concatenate(pend_y) if len(pend_y) > 1 else pend_y[0]
                pend_x, pend_y = [], []
                have = 0
                lo = 0
                while len(wx) - lo >= B:
                    if self._skipped < self.skip:
                        self._skipped += 1
                    else:
                        b = emit(wx, wy, lo)
                        b.first_index = index
                        yield b
                        emitted += 1
                        index += B
                        if self.take and emitted >= self.take:
                            full = True
                            return
                    lo += B
                if lo < len(wx):
                    pend_x, pend_y = [wx[lo:]], [wy[lo:]]
                    have = len(wx) - lo
            if have and self.pad_tail and self._skipped >= self.skip and \
                    (not self.take or emitted < self.take):
                wx = np.concatenate(pend_x) if len(pend_x) > 1 else pend_x[0]
                wy = np.concatenate(pend_y) if len(pend_y) > 1 else pend_y[0]
                b = emit(wx, wy, 0)
                b.first_index = index
                yield b
        finally:
            self._take_ended(full)

    # ----------------------------------------------------------- tracing
    def take_traces(self) -> List["tracing.TraceContext"]:
        """Hand the traces decoded since the last call to the caller —
        the pipeline closer (train step / scorer) owns their close()."""
        out: List[tracing.TraceContext] = []
        while True:
            try:
                out.append(self._pending_traces.popleft())
            except IndexError:
                return out

    # --------------------------------------------------------- epoch API
    def reset(self):
        """Rewind for the next epoch (reference re-reads the topic per epoch,
        python-scripts/README.md:114-117)."""
        self.consumer.seek_to_start()
        self.records_seen = 0
        self._skipped = 0
        self._full_takes = 0  # a re-read of the slice, not the next take

    def epochs(self, n: int):
        """Yield epoch iterators with automatic rewind between them."""
        for e in range(n):
            if self.cache:
                if self._cached is None:
                    self._cached = list(iter(self))
                yield iter(self._cached)
                continue
            if e:
                self.reset()
            yield iter(self)
