"""Offset-cursored stream consumer — the KafkaDataset equivalent.

The reference consumes with ``kafka_io.KafkaDataset(["topic:partition:offset"],
group=..., eof=True)`` (cardata-v3.py:46-47): an absolute-offset cursor over
one partition, EOF when the log end is reached, re-readable from the same
offset every epoch (the reference re-reads the topic per epoch,
python-scripts/README.md:114-117).

`StreamConsumer` reproduces those semantics over any broker duck-type
(emulator or native engine) and adds what the reference lacked: explicit
multi-partition specs, committed-offset resume, and a `seek` for epoch
re-reads without reconstructing the pipeline.

Positions are DELIVERED positions.  `read_ahead` may fetch the next
take's records while the caller computes, as Kafka's own consumer does
below `position()`; a record fetched and not yet delivered is invisible
to `positions()`, `commit()`, `record_lag()` and every checkpoint.
"""

from __future__ import annotations

import copy
import threading
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

from struct import error as struct_error

from ..obs import metrics as obs_metrics
from ..obs import tracing, watermark
from .broker import (Broker, Message, OffsetOutOfRangeError,
                     SchemaIdMismatchError)


def parse_spec(spec: str) -> tuple:
    """Parse the reference's "topic:partition:offset" subscription string."""
    parts = spec.split(":")
    if len(parts) == 1:
        return parts[0], 0, 0
    if len(parts) == 2:
        return parts[0], int(parts[1]), 0
    return parts[0], int(parts[1]), int(parts[2])


class _Ahead(NamedTuple):
    """One poll run ahead: the cursor state (`StreamConsumer._where`) it
    started from and left behind, and what it asked for and got."""

    before: tuple
    max_messages: int
    out: tuple  # the arrays `poll_decoded` returned
    after: tuple


class _ReadAhead:
    """One take's polls, run ahead of the caller on a copy of the
    cursors: `entries` holds them oldest first.  The fetching thread
    appends under `lock` until `dropped`; the consumer's thread joins
    it, pops the head, or drops the lot."""

    __slots__ = ("key", "entries", "thread", "lock", "dropped")

    def __init__(self, key: tuple):
        self.key = key  # (codec, strip, with_keys) of every entry
        self.entries: List[_Ahead] = []
        self.thread: Optional[threading.Thread] = None
        self.lock = threading.Lock()
        self.dropped = False


class StreamConsumer:
    """Cursor over one or more (topic, partition) logs.

    Its positions are delivered positions: what `read_ahead` has fetched
    and no poll has returned yet moved no cursor.

    Args:
      broker: broker duck-type (`fetch`, `end_offset`, `commit`, `committed`).
      specs: "topic:partition:offset" strings (reference subscription format).
      group: consumer-group id for offset commits.
      eof: if True, `poll` returns [] once all cursors hit the log end
           (reference eof=True batch-mode); if False, callers may poll again
           as data arrives (continuous scoring mode).
    """

    #: the take `read_ahead` fetched and no poll has returned yet
    _ahead: Optional[_ReadAhead] = None

    def __init__(self, broker: Broker, specs: Sequence[str],
                 group: str = "iotml", eof: bool = True):
        self.broker = broker
        self.group = group
        self.eof = eof
        self._cursors = []  # [topic, partition, next_offset]
        for s in specs:
            t, p, o = parse_spec(s)
            self._cursors.append([t, p, o])
        self._start = [c[2] for c in self._cursors]
        self._rr = 0
        # event-time accounting (ISSUE 13): per-(topic, partition)
        # [min_ts, max_ts] of records consumed since the last
        # take_event_time() — the consume paths fold decoder-reported
        # (columnar) or message (classic) timestamps in at batch
        # granularity; processing stages (scorer/trainer/twin) take the
        # ranges at their drain/commit boundary and publish the
        # ingest→stage watermark lag.
        self._event_ts: dict = {}
        # batch-granular trace contexts extracted from RAW batch frame
        # headers (the wire-trace leg): bounded, drained by the batcher
        import collections

        self._batch_traces: "collections.deque" = collections.deque(
            maxlen=1024)

    @classmethod
    def from_committed(cls, broker: Broker, topic: str, partitions: Sequence[int],
                       group: str, fallback_offset: int = 0, **kw):
        """Resume from committed group offsets (cursor-checkpoint restart)."""
        specs = []
        for p in partitions:
            off = broker.committed(group, topic, p)
            specs.append(f"{topic}:{p}:{off if off is not None else fallback_offset}")
        return cls(broker, specs, group=group, **kw)

    def rewind_to_committed(self) -> None:
        """Reset in-memory cursors to the last committed offsets (or the
        original start offsets when nothing was committed).  Used when a
        processing round aborts mid-chunk: `poll` has already advanced the
        cursors, so without a rewind the failed records would be silently
        skipped; rewinding retries them next round (at-least-once)."""
        self._drop_ahead()
        for i, cur in enumerate(self._cursors):
            topic, part, _ = cur
            off = self.broker.committed(self.group, topic, part)
            cur[2] = off if off is not None else self._start[i]

    # ------------------------------------------- event-time watermarks
    def _note_event_ts(self, topic: str, part: int,
                       ts_min: int, ts_max: int) -> None:
        """Fold one consumed batch's event-time bounds into the
        per-partition accumulation AND publish the consume-stage
        watermark — batch-granular, the columnar plane's substitute for
        per-record spans (ISSUE 13)."""
        if ts_max is None or ts_max < 0:
            return
        lo = ts_min if ts_min is not None and ts_min >= 0 else ts_max
        cur = self._event_ts.get((topic, part))
        if cur is None:
            self._event_ts[(topic, part)] = [lo, ts_max]
        else:
            if lo < cur[0]:
                cur[0] = lo
            if ts_max > cur[1]:
                cur[1] = ts_max
        # group-labeled: a trainer and a scorer consuming the same
        # partition in one process are different frontiers — without
        # the group the gauge would flap between them
        watermark.observe("consume", topic, part, lo, ts_max,
                          group=self.group)

    def take_event_time(self) -> dict:
        """{(topic, partition): (ts_min, ts_max)} of event time consumed
        since the last take, cleared on read — the processing stage's
        half of the watermark contract: take at the drain/commit
        boundary (where consumed == processed) and hand the ranges to
        ``watermark.observe_taken(stage, ...)``."""
        out = {k: tuple(v) for k, v in self._event_ts.items()}
        self._event_ts.clear()
        return out

    def take_batch_traces(self) -> list:
        """Drain batch-granular trace contexts extracted from RAW batch
        frame headers (the wire-trace leg): the batcher appends them to
        its pending set so the pipeline closer (scorer / train step)
        closes them with the e2e span, exactly like record traces."""
        out: list = []
        while True:
            try:
                out.append(self._batch_traces.popleft())
            except IndexError:
                return out

    def record_lag(self, cached_only: bool = False) -> int:
        """Refresh ``iotml_consumer_lag_records{group,topic,partition}``
        from the high-water mark and return the total lag.  Wire
        brokers answer from the hwm CACHED off every fetch response —
        classic FETCH and RAW_FETCH both carry it (zero extra round
        trips); otherwise one ``end_offset`` read per partition —
        called at commit/drain granularity and, ``cached_only``, once at
        the end of every poll: there a wire broker is asked nothing (a
        partition it has no cached hwm for is skipped; an in-process
        broker's ``end_offset`` is a local read), so the gauge moves
        through a live window, not only at its commits.  Never per
        record.  This is TELEMETRY riding the read and commit paths: no
        failure here may crash a drain, so anything the broker throws
        (dead socket, transient wire error, racing topic deletion)
        degrades to a skipped refresh."""
        total = 0
        hwm_of = getattr(self.broker, "last_hwm", None)
        for topic, part, off in self._cursors:
            try:
                hwm = hwm_of(topic, part) if hwm_of is not None else None
                if hwm is None:
                    if cached_only and hwm_of is not None:
                        continue
                    hwm = self.broker.end_offset(topic, part)
            except (KeyError, RuntimeError, OSError):
                # OSError covers ConnectionError AND socket timeouts;
                # RuntimeError is the wire client's non-OK error answer
                continue
            lag = max(int(hwm) - int(off), 0)
            total += lag
            obs_metrics.consumer_lag_records.set(
                lag, group=self.group, topic=topic, partition=part)
        return total

    def _fetch_autoreset(self, topic: str, part: int, off: int,
                         max_messages: int) -> tuple:
        """One broker fetch with the documented out-of-range policy:
        a cursor below the retained base (retention trimmed the head
        past it) auto-resets to EARLIEST — `auto.offset.reset=earliest`
        semantics, counted in iotml_consumer_autoresets_total so a
        consumer chronically outrun by retention is visible.  Returns
        (batch, effective_offset)."""
        for _ in range(4):  # retention may trim again between the calls
            try:
                return self.broker.fetch(topic, part, off, max_messages), off
            except OffsetOutOfRangeError as e:
                off = max(e.earliest, self.broker.begin_offset(topic, part))
                obs_metrics.consumer_autoresets.inc(topic=topic)
                self._drop_ahead()
        # chronically outrun by retention (it trimmed past every reset):
        # an empty batch with the cursor parked at the last-known
        # earliest keeps the documented contract — poll() never raises
        # for trimmed history, the next poll resumes the chase
        return [], off

    # --------------------------------------------------------------- read
    def poll(self, max_messages: int = 1024) -> List[Message]:
        """Fetch up to max_messages across cursors (round-robin between
        partitions so one hot partition cannot starve the rest).  A
        cursor stranded below the retained base auto-resets to earliest
        (see _fetch_autoreset)."""
        out: List[Message] = []
        n = len(self._cursors)
        attempts = 0
        while len(out) < max_messages and attempts < n:
            cur = self._cursors[self._rr % n]
            self._rr += 1
            attempts += 1
            topic, part, off = cur
            batch, off = self._fetch_autoreset(topic, part, off,
                                               max_messages - len(out))
            cur[2] = off  # an auto-reset moved the cursor even if empty
            if batch:
                cur[2] = batch[-1].offset + 1
                out.extend(batch)
                attempts = 0  # progress was made; give others another chance
                # true min/max over the batch — event timestamps are
                # NOT append-monotone (a flap-recovered car's store-and-
                # forward buffer appends old event times after fresh
                # ones), and endpoint sampling would hide exactly those
                # records' lag.  O(n) attribute reads over an already-
                # materialised message list; the columnar path gets the
                # same bounds from the decoder's walk for free.
                self._note_event_ts(
                    topic, part,
                    min(m.timestamp_ms for m in batch),
                    max(m.timestamp_ms for m in batch))
                tracing.touch("consume")
        if out:
            # batch-shape telemetry: a drifting-down batch size under
            # constant load means the consumer is outpacing the producers
            # (or fetches are being truncated) — only non-empty polls
            # observe, so idle polling does not flood the 1-bucket
            obs_metrics.fetch_batch_size.observe(len(out))
        self.record_lag(cached_only=True)
        return out

    def poll_decoded(self, codec, strip: int = 5, max_messages: int = 4096,
                     with_keys: bool = False):
        """Fused native poll: fetch + framing strip + Avro decode in one
        C++ call per partition (broker `fetch_decode`, the KafkaDataset-
        equivalent hot path).  Returns (numeric [n, F] float64, labels
        [n, S] bytes) — with `with_keys`, (numeric, labels, keys [n]
        bytes) — or None when this broker has no native decode path (for
        with_keys that includes brokers without `fetch_decode_keys`);
        n == 0 signals the same end-of-poll as an empty `poll()`.

        A poll that `read_ahead` already ran from this very cursor state
        returns that run's arrays and adopts its cursors: rows, order
        and positions after every poll are the unbuffered consumer's."""
        fd = self._fused_leg(with_keys)
        if fd is None:
            return None
        out = result = None
        if self._ahead is not None:
            out = self._take_ahead((codec, strip, with_keys), max_messages)
            result = "miss" if out is None else "hit"
        if out is None:
            out = self._poll_decoded(fd, codec, strip, max_messages,
                                     with_keys)
        if result:
            obs_metrics.consumer_readahead_rows.inc(len(out[0]),
                                                    result=result)
        self.record_lag(cached_only=True)
        return out

    def _fused_leg(self, with_keys: bool):
        """The broker's fused fetch + decode call, or None without one."""
        return getattr(self.broker,
                       "fetch_decode_keys" if with_keys else "fetch_decode",
                       None)

    def _poll_decoded(self, fd, codec, strip: int, max_messages: int,
                      with_keys: bool, autoreset: bool = True):
        """`poll_decoded`'s round over the partitions, on this object's
        cursors and `_rr`: the consumer's own, or the copy a read-ahead
        runs on (`autoreset` False there: a cursor below the retained
        base is the foreground poll's to reset and count)."""
        nums, labs, keys = [], [], []
        got = 0
        n = len(self._cursors)
        attempts = 0
        while got < max_messages and attempts < n:
            cur = self._cursors[self._rr % n]
            self._rr += 1
            attempts += 1
            topic, part, off = cur
            try:
                res = fd(topic, part, off, codec, strip=strip,
                         max_rows=max_messages - got)
            except OffsetOutOfRangeError as e:
                if not autoreset:
                    raise
                # same documented auto-reset-to-earliest as poll(): the
                # fused native path must not turn a retention trim into
                # a crashed trainer/scorer loop
                cur[2] = max(e.earliest,
                             self.broker.begin_offset(topic, part))
                obs_metrics.consumer_autoresets.inc(topic=topic)
                continue
            except SchemaIdMismatchError:
                # the runtime guard behind the blind strip=5 decode: an
                # evolved writer's frame sits at the cursor.  Return
                # whatever decoded BEFORE it (cursors already stop
                # there); with nothing decoded, surface the signal so
                # the batcher takes its resolving-Python chunk.
                if got:
                    break
                raise
            numeric, labels = res[0], res[1]
            next_off = res[-1]
            if len(numeric):
                cur[2] = next_off
                nums.append(numeric)
                labs.append(labels)
                if with_keys:
                    keys.append(res[2])
                got += len(numeric)
                attempts = 0
        if not nums:
            from .native import LABEL_STRIDE

            empty = (np.zeros((0, codec.n_numeric)),
                     np.zeros((0, codec.n_strings), f"S{LABEL_STRIDE}"))
            return empty + (np.zeros((0,), "S1"),) if with_keys else empty
        out = (np.concatenate(nums), np.concatenate(labs))
        return out + (np.concatenate(keys),) if with_keys else out

    # --------------------------------------------------------- read-ahead
    def _where(self) -> tuple:
        """The cursor state a poll starts from and leaves behind."""
        return tuple(c[2] for c in self._cursors), self._rr

    def read_ahead(self, requests: Sequence[int], codec, strip: int = 5,
                   with_keys: bool = False) -> None:
        """Fetch the next take while the caller computes: ONE short-lived
        thread runs `poll_decoded`'s round once for each of `requests`
        (the `max_messages` of the polls to come, in order) on a COPY of
        the cursors and keeps what each returned.  No cursor of this
        consumer moves; `poll_decoded` hands an entry on only to the
        very poll it replays (`_take_ahead`), so what is delivered, and
        in what order, is what it would have been.  An empty result is
        never kept (it means end of stream to the caller, who asks
        again), and whatever the thread raises ends it silently: the
        foreground poll meets the same condition and handles it as
        documented.  At most one take is held."""
        fd = self._fused_leg(with_keys)
        if fd is None or not requests:
            return
        from ..supervise.registry import register_thread

        self._drop_ahead()
        ahead = _ReadAhead((codec, strip, with_keys))
        shadow = copy.copy(self)
        shadow._cursors = [list(c) for c in self._cursors]
        shadow._ahead = None
        ahead.thread = register_thread(threading.Thread(
            target=self._fetch_ahead,
            args=(ahead, shadow, fd, list(requests)),
            name="iotml-consumer-read-ahead", daemon=True))
        ahead.thread.start()
        self._ahead = ahead

    @staticmethod
    def _fetch_ahead(ahead: _ReadAhead, shadow: "StreamConsumer", fd,
                     requests: List[int]) -> None:
        """The read-ahead thread: `shadow` is a copy of the consumer
        whose cursors only this thread moves."""
        codec, strip, with_keys = ahead.key
        late = 0  # rows that arrived after the drop
        try:
            for max_messages in requests:
                if ahead.dropped:
                    break
                before = shadow._where()
                # outside any loop's phase: iotml.stream.fetch — the
                # trainer's own loop reads only what IT still waits for
                with tracing.phase(None, "fetch"):
                    out = shadow._poll_decoded(fd, codec, strip,
                                               max_messages, with_keys,
                                               autoreset=False)
                if not len(out[0]):
                    break
                with ahead.lock:
                    if ahead.dropped:
                        late = len(out[0])
                        break
                    ahead.entries.append(
                        _Ahead(before, max_messages, out, shadow._where()))
        except Exception:  # noqa: BLE001 - the foreground poll meets it
            pass
        if late:
            obs_metrics.consumer_readahead_rows.inc(late, result="dropped")

    def _take_ahead(self, key: tuple, max_messages: int):
        """The head entry's arrays if this poll is the one it replays —
        same codec, strip and keys, same `max_messages`, and the cursors
        and `_rr` it started from — else None, with the rest dropped."""
        ahead = self._ahead
        ahead.thread.join()
        head = ahead.entries[0] if ahead.entries else None
        if head is None or key != ahead.key or \
                head.max_messages != max_messages or \
                head.before != self._where():
            self._drop_ahead()
            return None
        del ahead.entries[0]
        if not ahead.entries:
            self._ahead = None
        offsets, self._rr = head.after
        for cur, off in zip(self._cursors, offsets):
            cur[2] = off
        return head.out

    def _drop_ahead(self) -> None:
        """Forget what was fetched ahead, and whatever a fetch still in
        flight brings: called wherever a cursor moves other than by a
        delivery.  The records stay in the log and the cursors never
        passed them, so nothing is lost; the rows are counted."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return
        with ahead.lock:
            ahead.dropped = True
            rows = sum(len(e.out[0]) for e in ahead.entries)
            ahead.entries.clear()
        if rows:
            obs_metrics.consumer_readahead_rows.inc(rows, result="dropped")

    def poll_into(self, decoder, out_numeric, out_labels, out_keys=None,
                  max_rows: int = 4096, max_bytes: int = 1 << 20):
        """Columnar poll over RAW frame batches — THE zero-copy hot path
        and the ONE decode entry point for live consume and timestamp-
        replay backfill alike (a backfill is just this after
        ``seek_to_timestamp``).

        Fetches contiguous store-format frames (`Broker.fetch_raw` /
        wire RAW_FETCH) and decodes them straight into the CALLER-OWNED
        preallocated buffers via `decoder` (stream.native.FrameDecoder):
        zero per-record Python objects end to end.

        Returns ``(rows, fallback)`` — rows decoded into the buffers
        (cursors advanced past exactly those), and ``fallback=True``
        when the cursor is parked on a chunk the raw path must not
        decode (an evolved writer's schema id, or bytes only the
        resolving/legacy path can handle): the caller takes ONE legacy
        poll chunk and re-enters.  Returns None when the broker has no
        raw-batch support (callers use the legacy paths).  A cursor
        below the retained base auto-resets to earliest like poll()."""
        fr = getattr(self.broker, "fetch_raw", None)
        if fr is None or getattr(self, "_raw_unsupported", False):
            return None
        from .native import FRAMES_STOP_SCHEMA, FRAMES_STOP_TORN

        rows = 0
        n = len(self._cursors)
        attempts = 0
        while rows < max_rows and attempts < n:
            cur = self._cursors[self._rr % n]
            self._rr += 1
            attempts += 1
            topic, part, off = cur
            raw = None
            for _ in range(4):  # same retry envelope as _fetch_autoreset
                try:
                    raw = fr(topic, part, off, max_bytes=max_bytes)
                    break
                except NotImplementedError:
                    # wire server without the RAW_FETCH extension:
                    # remember and hand the caller back to the legacy
                    # paths for good (rows already decoded are
                    # returned, their cursors are final)
                    self._raw_unsupported = True
                    return (rows, False) if rows else None
                except OffsetOutOfRangeError as e:
                    # documented auto-reset-to-earliest, then RETRY the
                    # fetch at the reset cursor — a retention trim must
                    # not surface as a phantom end-of-stream
                    off = max(e.earliest,
                              self.broker.begin_offset(topic, part))
                    cur[2] = off
                    obs_metrics.consumer_autoresets.inc(topic=topic)
                    self._drop_ahead()
            if raw is None:
                continue
            got, next_off, flags, _skipped = decoder.decode_into(
                raw.data, off,
                out_numeric[rows:], out_labels[rows:],
                out_keys[rows:] if out_keys is not None else None,
                cap_rows=max_rows - rows)
            if got or next_off > off:
                # progress: decoded rows and/or skipped tombstones.
                # Event-time bounds fall out of the decoder's frame walk
                # for free (ISSUE 13): fold them into the watermark and
                # beat the consume-stage liveness — the batch-granular
                # telemetry the zero-record path otherwise cannot have.
                cur[2] = next_off
                rows += got
                attempts = 0
                self._note_event_ts(topic, part,
                                    getattr(decoder, "last_ts_min", -1),
                                    getattr(decoder, "last_ts_max", -1))
                if tracing.ENABLED:
                    tracing.touch("consume")
                    self._extract_batch_trace(raw, topic, part, off,
                                              next_off, got)
                continue
            if flags & FRAMES_STOP_SCHEMA:
                # evolved writer at the cursor: the caller resolves this
                # chunk by name in Python, then resumes columnar
                return rows, True
            if flags & FRAMES_STOP_TORN:
                # parked on bytes the raw scan can't cross: distinguish
                # a recovery hole (probe jumps it), a decodable-by-
                # legacy record (fall back for one chunk), and an
                # in-flight partial append (no data yet).  One bounded
                # 1-record probe — never per-record work.
                probe, eff = self._fetch_autoreset(topic, part, off, 1)
                cur[2] = eff
                if probe and probe[0].offset > eff:
                    cur[2] = probe[0].offset  # hole jumped; retry raw
                    continue
                if probe:
                    return rows, True
        if rows:
            obs_metrics.fetch_batch_size.observe(rows)
        self.record_lag(cached_only=True)
        return rows, False

    def _extract_batch_trace(self, raw, topic: str, part: int,
                             first_off: int, next_off: int,
                             got: int) -> None:
        """Wire-trace leg (ISSUE 13): a SAMPLED raw batch carries a
        trace context in its first frame's headers — ONE bounded
        first-frame parse per RAW fetch (only under tracing), never a
        batch walk.  The context is marked `consume` with the batch's
        offset range and held for the pipeline closer (scorer / train
        step) to close with its e2e span.  Gated at the cursor: a
        sparse-index-aligned re-serve of the batch head (first frame
        below `first_off`) is NOT a new batch — re-extracting it would
        close the same trace once per slice."""
        from ..ops.framing import first_frame_headers

        try:
            hdrs = first_frame_headers(raw.data, at_or_after=first_off)
        except (ValueError, struct_error):
            return
        ctx = tracing.from_headers(hdrs)
        if ctx is None:
            return
        tracing.mark_batch(ctx, "consume", topic, part, first_off,
                           next_off - 1, got)
        if len(self._batch_traces) == self._batch_traces.maxlen:
            # bounded like the batcher's pending set, and COUNTED like
            # it: a drill losing its cross-process traces to this bound
            # must show counter evidence of why
            tracing.spans_dropped.inc()
        self._batch_traces.append(ctx)

    def at_end(self) -> bool:
        return all(off >= self.broker.end_offset(t, p)
                   for t, p, off in self._cursors)

    def __iter__(self):
        """Iterate to EOF (reference eof=True semantics)."""
        while True:
            batch = self.poll()
            if not batch:
                if self.eof or self.at_end():
                    return
            yield from batch

    # ------------------------------------------------------------- cursor
    def seek_to_start(self):
        """Rewind to the construction offsets (per-epoch stream re-read)."""
        self._drop_ahead()
        for cur, off in zip(self._cursors, self._start):
            cur[2] = off

    def seek_to_timestamp(self, timestamp_ms: int) -> None:
        """Move every cursor to the first record at/after `timestamp_ms`
        (the broker's timestamp index / ListOffsets-by-timestamp) — the
        replay entry point for training backfill.  Brokers without the
        replay API (native engine) leave the cursors untouched."""
        oft = getattr(self.broker, "offset_for_timestamp", None)
        if oft is None:
            return
        self._drop_ahead()
        for cur in self._cursors:
            cur[2] = oft(cur[0], cur[1], timestamp_ms)

    def seek(self, topic: str, partition: int, offset: int):
        self._drop_ahead()
        for cur in self._cursors:
            if cur[0] == topic and cur[1] == partition:
                cur[2] = offset
                return
        raise KeyError((topic, partition))

    def positions(self) -> List[tuple]:
        """Current (topic, partition, next_offset) cursor state — this tuple
        is the stream-side resume checkpoint (SURVEY §5 'offset is the resume
        cursor').  Delivered positions: a record `read_ahead` fetched and
        no poll returned has moved nothing here."""
        return [tuple(c) for c in self._cursors]

    def commit(self):
        """Commit the delivered positions (`positions()`), never what
        `read_ahead` holds: a crash after it re-reads those records."""
        # commit is the drain boundary — the batch-granular spot to
        # refresh the first-class lag gauge (ISSUE 13 satellite)
        self.record_lag()
        with obs_metrics.commit_seconds.time():
            commit_many = getattr(self.broker, "commit_many", None)
            if commit_many is not None:
                # one request per topic instead of one per partition — over
                # the wire each commit is a round trip into the broker
                # process
                by_topic: dict = {}
                for t, p, off in self._cursors:
                    by_topic.setdefault(t, []).append((p, off))
                for t, entries in by_topic.items():
                    commit_many(self.group, t, entries)
                return
            for t, p, off in self._cursors:
                self.broker.commit(self.group, t, p, off)
