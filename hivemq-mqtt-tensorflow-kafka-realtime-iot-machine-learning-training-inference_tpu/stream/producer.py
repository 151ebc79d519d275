"""Producers: the ordered KafkaOutputSequence equivalent and the
zero-copy RAW batch producer.

The reference writes predictions back with ``kafka_io.KafkaOutputSequence``
(cardata-v3.py:238-252): results are assigned an absolute *index* as batches
complete, and ``flush()`` publishes them in index order, so the output topic
preserves input-stream order even when batches finish out of order.  That
ordering contract is what lets downstream consumers join predictions back to
source offsets, so we keep it exactly: ``setitem(index, message)`` + ordered
``flush()``, with gap detection instead of silent misalignment.

``RawBatchProducer`` (ISSUE 12) is the write-path twin of the consume
side's FrameDecoder: a converted chunk is framed ONCE (natively, at
conversion) and the resulting raw frame batch ships over RAW_PRODUCE to
be appended segment-verbatim — with the documented fallback ladder
(IOTML_RAW_PRODUCE auto|on|off; an UNSUPPORTED_VERSION server pins the
producer back to classic PRODUCE permanently, exactly like the consume
side's RAW_FETCH pin-back).
"""

from __future__ import annotations

from typing import Dict, Optional

from ..obs import tracing
from ..obs.metrics import default_registry as _metrics
from .broker import Broker

#: write-plane telemetry — the produce-leg breakdown (convert+frame
#: seconds live with the encoder; these cover the append/ship leg)
raw_produce_records = _metrics.counter(
    "iotml_raw_produce_records_total",
    "records shipped as pre-framed RAW_PRODUCE batches")
raw_produce_fallbacks = _metrics.counter(
    "iotml_raw_produce_fallbacks_total",
    "producers pinned back to classic PRODUCE (UNSUPPORTED_VERSION)")
raw_produce_append_seconds = _metrics.histogram(
    "iotml_raw_produce_append_seconds",
    "RAW_PRODUCE ship+append latency per batch (the produce leg's "
    "append half)")
raw_produce_convert_seconds = _metrics.histogram(
    "iotml_raw_produce_convert_seconds",
    "convert+frame latency per raw batch (the produce leg's native "
    "JSON→Avro→frame half, observed by the fused converters)")


class OutputSequence:
    """Buffer of (index → message) flushed to a topic in index order."""

    def __init__(self, broker: Broker, topic: str,
                 partition: Optional[int] = None):
        self.broker = broker
        self.topic = topic
        self.partition = partition
        self._buf: Dict[int, bytes] = {}

    def setitem(self, index: int, message):
        if isinstance(message, str):
            message = message.encode()
        if index in self._buf:
            raise ValueError(f"duplicate output index {index}")
        self._buf[index] = message

    def __setitem__(self, index: int, message):
        self.setitem(index, message)

    def flush(self, allow_gaps: bool = False) -> int:
        """Publish buffered messages in ascending index order.

        Returns the number of messages flushed.  With allow_gaps=False
        (default) a missing index raises — an out-of-order scorer bug should
        fail loudly, not ship misaligned predictions.
        """
        if not self._buf:
            return 0
        idxs = sorted(self._buf)
        if not allow_gaps:
            lo, hi = idxs[0], idxs[-1]
            if hi - lo + 1 != len(idxs):
                missing = set(range(lo, hi + 1)) - set(idxs)
                raise ValueError(f"output sequence has gaps at {sorted(missing)[:8]}...")
        produce_many = getattr(self.broker, "produce_many", None)
        if produce_many is not None:
            # one batched call: over the Kafka wire a per-message produce
            # is a round trip each — a drain's flush would cost thousands
            # of them.  Order within the batch is preserved by contract.
            produce_many(self.topic, [(None, self._buf[i], 0) for i in idxs],
                         partition=self.partition)
        else:
            for i in idxs:
                self.broker.produce(self.topic, self._buf[i],
                                    partition=self.partition)
        n = len(idxs)
        self._buf.clear()
        return n


class RawBatchProducer:
    """Ship pre-framed raw batches to one topic, with the classic
    fallback ladder.

    The producer OWNS the plane decision per ``IOTML_RAW_PRODUCE``:

    - ``auto`` (default): try ``produce_raw``; the first
      NotImplementedError (extension-less server / relay) pins this
      producer back to classic ``produce_many`` permanently — the same
      one-way downgrade the consume side applies to RAW_FETCH.
    - ``on``: raw required — an extension-less server raises (the CI
      parity gate's mode: a silent fallback must fail, not degrade).
    - ``off``: classic everywhere (debug escape hatch).

    Redelivery stays caller-owned (RAW_PRODUCE is NOT idempotent);
    CorruptMessageError means nothing was appended — re-frame and
    resend.  Batches above IOTML_PRODUCE_BATCH_BYTES are the CALLER's
    job to split (frames only split at frame boundaries, which the
    encoder owns); `produce_frames` ships one pre-split batch.
    """

    def __init__(self, broker, topic: str, mode: Optional[str] = None):
        from ..data.pipeline import raw_produce_mode

        self.broker = broker
        self.topic = topic
        self.mode = raw_produce_mode() if mode is None else mode
        # plane state: None = undecided (auto), True = raw, False = classic
        self._raw: Optional[bool] = {"on": True, "off": False,
                                     "auto": None}[self.mode]
        self.raw_batches = 0
        self.classic_records = 0

    @property
    def engaged(self) -> Optional[bool]:
        """True = raw plane active, False = pinned classic, None = not
        yet decided (auto, before the first batch)."""
        return self._raw

    def produce_frames(self, partition: int, frames: bytes,
                       count: int, entries=None) -> int:
        """Ship one pre-framed batch to `partition`; returns the batch's
        base offset.  `entries` ([(key, value, ts[, headers])]) is the
        classic-fallback form of the same records — REQUIRED in auto
        mode (the downgrade re-ships the exact records); omit it only
        under mode='on', where fallback is an error by contract."""
        import time

        if self._raw is False:
            return self._classic(partition, entries)
        produce_raw = getattr(self.broker, "produce_raw", None)
        if produce_raw is None:
            self._pin_classic()
            return self._classic(partition, entries)
        ctx = None
        if tracing.ENABLED:
            # wire-trace leg (ISSUE 13): a SAMPLED batch carries one
            # trace context in its first frame's headers — the frame
            # field survives RAW_PRODUCE, the segment, replica mirrors
            # and RAW_FETCH verbatim, so the batch's journey is
            # reconstructable across processes.  Cost: one record
            # re-encode per sampled batch, zero on unsampled ones.
            ctx = tracing.start("raw_produce")
            if ctx is not None:
                from ..ops.framing import stamp_first_frame

                frames = stamp_first_frame(
                    frames, ((tracing.HEADER_KEY, ctx),))
        try:
            t0 = time.perf_counter()
            base = produce_raw(self.topic, partition, frames)
            raw_produce_append_seconds.observe(time.perf_counter() - t0)
        except NotImplementedError:
            self._pin_classic()
            return self._classic(partition, entries)
        if ctx is not None:
            tracing.mark_batch(ctx, "raw_produce_append", self.topic,
                               partition, base, base + count - 1, count)
        self._raw = True
        self.raw_batches += 1
        raw_produce_records.inc(count)
        return base

    def _pin_classic(self) -> None:
        if self.mode == "on":
            raise NotImplementedError(
                f"IOTML_RAW_PRODUCE=on but the broker for "
                f"{self.topic!r} lacks the RAW_PRODUCE extension")
        if self._raw is not False:
            self._raw = False
            raw_produce_fallbacks.inc()

    def _classic(self, partition: int, entries) -> int:
        if entries is None:
            raise NotImplementedError(
                f"RAW_PRODUCE unavailable for {self.topic!r} and no "
                f"classic-fallback entries were provided")
        if callable(entries):
            entries = entries()  # built lazily: the fallback form costs
            # a per-record encode, paid only when actually downgrading
        last = self.broker.produce_many(self.topic, entries,
                                        partition=partition)
        self.classic_records += len(entries)
        return last - len(entries) + 1
