"""iotml.store — segmented log, crash recovery, offsets, replay, and
the broker/wire/consumer integration of the durable backend.

Recovery edge cases follow the ISSUE-5 checklist: torn tail record,
empty tail segment (death right after a roll), index/log mismatch
rebuilt from the log, and byte-identical replay after recovery (seeded
via the chaos schedule machinery, so the corruption pattern replays)."""

import os
import random
import struct

import pytest

from iotml.store import (OffsetsFile, SegmentedLog, SegmentWriter,
                         StorePolicy, crc32c)
from iotml.store import segment as seg
from iotml.store.segment import _crc32c_py
from iotml.stream.broker import Broker, OffsetOutOfRangeError


def _fill(log, n, ts0=1000, payload=b"v"):
    for i in range(n):
        log.append(f"k{i}".encode() if i % 3 else None,
                   payload + str(i).encode(), ts0 + i)


def _dump(log):
    return log.read_from(log.base_offset, 10 ** 6)


# ------------------------------------------------------------- framing
def test_crc32c_known_answer_and_fast_path_parity():
    # the canonical CRC32C check value ("123456789" -> 0xE3069283)
    assert _crc32c_py(b"123456789") == 0xE3069283
    rng = random.Random(7)
    for _ in range(64):
        blob = bytes(rng.randrange(256) for _ in range(rng.randrange(200)))
        assert crc32c(blob) == _crc32c_py(blob)


def test_record_roundtrip_with_and_without_headers():
    hdrs = (("iotml_trace", b"wire-bytes"), ("other", "strval"))
    frame = seg.encode_record(42, b"key", b"value", 1234, hdrs)
    rows = list(seg.scan_records(frame))
    assert len(rows) == 1
    _pos, end, off, key, value, ts, got = rows[0]
    assert (off, key, value, ts) == (42, b"key", b"value", 1234)
    assert got == (("iotml_trace", b"wire-bytes"), ("other", b"strval"))
    assert end == len(frame)
    # null key, no headers
    frame2 = seg.encode_record(0, None, b"v", 0, None)
    (_p, _e, off, key, value, ts, hdrs2), = seg.scan_records(frame2)
    assert key is None and hdrs2 is None


def test_scan_stops_at_corrupt_frame():
    a = seg.encode_record(0, None, b"a", 1, None)
    b = seg.encode_record(1, None, b"b", 2, None)
    flipped = bytearray(a + b)
    flipped[-1] ^= 0xFF  # corrupt b's payload: its CRC must fail
    rows = list(seg.scan_records(bytes(flipped)))
    assert [r[2] for r in rows] == [0]


def test_segment_writer_rejects_bad_fsync_policy(tmp_path):
    with pytest.raises(ValueError, match="never|interval|always"):
        SegmentWriter(str(tmp_path / "x.log"), fsync="sometimes")
    with pytest.raises(ValueError):
        StorePolicy(fsync="bogus")


# ------------------------------------------------------ log + recovery
def test_two_writers_of_one_path_each_publish_a_whole_file(tmp_path):
    """`atomic_write`'s temporary is the call's own: two threads that
    write one path a few hundred times never find the other's rename
    has taken their file, a reader sees one whole blob or the other at
    every moment, and no temporary is left behind."""
    import threading

    path = str(tmp_path / "sidecar.index")
    blobs = [bytes([i]) * (4096 + i) for i in (1, 2)]
    failed = []

    def write(blob):
        try:
            for _ in range(300):
                seg.atomic_write(path, blob, fsync=False)
                with open(path, "rb") as fh:
                    assert fh.read() in blobs
        except BaseException as exc:   # a thread's failure is the test's
            failed.append(exc)

    threads = [threading.Thread(target=write, args=(b,)) for b in blobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failed
    with open(path, "rb") as fh:
        assert fh.read() in blobs
    assert os.listdir(tmp_path) == ["sidecar.index"]


def test_a_write_that_fails_leaves_no_temporary(tmp_path):
    (tmp_path / "taken").mkdir()   # a rename onto a directory fails
    with pytest.raises(OSError):
        seg.atomic_write(str(tmp_path / "taken"), b"blob", fsync=False)
    assert os.listdir(tmp_path) == ["taken"]


def test_roll_retention_and_sparse_index(tmp_path):
    pol = StorePolicy(fsync="never", segment_bytes=300,
                      index_interval_bytes=128)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 60)
    assert len(log._segments) > 3          # rolled by bytes
    assert log.end_offset == 60
    # the sparse index is sparse: far fewer entries than records
    assert 0 < len(log.index_entries()) < 20
    # reads seek through segments and honor max_records
    chunk = log.read_from(17, 5)
    assert [r[0] for r in chunk] == [17, 18, 19, 20, 21]
    # retention by bytes drops whole sealed head segments
    log.policy.retention_bytes = 600
    dropped = log.enforce_retention()
    assert dropped > 0 and log.base_offset == dropped
    with pytest.raises(LookupError):
        log.read_from(0)
    log.close()


def test_retention_by_age_against_newest_timestamp(tmp_path):
    pol = StorePolicy(fsync="never", segment_bytes=200, retention_ms=50)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 30, ts0=1000)   # ts 1000..1029
    assert log.enforce_retention() == 0  # all within 50ms of newest
    log.append(None, b"new", 5000)
    dropped = log.enforce_retention()
    assert dropped > 0
    # the active segment (holding ts=5000) always survives
    assert any(r[3] == 5000 for r in _dump(log))
    log.close()


def test_recovery_truncates_torn_tail_and_replays_byte_identically(tmp_path):
    """Seeded via the chaos schedule machinery: the scenario's RNG picks
    the torn-blob shape, so the corruption pattern itself replays."""
    from iotml.chaos.scenarios import build

    sched = build("broker-crash-recover", seed=13, records=100)
    rng = random.Random(sched.seed)
    pol = StorePolicy(fsync="always", segment_bytes=400)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 40)
    before = _dump(log)
    torn = bytes(rng.randrange(256) for _ in range(rng.randrange(8, 64)))
    n = log.simulate_torn_write(struct.pack(">I", 1 << 30) + torn)
    # no close(): the process "dies" here
    log2 = SegmentedLog(str(tmp_path), pol)
    assert log2.recovered_truncated_bytes == n
    assert _dump(log2) == before            # byte-identical replay
    assert log2.append(None, b"after", 9999) == 40  # appends continue
    log2.close()
    # a second mount is clean: recovery is idempotent
    log3 = SegmentedLog(str(tmp_path), pol)
    assert log3.recovered_truncated_bytes == 0
    assert [r[0] for r in _dump(log3)] == list(range(41))
    log3.close()


def test_recovery_drops_empty_tail_segment(tmp_path):
    """Death right after a roll leaves a zero-record tail segment; the
    mount must drop it and resume appending at the right offset."""
    pol = StorePolicy(fsync="never", segment_bytes=10 ** 9)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 10)
    log.roll()  # seals segment 0, creates an empty active segment
    log.close()
    empties = [n for n in os.listdir(str(tmp_path)) if n.endswith(".log")
               and os.path.getsize(tmp_path / n) == 0]
    assert empties  # the crash artifact exists
    log2 = SegmentedLog(str(tmp_path), pol)
    assert log2.end_offset == 10
    assert log2.recovered_truncated_bytes == 0  # empty tail is not "torn"
    assert log2.append(None, b"next", 0) == 10
    assert [r[0] for r in _dump(log2)] == list(range(11))
    log2.close()


def _flip_last_byte(path, size):
    with open(path, "r+b") as fh:
        fh.seek(size - 1)
        b = fh.read(1)
        fh.seek(size - 1)
        fh.write(bytes([b[0] ^ 0xFF]))


def test_corrupt_sealed_segment_gap_is_jumped_not_stalled(tmp_path):
    """A corrupted frame inside a SEALED (non-tail) segment must never
    stall readers (a stalled at_end() would hang the scorer) — on BOTH
    mount paths: the rescan path (no sidecars) truncates and counts the
    corruption; the trusted-sidecar fast path discovers it at read time
    and skips the hole.  Either way every intact later record serves."""
    pol = StorePolicy(fsync="never", segment_bytes=200)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 40)
    assert len(log._segments) > 2
    victim = log._segments[1]  # sealed, mid-log
    log.close()
    _flip_last_byte(victim.path, victim.size)

    def drain(log):
        """Cursor-style reads, like a consumer: a hole may only ever
        appear BETWEEN batches (batch starts), never inside one — the
        replica's realignment check reads msgs[0].offset only."""
        got, off = [], 0
        while True:
            chunk = log.read_from(off, 1000)
            if not chunk:
                return got
            offs = [r[0] for r in chunk]
            assert offs == list(range(offs[0], offs[0] + len(offs)))
            got += offs
            off = offs[-1] + 1

    # path 1 — trusted sidecars (size stamp still matches): mount stays
    # O(tail), the corruption surfaces at read time as a skipped hole
    log2 = SegmentedLog(str(tmp_path), pol)
    assert log2.recovered_truncated_bytes == 0
    got = drain(log2)
    assert got[0] == 0 and got[-1] == 39          # later segments served
    hole = set(range(40)) - set(got)
    assert hole and all(victim.base_offset <= o < 40 for o in hole)
    log2.close()

    # path 2 — sidecars gone: full rescan detects, truncates, counts
    for n in list(os.listdir(str(tmp_path))):
        if n.endswith((".index", ".timeindex")):
            os.remove(str(tmp_path / n))
    log3 = SegmentedLog(str(tmp_path), pol)
    assert log3.recovered_truncated_bytes > 0
    got = drain(log3)
    assert got[0] == 0 and got[-1] == 39
    hole = set(range(40)) - set(got)
    assert hole and all(victim.base_offset <= o < 40 for o in hole)
    # a reader starting INSIDE the hole also gets un-stalled
    assert log3.read_from(min(hole), 10)[0][0] == max(hole) + 1
    log3.close()


def test_index_log_mismatch_rebuilt_from_log(tmp_path):
    """Sidecar indexes are an accelerator, never ground truth: a
    corrupted or deleted .index/.timeindex must not change reads."""
    pol = StorePolicy(fsync="never", segment_bytes=300)
    log = SegmentedLog(str(tmp_path), pol)
    _fill(log, 50)
    before = _dump(log)
    ts_probe = log.offset_for_timestamp(1025)
    log.close()
    sidecars = [n for n in os.listdir(str(tmp_path))
                if n.endswith((".index", ".timeindex"))]
    assert sidecars  # sealed segments published them
    for i, name in enumerate(sidecars):
        p = str(tmp_path / name)
        if i % 2:
            os.remove(p)
        else:  # garbage content: disagrees with the log
            with open(p, "wb") as fh:
                fh.write(b"\xff" * 24)
    log2 = SegmentedLog(str(tmp_path), pol)
    assert _dump(log2) == before
    assert log2.offset_for_timestamp(1025) == ts_probe == 25
    log2.close()


def test_timestamp_index_and_read_since(tmp_path):
    log = SegmentedLog(str(tmp_path), StorePolicy(fsync="never",
                                                  segment_bytes=250))
    _fill(log, 40, ts0=100)
    assert log.offset_for_timestamp(0) == 0
    assert log.offset_for_timestamp(120) == 20
    assert log.offset_for_timestamp(10 ** 9) == log.end_offset
    assert [r[0] for r in log.read_since(135, 10)] == [35, 36, 37, 38, 39]
    # non-monotone timestamps: earliest offset at/after T, Kafka's rule
    log.append(None, b"late", 50)   # older ts after newer ones
    assert log.offset_for_timestamp(120) == 20
    log.close()


def test_align_base_and_reset(tmp_path):
    log = SegmentedLog(str(tmp_path), StorePolicy(fsync="never"))
    log.align_base(500)
    assert log.base_offset == log.end_offset == 500
    assert log.append(None, b"v", 0) == 500
    with pytest.raises(ValueError):
        log.align_base(900)
    log.reset(42)
    assert log.base_offset == log.end_offset == 42
    assert log.append(None, b"w", 0) == 42
    log.close()


# -------------------------------------------------------------- offsets
def test_offsets_file_compacts_and_survives_torn_tail(tmp_path):
    of = OffsetsFile(str(tmp_path), fsync="always", compact_ratio=4)
    for i in range(100):
        of.commit("g", "t", i % 3, i)
    size_after_compaction = os.path.getsize(of.path)
    # 100 appended records over 3 live keys MUST have compacted
    assert of._records < 100
    assert size_after_compaction < 100 * 40
    of.commit_many("g2", "t", [(0, 7), (1, 9)])
    of.close()
    of2 = OffsetsFile(str(tmp_path))
    assert of2.get("g", "t", 0) == 99
    assert of2.get("g2", "t", 1) == 9
    # torn tail: the partial record is dropped, the rest loads
    of2.close()
    with open(of2.path, "ab") as fh:
        fh.write(b"\x00\x00\x10\x00partial")
    of3 = OffsetsFile(str(tmp_path))
    assert of3.recovered_truncated_bytes > 0
    assert of3.get("g", "t", 0) == 99
    of3.close()


# ----------------------------------------------------- broker (durable)
def test_durable_broker_restart_resumes_everything(tmp_path):
    d = str(tmp_path / "store")
    pol = dict(fsync="always", segment_bytes=500)
    b = Broker(store_dir=d, store_policy=StorePolicy(**pol))
    b.create_topic("t", partitions=2, retention_bytes=0)
    for i in range(30):
        b.produce("t", f"v{i}".encode(), key=f"k{i % 4}".encode(),
                  timestamp_ms=i)
    b.produce_many("t", [(None, b"bulk", 99), (b"k", b"bulk2", 100)])
    b.commit("g", "t", 0, 5)
    b.commit_many("g", "t", [(0, 7), (1, 3)])
    ends = [b.end_offset("t", p) for p in (0, 1)]
    rows = [b.fetch("t", p, b.begin_offset("t", p), 1000) for p in (0, 1)]
    b.close()

    b2 = Broker(store_dir=d, store_policy=StorePolicy(**pol))
    assert b2.durable and b2.topic("t").partitions == 2
    assert [b2.end_offset("t", p) for p in (0, 1)] == ends
    assert [b2.fetch("t", p, b2.begin_offset("t", p), 1000)
            for p in (0, 1)] == rows
    assert b2.committed("g", "t", 0) == 7
    assert b2.committed("g", "t", 1) == 3
    b2.close()


def test_durable_broker_replay_api_and_metric(tmp_path):
    from iotml.store.log import store_replay_records

    b = Broker(store_dir=str(tmp_path / "s"))
    b.create_topic("t")
    for i in range(20):
        b.produce("t", str(i).encode(), partition=0, timestamp_ms=1000 + i)
    before = store_replay_records.value()
    msgs = b.read_since("t", 0, 1015, 100)
    assert [m.offset for m in msgs] == [15, 16, 17, 18, 19]
    assert b.offset_for_timestamp("t", 0, 1015) == 15
    assert store_replay_records.value() == before + 5
    b.close()


def test_durable_retention_segment_granular(tmp_path):
    b = Broker(store_dir=str(tmp_path / "s"),
               store_policy=StorePolicy(fsync="never", segment_bytes=300))
    b.create_topic("t", retention_bytes=700)
    for i in range(100):
        b.produce("t", b"x" * 20, partition=0)
    assert b.begin_offset("t", 0) > 0        # head segments deleted
    assert b.end_offset("t", 0) == 100
    with pytest.raises(OffsetOutOfRangeError):
        b.fetch("t", 0, 0)
    # count retention too (the CLI's --retention on a durable platform):
    # segment-granular, may over-retain up to one segment, never under
    b.create_topic("tc", retention_messages=10)
    for i in range(100):
        b.produce("tc", b"y" * 20, partition=0)
    retained = b.end_offset("tc", 0) - b.begin_offset("tc", 0)
    assert 10 <= retained < 40
    b.close()


def test_durable_topic_retention_inherit_vs_explicit_unlimited(tmp_path):
    """None (unset) inherits the store-wide retention default; 0 (the
    wire's -1 sentinel) explicitly opts the topic out of it."""
    b = Broker(store_dir=str(tmp_path / "s"),
               store_policy=StorePolicy(fsync="never", segment_bytes=300,
                                        retention_bytes=700))
    b.create_topic("inherits")           # None: store default applies
    b.create_topic("unlimited", retention_bytes=0)  # explicit opt-out
    for i in range(100):
        b.produce("inherits", b"x" * 20, partition=0)
        b.produce("unlimited", b"x" * 20, partition=0)
    assert b.begin_offset("inherits", 0) > 0
    assert b.begin_offset("unlimited", 0) == 0
    assert b.end_offset("unlimited", 0) == 100
    b.close()


def test_store_metrics_registered_and_live(tmp_path):
    from iotml.obs import metrics as obs_metrics

    b = Broker(store_dir=str(tmp_path / "s"))
    b.create_topic("t")
    b.produce("t", b"v", partition=0)
    rendered = obs_metrics.default_registry.render()
    for family in ("iotml_store_segment_bytes", "iotml_store_fsync_seconds",
                   "iotml_store_recovery_truncated_bytes",
                   "iotml_store_replay_records_total"):
        assert family in rendered, family
    from iotml.store.log import store_segment_bytes

    assert store_segment_bytes.value(topic="t", partition="0") > 0
    b.close()


# --------------------------------------------------------- wire + store
def test_wire_out_of_range_and_timestamp_listing(tmp_path):
    """The trimmed-log read path over TCP: error 1 + earliest offset in
    the response, client raises OffsetOutOfRangeError, StreamConsumer
    auto-resets; ListOffsets with ts>=0 answers the replay cursor."""
    from iotml.stream.consumer import StreamConsumer
    from iotml.stream.kafka_wire import KafkaWireBroker, KafkaWireServer

    b = Broker()
    b.create_topic("t", retention_messages=5)
    for i in range(20):
        b.produce("t", str(i).encode(), partition=0, timestamp_ms=i)
    with KafkaWireServer(b) as srv:
        client = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        with pytest.raises(OffsetOutOfRangeError) as ei:
            client.fetch("t", 0, 0)
        assert ei.value.earliest == 15
        assert client.offset_for_timestamp("t", 0, 17) == 17
        # consumer over the wire: documented auto-reset-to-earliest
        c = StreamConsumer(client, ["t:0:0"], group="g", eof=False)
        assert [m.offset for m in c.poll()] == [15, 16, 17, 18, 19]
        client.close()


def test_wire_create_topic_carries_retention_configs(tmp_path):
    from iotml.stream.kafka_wire import KafkaWireBroker, KafkaWireServer

    b = Broker()
    with KafkaWireServer(b) as srv:
        client = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        client.create_topic("t", partitions=2, retention_messages=9,
                            retention_bytes=1234, retention_ms=5678)
        spec = b.topic("t")
        assert (spec.retention_messages, spec.retention_bytes,
                spec.retention_ms) == (9, 1234, 5678)
        with pytest.raises(ValueError):
            client.create_topic("neg", retention_ms=-4)
        # Kafka's documented -1 'unlimited' sentinel maps to EXPLICIT
        # unlimited (0) — distinct from None/unset, which on a durable
        # broker would inherit the store-wide retention default
        client.create_topic("unlim", retention_ms=-1)
        assert b.topic("unlim").retention_ms == 0
        client.close()


# ---------------------------------------------------- trainer backfill
def test_trainer_backfills_from_timestamp_on_cold_start(tmp_path):
    """ContinuousTrainer with backfill_since_ms: a first incarnation (no
    committed cursor) starts at the replay offset; a partition WITH a
    commit resumes from it untouched."""
    from iotml.train.artifacts import ArtifactStore
    from iotml.train.live import ContinuousTrainer

    b = Broker(store_dir=str(tmp_path / "s"))
    b.create_topic("t", partitions=2)
    for i in range(50):
        b.produce("t", str(i).encode(), partition=i % 2,
                  timestamp_ms=1000 + i)
    b.commit("cold", "t", 1, 11)  # partition 1 has a committed cursor
    ct = ContinuousTrainer(b, "t", ArtifactStore(str(tmp_path / "art")),
                           group="cold", backfill_since_ms=1030)
    pos = dict((p, off) for _t, p, off in ct.consumer.positions())
    assert pos[0] == b.offset_for_timestamp("t", 0, 1030)
    assert pos[0] > 0
    assert pos[1] == 11  # resume beats replay
    b.close()


def test_consumer_seek_to_timestamp(tmp_path):
    from iotml.stream.consumer import StreamConsumer

    b = Broker(store_dir=str(tmp_path / "s"))
    b.create_topic("t")
    for i in range(10):
        b.produce("t", str(i).encode(), partition=0, timestamp_ms=100 + i)
    c = StreamConsumer(b, ["t:0:0"], group="g")
    c.seek_to_timestamp(106)
    assert [m.offset for m in c.poll()] == [6, 7, 8, 9]
    b.close()


def test_sanitized_topic_names_never_share_a_directory(tmp_path):
    """"a b" and "a_b" sanitize identically; two SegmentedLogs over one
    directory would interleave frames — the dir names must diverge."""
    from iotml.store.mount import _dirname_for

    assert _dirname_for("a b") != _dirname_for("a_b")
    assert _dirname_for("plain-topic.ok") == "plain-topic.ok"
    b = Broker(store_dir=str(tmp_path / "s"))
    b.create_topic("a b")
    b.create_topic("a_b")
    b.produce("a b", b"spaced", partition=0)
    b.produce("a_b", b"underscored", partition=0)
    assert b.fetch("a b", 0, 0)[0].value == b"spaced"
    assert b.fetch("a_b", 0, 0)[0].value == b"underscored"
    assert b.end_offset("a b", 0) == b.end_offset("a_b", 0) == 1
    b.close()


def test_store_dir_single_writer_lock(tmp_path):
    """Two broker PROCESSES must not share one store dir (interleaved
    frames in the active segment are unrecoverable corruption); a
    remount in the SAME process (the crash-simulation path) must work."""
    import subprocess
    import sys

    d = str(tmp_path / "s")
    b = Broker(store_dir=d)
    b.create_topic("t")
    # same-process remount (chaos runner's kill path): allowed
    b2 = Broker(store_dir=d)
    assert "t" in b2.topics()
    # a second PROCESS: refused while this one holds the mount
    probe = subprocess.run(
        [sys.executable, "-c",
         "from iotml.stream.broker import Broker\n"
         f"Broker(store_dir={d!r})"],
        capture_output=True, text=True, cwd="/root/repo")
    assert probe.returncode != 0
    assert "locked by another broker process" in probe.stderr
    b.close()
    b2.close()
    # lock released with the mount: the next process may take it
    probe2 = subprocess.run(
        [sys.executable, "-c",
         "from iotml.stream.broker import Broker\n"
         f"br = Broker(store_dir={d!r}); br.close()"],
        capture_output=True, text=True, cwd="/root/repo")
    assert probe2.returncode == 0, probe2.stderr


# -------------------------------------------------- platform / config
def test_platform_durable_mode_survives_restart(tmp_path):
    """--durable end to end: a Platform over a store dir, records in,
    torn down; a SECOND Platform over the same dir serves the same
    records and committed offsets (the quickstart's restart story)."""
    from iotml.cli.up import Platform

    d = str(tmp_path / "plat")
    plat = Platform(partitions=2, store_dir=d,
                    store_policy=StorePolicy(fsync="always")).start()
    try:
        plat.broker.create_topic("raw")  # outside the reference topic set
        for i in range(10):
            plat.broker.produce("raw", str(i).encode(), partition=0)
        plat.broker.commit("g", "raw", 0, 4)
    finally:
        plat.stop()

    plat2 = Platform(partitions=2, store_dir=d,
                     store_policy=StorePolicy(fsync="always")).start()
    try:
        assert plat2.endpoints().get("store") == d
        assert plat2.broker.end_offset("raw", 0) == 10
        assert plat2.broker.committed("g", "raw", 0) == 4
        assert "sensor-data" in plat2.broker.topics()
    finally:
        plat2.stop()


def test_store_config_section_resolves_from_env():
    from iotml.config import load_config
    from iotml.store import StorePolicy as SP

    cfg, _ = load_config([], env={"IOTML_STORE_DIR": "/tmp/x",
                                  "IOTML_STORE_FSYNC": "always",
                                  "IOTML_STORE_RETENTION_MS": "100000"})
    assert cfg.store.dir == "/tmp/x"
    assert cfg.store.fsync == "always"
    assert cfg.store.retention_ms == 100000
    pol = SP.from_config(cfg.store)
    assert pol.fsync == "always" and pol.retention_ms == 100000
    with pytest.raises(ValueError):
        load_config([], env={"IOTML_STORE_FSYNCK": "always"})