"""Key-based log compaction (iotml.store.compact): the keep/discard
rule, segment rewrites, dirty-ratio triggering, tombstone grace,
composition with retention/indexes/recovery/replication, and the
tombstone transport end to end (broker, wire, native client).

The ISSUE-8 checklist rows: dirty-ratio trigger, tombstone grace
expiry, compaction x retention interplay, index rebuild over compacted
segments, byte-stable remount."""

import os

import pytest

from iotml.store import SegmentedLog, StorePolicy
from iotml.store import compact as cp
from iotml.store import segment as seg
from iotml.stream.broker import Broker


def _pol(**kw):
    kw.setdefault("fsync", "never")
    kw.setdefault("segment_bytes", 10 ** 9)
    return StorePolicy(**kw)


def _offsets(log):
    return [r[0] for r in log.read_from(log.base_offset, 10 ** 6)]


def _records(log):
    return log.read_from(log.base_offset, 10 ** 6)


def _drain(b, topic, p=0):
    """Broker-level cursor read: fetch batches END at compaction holes
    (no internal gaps), so a full read walks batch by batch."""
    out, off = [], b.begin_offset(topic, p)
    end = b.end_offset(topic, p)
    while off < end:
        batch = b.fetch(topic, p, off, 10 ** 6)
        if not batch:
            break
        out += batch
        off = batch[-1].offset + 1
    return out


# ---------------------------------------------------------- the decision
def test_tombstone_frame_is_byte_distinct_from_empty():
    dead = seg.encode_record(5, b"k", None, 10, None)
    empty = seg.encode_record(5, b"k", b"", 10, None)
    assert dead != empty
    (_p, _e, _o, _k, v_dead, _t, _h), = seg.scan_records(dead)
    (_p, _e, _o, _k, v_empty, _t, _h), = seg.scan_records(empty)
    assert v_dead is None and v_empty == b""


def test_keep_rule_latest_per_key_unkeyed_and_grace():
    recs = [(0, b"a", b"1", 100, None), (1, None, b"x", 110, None),
            (2, b"a", b"2", 120, None), (3, b"b", None, 130, None)]
    latest = cp.latest_offsets(recs)
    assert latest == {b"a": 2, b"b": 3}
    newest = 130
    # shadowed value out, latest + unkeyed in
    assert not cp.keep(recs[0], latest, newest, grace_ms=10 ** 6)
    assert cp.keep(recs[1], latest, newest, grace_ms=10 ** 6)
    assert cp.keep(recs[2], latest, newest, grace_ms=10 ** 6)
    # the tombstone: kept inside grace, dropped past it, forever if None
    assert cp.keep(recs[3], latest, newest_ts=200, grace_ms=100)
    assert not cp.keep(recs[3], latest, newest_ts=300, grace_ms=100)
    assert cp.keep(recs[3], latest, newest_ts=10 ** 9, grace_ms=None)


# ----------------------------------------------------- segment compactor
def test_compact_keeps_latest_per_key_and_preserves_offsets(tmp_path):
    log = SegmentedLog(str(tmp_path), _pol(segment_bytes=256))
    for rnd in range(6):
        for k in range(4):
            log.append(f"k{k}".encode(), f"v{rnd}".encode(),
                       1000 + rnd * 10 + k)
    log.append(None, b"unkeyed", 2000)  # never compacted away
    log.roll()
    assert len(log._segments) > 2
    before = {r[0]: r for r in _records(log)}
    stats = log.compact()
    assert stats.records_removed > 0 and stats.bytes_reclaimed > 0
    after = _records(log)
    # offsets preserved: every survivor is its original byte-for-byte
    # record, never renumbered
    for r in after:
        assert before[r[0]] == r
    by_key = {}
    for off, key, value, ts, _h in after:
        if key is not None:
            by_key[key] = value
    assert by_key == {f"k{k}".encode(): b"v5" for k in range(4)}
    assert any(key is None for _o, key, _v, _t, _h in after)
    # the ACTIVE segment is never touched; a second pass is a no-op
    assert log.compact().segments_rewritten == 0


def test_dirty_ratio_trigger_and_broker_gate(tmp_path):
    b = Broker(store_dir=str(tmp_path),
               store_policy=_pol(segment_bytes=256,
                                 compact_min_dirty_ratio=0.5))
    b.create_topic("C", cleanup_policy="compact")
    b.create_topic("D")  # delete-policy topic: never compacted
    slog = b.store.log_for("C", 0)
    assert slog.dirty_ratio() == 0.0  # nothing sealed yet
    for rnd in range(8):
        for k in range(4):
            b.produce("C", f"v{rnd}".encode(), key=f"k{k}".encode(),
                      partition=0, timestamp_ms=1000 + rnd)
            b.produce("D", b"x", key=b"k", partition=0)
    slog.roll()
    assert slog.dirty_ratio() == 1.0  # all sealed bytes unclean
    out = b.run_compaction()
    assert ("C", 0) in out and ("D", 0) not in out
    assert slog.dirty_ratio() == 0.0
    # a little new data: below the 0.5 gate, the pass skips it
    b.produce("C", b"v9", key=b"k0", partition=0, timestamp_ms=2000)
    b.store.log_for("C", 0).roll()
    assert 0.0 < b.store.log_for("C", 0).dirty_ratio() < 0.5
    assert b.run_compaction() == {}
    assert b.run_compaction(force=True) != {}
    b.close()


def test_tombstone_grace_expiry(tmp_path):
    log = SegmentedLog(str(tmp_path), _pol())
    log.append(b"a", b"v1", 1000)
    log.append(b"a", None, 2000)     # delete a
    log.append(b"b", b"v2", 2500)    # newest record ts
    log.roll()
    # inside grace (2500-2000 <= 1000): the tombstone survives so slow
    # readers still observe the delete
    log.compact(grace_ms=1000)
    recs = _records(log)
    assert (1, b"a", None, 2000, None) in recs
    # past grace: the tombstone itself is reclaimed; the key is gone
    log.compact(grace_ms=100)
    recs = _records(log)
    assert [r[0] for r in recs] == [2]
    assert all(r[1] != b"a" for r in recs)


def test_compaction_composes_with_retention(tmp_path):
    b = Broker(store_dir=str(tmp_path), store_policy=_pol(segment_bytes=256))
    b.create_topic("C", cleanup_policy="compact", retention_messages=16)
    # 40 UNIQUE keys first: compaction has nothing to reclaim here, so
    # bounding the log is retention's job (whole head segments go as
    # the produce loop outgrows the cap)
    for k in range(40):
        b.produce("C", b"first", key=f"u{k:02d}".encode(),
                  partition=0, timestamp_ms=1000 + k)
    assert b.begin_offset("C", 0) > 0  # retention trimmed the head
    # then repeated UPDATES of a retained key: retention can't touch
    # the newest segments, so bounding those is compaction's job
    for rnd in range(8):
        b.produce("C", f"v{rnd}".encode(), key=b"hot", partition=0,
                  timestamp_ms=2000 + rnd)
    b.store.log_for("C", 0).roll()
    base_before = b.begin_offset("C", 0)
    out = b.run_compaction(force=True)
    assert out[("C", 0)].records_removed > 0
    # compaction never moves the base (the out-of-range contract is
    # retention's alone) and the key's latest value survives both
    assert b.begin_offset("C", 0) == base_before
    live = {m.key: m.value for m in _drain(b, "C")}
    assert live[b"hot"] == b"v7"
    assert sum(1 for k in live if k.startswith(b"u")) == len(live) - 1
    b.close()


def test_index_rebuild_and_reads_over_compacted_segments(tmp_path):
    pol = _pol(segment_bytes=256, index_interval_bytes=64)
    log = SegmentedLog(str(tmp_path), pol)
    for rnd in range(8):
        for k in range(4):
            log.append(f"k{k}".encode(), b"v%d" % rnd, 1000 + rnd * 10 + k)
    log.roll()
    log.compact()
    survivors = _offsets(log)
    # cursor reads across the holes: batches never carry internal gaps,
    # and a read starting INSIDE a hole lands on the next survivor
    got, off = [], 0
    while True:
        chunk = log.read_from(off, 3)
        if not chunk:
            break
        offs = [r[0] for r in chunk]
        assert offs == list(range(offs[0], offs[0] + len(offs)))
        got += offs
        off = offs[-1] + 1
    assert got == survivors
    # timestamp replay over the compacted log: first surviving record
    # at/after the timestamp
    ts_target = 1050
    off_for = log.offset_for_timestamp(ts_target)
    assert off_for in survivors or off_for == log.end_offset
    log.close()
    # remount: sidecar indexes rebuilt/trusted over the compacted
    # segments, same reads
    log2 = SegmentedLog(str(tmp_path), pol)
    assert _offsets(log2) == survivors
    assert log2.offset_for_timestamp(ts_target) == off_for
    log2.close()
    # index/log mismatch path: delete sidecars, full rescan, same reads
    for n in list(os.listdir(str(tmp_path))):
        if n.endswith((".index", ".timeindex")):
            os.remove(str(tmp_path / n))
    log3 = SegmentedLog(str(tmp_path), pol)
    assert _offsets(log3) == survivors
    log3.close()


def test_compacted_reads_byte_stable_across_remount(tmp_path):
    pol = _pol(segment_bytes=256)
    log = SegmentedLog(str(tmp_path), pol)
    for rnd in range(8):
        for k in range(4):
            log.append(f"k{k}".encode(), b"v%d" % rnd, 1000 + rnd)
    log.append(b"k0", None, 1100)  # a tombstone inside grace: kept
    log.roll()
    log.compact(grace_ms=10 ** 9)
    want = _records(log)
    names = sorted(n for n in os.listdir(str(tmp_path))
                   if n.endswith(".log"))
    # the max-named file is the EMPTY active segment the roll opened;
    # recovery legitimately drops it at remount, so byte-stability is a
    # sealed-segment contract
    files = {n: open(os.path.join(str(tmp_path), n), "rb").read()
             for n in names[:-1]}
    log.close()
    log2 = SegmentedLog(str(tmp_path), pol)
    # fetch-level byte stability: identical (offset, key, value, ts)
    assert _records(log2) == want
    # file-level too: a remount rewrites nothing
    for n, blob in files.items():
        assert open(os.path.join(str(tmp_path), n), "rb").read() == blob
    log2.close()


def test_fully_dead_segments_drop_but_head_keeps_base(tmp_path):
    log = SegmentedLog(str(tmp_path), _pol(segment_bytes=200))
    for rnd in range(12):
        log.append(b"one-key", b"v%02d" % rnd, 1000 + rnd)
    log.roll()
    n_before = len(log._segments)
    assert n_before > 3
    log.compact()
    # every sealed record except the last write is shadowed: non-head
    # dead segments are dropped outright, the head survives (possibly
    # empty) so base_offset — and the out-of-range contract — is
    # compaction-invariant
    assert len(log._segments) < n_before
    assert log.base_offset == 0
    assert [r[:3] for r in _records(log)] == [(11, b"one-key", b"v11")]
    log.close()
    log2 = SegmentedLog(str(tmp_path), _pol(segment_bytes=200))
    assert log2.base_offset == 0 and _offsets(log2) == [11]
    log2.close()


@pytest.mark.parametrize("left_behind", [
    "00000000000000000000.log" + cp.CLEANED_SUFFIX,   # a rewrite's
    "00000000000000000000.index.4242.7.tmp",          # `atomic_write`'s
], ids=["cleaned", "atomic_write"])
def test_stale_cleaned_tmp_swept_at_mount(tmp_path, left_behind):
    pol = _pol()
    log = SegmentedLog(str(tmp_path), pol)
    log.append(b"k", b"v", 1)
    log.close()
    stale = os.path.join(str(tmp_path), left_behind)
    with open(stale, "wb") as fh:  # lint-ok: R9 seeding the crash artifact the mount must sweep
        fh.write(b"half-finished rewrite")
    log2 = SegmentedLog(str(tmp_path), pol)
    assert not os.path.exists(stale)
    assert _offsets(log2) == [0]
    log2.close()


# ------------------------------------------------- offsets-file migration
def test_offsets_file_routes_through_generic_compactor(tmp_path, monkeypatch):
    """The satellite: ONE compaction implementation.  OffsetsFile.compact
    must route its keep/discard decision through store.compact.keep."""
    from iotml.store import OffsetsFile

    calls = []
    real_keep = cp.keep

    def spy(record, latest, newest_ts, grace_ms):
        calls.append(record)
        return real_keep(record, latest, newest_ts, grace_ms)

    monkeypatch.setattr(cp, "keep", spy)
    f = OffsetsFile(str(tmp_path / "offsets"), fsync="never",
                    compact_ratio=10 ** 9)
    for i in range(20):
        f.commit("g", "t", 0, i)
    f.compact()
    assert calls, "OffsetsFile.compact bypassed the generic keep rule"
    assert f.table()[("g", "t", 0)] == 19
    f.close()
    # and the compacted file still reloads to the same table
    f2 = OffsetsFile(str(tmp_path / "offsets"), fsync="never")
    assert f2.table()[("g", "t", 0)] == 19
    f2.close()


# ----------------------------------------------- tombstone transport e2e
def test_tombstone_survives_durable_broker_remount(tmp_path):
    b = Broker(store_dir=str(tmp_path), store_policy=_pol())
    b.create_topic("C", cleanup_policy="compact")
    b.produce("C", b"v", key=b"k", partition=0, timestamp_ms=1)
    b.produce("C", None, key=b"k", partition=0, timestamp_ms=2)
    msgs = b.fetch("C", 0, 0, 10)
    assert [m.value for m in msgs] == [b"v", None]
    b.close()
    b2 = Broker(store_dir=str(tmp_path), store_policy=_pol())
    assert b2.topic("C").cleanup_policy == "compact"  # manifest carried it
    msgs = b2.fetch("C", 0, 0, 10)
    assert [m.value for m in msgs] == [b"v", None]
    assert msgs[1].value is not b"" and msgs[1].value is None
    b2.close()


def test_tombstone_and_cleanup_policy_over_the_wire():
    from iotml.stream.kafka_wire import KafkaWireBroker, KafkaWireServer

    b = Broker()
    with KafkaWireServer(b) as srv:
        client = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        client.create_topic("C", partitions=1, cleanup_policy="compact")
        assert b.topic("C").cleanup_policy == "compact"
        with pytest.raises(ValueError):
            client.create_topic("bad", cleanup_policy="sometimes")
        client.produce("C", b"v", key=b"k", partition=0)
        client.produce("C", None, key=b"k", partition=0)
        got = client.fetch("C", 0, 0)
        assert [m.value for m in got] == [b"v", None]
        assert got[1].key == b"k"
        client.close()


def test_tombstone_through_native_client():
    from iotml.stream import native
    from iotml.stream.kafka_wire import KafkaWireServer
    from iotml.stream.native_kafka import NativeKafkaBroker

    if native.load() is None:
        pytest.skip("native engine not built")
    b = Broker()
    with KafkaWireServer(b) as srv:
        client = NativeKafkaBroker(f"127.0.0.1:{srv.port}")
        # the policy rides the native CreateTopics too (a TwinService
        # can own its changelog over the native client)
        client.create_topic("C", cleanup_policy="compact")
        assert b.topic("C").cleanup_policy == "compact"
        client.produce_many("C", [(b"k", b"v", 1), (b"k", None, 2),
                                  (b"j", b"w", 3)], partition=0)
        got = client.fetch("C", 0, 0)
        assert [(m.key, m.value) for m in got] == \
            [(b"k", b"v"), (b"k", None), (b"j", b"w")]
        client.close()


def test_replica_mirrors_compacted_topic_with_holes(tmp_path):
    """Compaction punches offset holes; a durable follower must mirror
    them offset-preserving (produce_at), never renumber."""
    from iotml.stream.kafka_wire import KafkaWireServer
    from iotml.stream.replica import FollowerReplica

    leader = Broker(store_dir=str(tmp_path / "leader"),
                    store_policy=_pol(segment_bytes=256))
    leader.create_topic("C", cleanup_policy="compact")
    for rnd in range(8):
        for k in range(4):
            leader.produce("C", f"v{rnd}".encode(), key=f"k{k}".encode(),
                           partition=0, timestamp_ms=1000 + rnd)
    leader.store.log_for("C", 0).roll()
    leader.run_compaction(force=True)
    want = [(m.offset, m.key, m.value, m.timestamp_ms)
            for m in leader.fetch("C", 0, 0, 10 ** 6)]
    assert [o for o, _k, _v, _t in want] != list(range(len(want)))  # holes
    with KafkaWireServer(leader) as srv:
        # the wire Metadata carries no topic configs, so a wire follower
        # is TOLD which topics mirror with compacted semantics — same
        # operator contract as its retention bound
        with FollowerReplica(f"127.0.0.1:{srv.port}", topics=["C"],
                             store_dir=str(tmp_path / "follower"),
                             compacted_topics=("C",)) as rep:
            assert rep.caught_up(timeout_s=15)
            rep.pause()  # round barrier: no in-flight sync while we read
            assert rep.sync_errors == []
            got = [(m.offset, m.key, m.value, m.timestamp_ms)
                   for m in rep.local.fetch("C", 0, 0, 10 ** 6)]
            assert got == want  # identical offsets, identical holes
            assert rep.local.topic("C").cleanup_policy == "compact"
    leader.close()


def test_in_memory_tombstone_and_compact_policy_metadata():
    b = Broker()
    spec = b.create_topic("C", cleanup_policy="compact")
    assert spec.cleanup_policy == "compact"
    with pytest.raises(ValueError):
        b.create_topic("bad", cleanup_policy="compact,delete")
    b.produce("C", None, key=b"k", partition=0)
    (m,) = b.fetch("C", 0, 0, 10)
    assert m.value is None
    assert b.run_compaction() == {}  # nothing durable to reclaim
