"""The consumer's read-ahead of one take (ISSUE 31): `read_ahead` runs
the next take's polls on a copy of the cursors while the caller
computes, `poll_decoded` hands each result on only to the very poll it
replays, and positions stay DELIVERED positions.

Event-driven throughout: the fake broker's `fetch_decode` blocks on an
`Event` the test releases; no sleeps, no wall-clock assertions (the
timeouts only keep a broken tree from hanging the suite)."""

import threading

import numpy as np
import pytest

from iotml.core.schema import KSQL_CAR_SCHEMA
from iotml.data.dataset import SensorBatches
from iotml.obs import metrics as obs_metrics
from iotml.ops import framing
from iotml.ops.avro import AvroCodec
from iotml.stream import native as native_mod
from iotml.stream.broker import (Broker, OffsetOutOfRangeError,
                                 SchemaIdMismatchError)
from iotml.stream.consumer import StreamConsumer
from iotml.stream.kafka_wire import KafkaWireServer

pytestmark = pytest.mark.skipif(not native_mod.available(),
                                reason="C++ engine not built")

CODEC = AvroCodec(KSQL_CAR_SCHEMA)
PARTS = 10
SPECS = [f"T:{p}:0" for p in range(PARTS)]
#: the three listed cells' polls of one take
PATTERNS = {"sf": [1040], "gh": [4096, 4], "km": [4096, 4096, 4]}
AHEAD = "iotml-consumer-read-ahead"
WAIT = 60  # s: never reached on a sound tree


class Plain(StreamConsumer):
    """The unbuffered consumer: what every result is compared with."""

    read_ahead = None


def fill(broker, per_part: int, first: int = 0, labels=("false",),
         create: bool = True) -> None:
    """`per_part` records a partition; the first numeric field is the
    record's ordinal over the whole log, so rows and order show."""
    if create:
        broker.create_topic("T", partitions=PARTS)
    rec = {f.name: ("false" if f.avro_type == "string" else 0.5)
           for f in KSQL_CAR_SCHEMA.fields}
    k = first
    for p in range(PARTS):
        entries = []
        for _ in range(per_part):
            rec["COOLANT_TEMP"] = float(k)
            rec[KSQL_CAR_SCHEMA.label_field] = labels[k % len(labels)]
            entries.append((b"car-%d" % (k % 7),
                            framing.frame(CODEC.encode(rec), 1),
                            1_700_000_000_000 + k))
            k += 1
        broker.produce_many("T", entries, partition=p)


class FakeDecodeBroker:
    """`fetch_decode` over the in-process `Broker` (which has none of
    its own), with what the tests steer: a gate the read-ahead thread's
    fetches wait at, a log of every call, a retained base and a frame
    of a foreign schema to meet."""

    def __init__(self, inner: Broker):
        self.inner = inner
        self.calls = []                   # (thread name, part, offset, rows)
        self.gate = threading.Event()     # open: nothing waits
        self.gate.set()
        self.entered = threading.Event()  # a read-ahead fetch has begun
        self.base = {}                    # part -> retained base offset
        self.foreign = set()              # (part, offset) of evolved frames

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def begin_offset(self, topic, part=0):
        return self.base.get(part, 0)

    def fetch_decode_keys(self, topic, part, off, codec, strip=5,
                          max_rows=4096):
        who = threading.current_thread().name
        self.calls.append((who, part, off, max_rows))
        if who == AHEAD:
            self.entered.set()
            assert self.gate.wait(WAIT)
        if off < self.base.get(part, 0):
            raise OffsetOutOfRangeError(topic, part, off, self.base[part])
        if (part, off) in self.foreign:
            raise SchemaIdMismatchError(topic, part, off)
        stops = [o for q, o in self.foreign if q == part and o > off]
        if stops:
            max_rows = min(max_rows, min(stops) - off)
        msgs = self.inner.fetch(topic, part, off, max_rows)
        if not msgs:
            return (np.zeros((0, codec.n_numeric)),
                    np.zeros((0, codec.n_strings),
                             f"S{native_mod.LABEL_STRIDE}"),
                    np.zeros((0,), "S64"), off)
        num, lab = codec.decode_batch([m.value for m in msgs], strip=strip)
        keys = np.asarray([m.key for m in msgs], "S64")
        return num, lab, keys, msgs[-1].offset + 1

    def fetch_decode(self, *a, **kw):
        res = self.fetch_decode_keys(*a, **kw)
        return res[0], res[1], res[3]

    def ahead_calls(self):
        return [c for c in self.calls if c[0] == AHEAD]


def counter() -> dict:
    reg = obs_metrics.default_registry.collect()
    return {r: reg.get("iotml_consumer_readahead_rows_total"
                       f'{{result="{r}"}}', 0.0)
            for r in ("hit", "miss", "dropped")}


def moved(before: dict) -> dict:
    return {r: v - before[r] for r, v in counter().items()}


def autoresets() -> float:
    return obs_metrics.default_registry.collect().get(
        'iotml_consumer_autoresets_total{topic="T"}', 0.0)


def settle(consumer) -> None:
    """Wait for the read-ahead's thread (an event, not a time)."""
    consumer._ahead.thread.join(WAIT)
    assert not consumer._ahead.thread.is_alive()


def same(a, b) -> None:
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


@pytest.fixture
def nc():
    return native_mod.NativeCodec(KSQL_CAR_SCHEMA)


@pytest.fixture(params=["wire", "in_process"])
def leg(request):
    """A filled log of ten partitions behind each broker the fused poll
    runs on: the native client over `KafkaWireServer`, and the fake
    over the in-process `Broker`.  3,000 records a partition, so a poll
    of 4,096 crosses into the next partition."""
    inner = Broker()
    fill(inner, 3000)
    if request.param == "in_process":
        yield FakeDecodeBroker(inner)
        return
    from iotml.stream.native_kafka import NativeKafkaBroker

    with KafkaWireServer(inner) as srv:
        nb = NativeKafkaBroker(f"127.0.0.1:{srv.port}")
        yield nb
        nb.close()


# ------------------------------------ (a) rows, order, positions: exact
@pytest.mark.parametrize("cell", list(PATTERNS))
@pytest.mark.parametrize("with_keys", [False, True])
def test_every_poll_is_the_unbuffered_consumers(leg, nc, cell, with_keys):
    polls = PATTERNS[cell]
    ahead = StreamConsumer(leg, SPECS, group="ra")
    plain = Plain(leg, SPECS, group="plain")
    before = counter()
    seen = []
    for take in range(3):
        for m in polls:
            got = ahead.poll_decoded(nc, max_messages=m, with_keys=with_keys)
            want = plain.poll_decoded(nc, max_messages=m,
                                      with_keys=with_keys)
            same(got, want)
            assert len(got[0]) == m
            assert ahead.positions() == plain.positions()
            assert ahead._rr == plain._rr
            seen.append(got[0][:, 0])
        if take < 2:
            ahead.read_ahead(polls, nc, with_keys=with_keys)
    # no record twice, none skipped: partition by partition, in order
    ids = np.concatenate(seen)
    assert len(np.unique(ids)) == len(ids) == 3 * sum(polls)
    for p, (_t, _p, off) in enumerate(ahead.positions()):
        mine = np.sort(ids[(ids >= p * 3000) & (ids < (p + 1) * 3000)])
        assert np.array_equal(mine, p * 3000 + np.arange(off))
    # takes two and three came out of the buffer, whole
    assert moved(before) == {"hit": 2 * sum(polls), "miss": 0,
                             "dropped": 0}


# ----------------------- (b) the guarantee: positions are delivered ones
@pytest.mark.parametrize("cell", list(PATTERNS))
def test_commit_and_positions_know_nothing_of_the_buffer(leg, nc, cell):
    polls = PATTERNS[cell]
    cons = StreamConsumer(leg, SPECS, group="guarantee")
    for m in polls:
        cons.poll_decoded(nc, max_messages=m)
    delivered = cons.positions()
    cons.read_ahead(polls, nc)
    settle(cons)
    held = [e.out for e in cons._ahead.entries]
    assert sum(len(h[0]) for h in held) == sum(polls)  # a full take
    assert cons.positions() == delivered
    lag = cons.record_lag()
    assert lag == PARTS * 3000 - sum(polls)
    cons.commit()
    assert [leg.committed("guarantee", "T", p) for p in range(PARTS)] \
        == [off for _t, _p, off in delivered]
    # the crash: a new consumer of the group re-reads every row that
    # was fetched ahead and never delivered
    again = StreamConsumer.from_committed(leg, "T", range(PARTS),
                                          group="guarantee")
    assert again.positions() == delivered
    again._rr = cons._rr
    for m, h in zip(polls, held):
        same(again.poll_decoded(nc, max_messages=m), h)


# ------------------- (c) whatever moves a cursor drops what was fetched
MOVES = {
    "seek": lambda c: c.seek("T", 3, 17),
    "seek_to_start": lambda c: c.seek_to_start(),
    "seek_to_timestamp": lambda c: c.seek_to_timestamp(
        1_700_000_000_000 + 4000),
    "rewind_to_committed": lambda c: c.rewind_to_committed(),
}


@pytest.mark.parametrize("move", list(MOVES))
@pytest.mark.parametrize("in_flight", [False, True])
def test_a_cursor_move_drops_buffer_and_fetch_in_flight(nc, move,
                                                        in_flight):
    inner = Broker()
    fill(inner, 3000)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="moves")
    plain = Plain(fake, SPECS, group="moves-plain")
    for c in (cons, plain):
        c.poll_decoded(nc, max_messages=1040)
    before = counter()
    if in_flight:
        fake.gate.clear()
    cons.read_ahead([1040], nc)
    thread = cons._ahead.thread
    if in_flight:
        assert fake.entered.wait(WAIT)
    else:
        settle(cons)
    for c in (cons, plain):
        MOVES[move](c)
    assert cons._ahead is None
    fake.gate.set()
    thread.join(WAIT)
    assert not thread.is_alive()
    same(cons.poll_decoded(nc, max_messages=1040),
         plain.poll_decoded(nc, max_messages=1040))
    assert cons.positions() == plain.positions()
    # fetched, never delivered, counted; the poll after it was not armed
    assert moved(before) == {"hit": 0, "miss": 0, "dropped": 1040}


# --------------------------- (d) an empty read-ahead is never delivered
@pytest.mark.parametrize("eof", [True, False])
def test_an_empty_read_ahead_is_asked_again(nc, eof):
    inner = Broker()
    fill(inner, 104)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="end", eof=eof)
    assert len(cons.poll_decoded(nc, max_messages=1040)[0]) == 1040
    cons.read_ahead([1040], nc)
    settle(cons)
    assert cons._ahead.entries == []
    n_calls = len(fake.calls)
    assert len(cons.poll_decoded(nc, max_messages=1040)[0]) == 0
    # the foreground asked the broker itself, every partition
    assert len(fake.calls) == n_calls + PARTS
    assert cons._ahead is None
    fill(inner, 10, first=5000, create=False)
    num, _lab = cons.poll_decoded(nc, max_messages=1040)
    assert np.array_equal(np.sort(num[:, 0]), 5000 + np.arange(100))


def test_a_short_read_ahead_at_the_logs_end_is_a_poll_of_its_moment(nc):
    inner = Broker()
    fill(inner, 150)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="short", eof=False)
    plain = Plain(fake, SPECS, group="short-plain", eof=False)
    for c in (cons, plain):
        assert len(c.poll_decoded(nc, max_messages=1040)[0]) == 1040
    cons.read_ahead([1040], nc)
    settle(cons)
    want = plain.poll_decoded(nc, max_messages=1040)   # the 460 left
    fill(inner, 10, first=9000, create=False)
    same(cons.poll_decoded(nc, max_messages=1040), want)
    assert cons.positions() == plain.positions()
    # what arrived since is the next poll's, on both
    same(cons.poll_decoded(nc, max_messages=1040),
         plain.poll_decoded(nc, max_messages=1040))


# ------------------ (e) what the thread met, the foreground meets itself
def test_out_of_range_in_the_thread_is_the_foregrounds_to_reset(nc):
    inner = Broker()
    fill(inner, 3000)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="oor")
    plain = Plain(fake, SPECS, group="oor-plain")
    for c in (cons, plain):
        c.poll_decoded(nc, max_messages=1040)
    fake.base[1] = 500   # retention trimmed partition 1 past its cursor
    resets = autoresets()
    cons.read_ahead([1040], nc)
    settle(cons)
    assert cons._ahead.entries == []          # dropped, silently
    assert autoresets() == resets             # and not counted there
    got = cons.poll_decoded(nc, max_messages=1040)
    assert autoresets() == resets + 1
    same(got, plain.poll_decoded(nc, max_messages=1040))
    assert cons.positions() == plain.positions()
    assert cons.positions()[1] == ("T", 1, 500)


def test_schema_mismatch_in_the_thread_surfaces_in_the_foreground(nc):
    inner = Broker()
    fill(inner, 3000)
    fake = FakeDecodeBroker(inner)
    fake.foreign.add((0, 1500))   # an evolved writer's frame
    cons = StreamConsumer(fake, ["T:0:0"], group="schema")
    plain = Plain(fake, ["T:0:0"], group="schema-plain")
    for c in (cons, plain):
        c.poll_decoded(nc, max_messages=1040)
    cons.read_ahead([1040, 1040], nc)
    settle(cons)
    # the rows BEFORE the frame are a poll's result as they always were
    got = cons.poll_decoded(nc, max_messages=1040)
    same(got, plain.poll_decoded(nc, max_messages=1040))
    assert len(got[0]) == 460
    assert cons.positions() == plain.positions() == [("T", 0, 1500)]
    # on the frame the thread gave up; the foreground raises as it did
    for c in (cons, plain):
        with pytest.raises(SchemaIdMismatchError):
            c.poll_decoded(nc, max_messages=1040)
    assert cons.positions() == plain.positions() and cons._rr == plain._rr


# --------------------- (f) a poll the head entry does not replay: dropped
@pytest.mark.parametrize("differs", ["max_messages", "cursors", "rr",
                                     "keys"])
def test_a_poll_that_is_not_the_replay_reads_in_the_foreground(nc, differs):
    inner = Broker()
    fill(inner, 3000)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="other")
    plain = Plain(fake, SPECS, group="other-plain")
    for c in (cons, plain):
        c.poll_decoded(nc, max_messages=4096)
    cons.read_ahead([4096, 4], nc)
    settle(cons)
    before = counter()
    kw = dict(max_messages=4096)
    for c in (cons, plain):
        if differs == "max_messages":
            kw = dict(max_messages=4000)
        elif differs == "cursors":
            assert len(c.poll(8)) == 8   # another leg moved a cursor
        elif differs == "rr":
            c._rr += 3
        else:
            kw = dict(max_messages=4096, with_keys=True)
    same(cons.poll_decoded(nc, **kw), plain.poll_decoded(nc, **kw))
    assert cons._ahead is None
    assert moved(before) == {"hit": 0, "miss": kw["max_messages"],
                             "dropped": 4100}
    same(cons.poll_decoded(nc, max_messages=4),
         plain.poll_decoded(nc, max_messages=4))
    assert cons.positions() == plain.positions()


def test_a_label_filtered_take_stays_correct_at_a_lower_hit_share(nc):
    """`ContinuousTrainer`'s shape: `only_normal` makes the polls after
    a take's first depend on how many rows the filter kept."""
    inner = Broker()
    # one record in three is a failure, so a poll's yield varies
    fill(inner, 3000, labels=("false", "true", "false", "false", "true",
                              "false", "false"))
    fake = FakeDecodeBroker(inner)
    kw = dict(batch_size=100, take=12, only_normal=True, poll_chunk=1024)
    cons = StreamConsumer(fake, SPECS, group="filtered")
    plain = Plain(fake, SPECS, group="filtered-plain")
    a, b = SensorBatches(cons, **kw), SensorBatches(plain, **kw)
    before = counter()
    for _take in range(6):
        got, want = list(a), list(b)
        assert len(got) == len(want) == 12
        for x, y in zip(got, want):
            assert np.array_equal(x.x, y.x) and x.n_valid == y.n_valid
        assert cons.positions() == plain.positions()
    m = moved(before)
    assert m["hit"] > 0
    assert m["hit"] + m["miss"] <= sum(
        off for _t, _p, off in cons.positions())


# ------------- (g) all hits from the armed take on; a one-shot job: none
@pytest.mark.parametrize("cell,kw", [
    ("sf", dict(batch_size=4, take=4, window=1024)),
    ("gh", dict(batch_size=1, take=4, window=4096)),
    ("km", dict(batch_size=1, take=4, window=8192)),
])
def test_a_loop_of_jobs_hits_and_a_one_shot_job_fetches_nothing_more(
        nc, cell, kw):
    inner = Broker()
    fill(inner, 3300)
    fake, fake2 = FakeDecodeBroker(inner), FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="jobs")
    plain = Plain(fake2, SPECS, group="jobs-plain")
    a, b = SensorBatches(cons, **kw), SensorBatches(plain, **kw)
    rows = sum(PATTERNS[cell])
    before = counter()
    for job in range(4):
        n_calls = len(fake.calls)
        got, want = list(a), list(b)
        if job == 0:
            # the one-shot job: not one fetch the unbuffered job lacks
            assert cons._ahead is None
            assert [c[1:] for c in fake.calls] == [c[1:]
                                                   for c in fake2.calls]
            assert fake.ahead_calls() == []
        if job >= 2:
            # served whole from the buffer: the job's own thread asked
            # the broker nothing
            settle(cons)
            assert [c for c in fake.calls[n_calls:] if c[0] != AHEAD] == []
        for x, y in zip(got, want):
            assert np.array_equal(x.x, y.x) and np.array_equal(x.y, y.y)
        assert cons.positions() == plain.positions()
    settle(cons)
    assert moved(before) == {"hit": 2 * rows, "miss": 0, "dropped": 0}
    assert a._take_polls is None and a._full_takes == 4


# ---------- (h) the overlap, by order: issued before the next take polls
def test_the_request_is_out_before_the_next_take_begins(nc):
    inner = Broker()
    fill(inner, 3000)
    fake = FakeDecodeBroker(inner)
    cons = StreamConsumer(fake, SPECS, group="overlap")
    batches = SensorBatches(cons, batch_size=4, take=4, window=1024)
    list(batches)
    assert not fake.entered.is_set()    # take one: a one-shot job so far
    fake.gate.clear()
    list(batches)
    # take two has ended and NOTHING of take three has been called: the
    # thread is already inside the broker, where the fit's time goes
    assert fake.entered.wait(WAIT)
    assert fake.ahead_calls()[0][3] == 1040
    delivered = cons.positions()
    foreground = len(fake.calls) - len(fake.ahead_calls())
    fake.gate.set()
    before = counter()
    assert len(list(batches)) == 4
    assert len(fake.calls) - len(fake.ahead_calls()) == foreground
    assert moved(before)["hit"] == 1040
    assert cons.positions() != delivered


def test_a_client_closed_under_the_thread_ends_it_quietly(nc):
    """The fused legs refuse a closed native client (the engine would
    dereference a null handle): a read-ahead outliving `close()` just
    ends, and the foreground gets the error a dead socket gives."""
    from iotml.stream.native_kafka import NativeKafkaBroker

    inner = Broker()
    fill(inner, 200)
    with KafkaWireServer(inner) as srv:
        nb = NativeKafkaBroker(f"127.0.0.1:{srv.port}")
        cons = StreamConsumer(nb, SPECS, group="closed")
        assert len(cons.poll_decoded(nc, max_messages=1040)[0]) == 1040
        nb.close()
        cons.read_ahead([500], nc)
        settle(cons)
        assert cons._ahead.entries == []
        with pytest.raises(ConnectionError):
            cons.poll_decoded(nc, max_messages=500)
