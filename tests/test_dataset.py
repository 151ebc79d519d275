"""Stream → fixed-shape batch pipeline (decode, filter, pad, window)."""

import numpy as np
import pytest

from iotml.core.schema import KSQL_CAR_SCHEMA
from iotml.data.dataset import SensorBatches
from iotml.gen.simulator import FleetGenerator, FleetScenario
from iotml.stream import native as native_mod
from iotml.stream.broker import Broker
from iotml.stream.consumer import StreamConsumer


def make_stream(num_cars=30, ticks=10, failure_rate=0.0, topic="SENSOR_DATA_S_AVRO"):
    broker = Broker()
    gen = FleetGenerator(FleetScenario(num_cars=num_cars, failure_rate=failure_rate))
    n = gen.publish(broker, topic, n_ticks=ticks)
    consumer = StreamConsumer(broker, [f"{topic}:0:0"], group="test")
    return broker, consumer, n


def test_batches_fixed_shape_and_padding():
    _, consumer, n = make_stream(num_cars=30, ticks=10)  # 300 records
    batches = list(SensorBatches(consumer, batch_size=64))
    assert len(batches) == 5  # 4 full + 1 padded tail
    for b in batches[:-1]:
        assert b.x.shape == (64, 18) and b.n_valid == 64
    tail = batches[-1]
    assert tail.x.shape == (64, 18)
    assert tail.n_valid == 300 - 4 * 64
    assert np.all(tail.x[tail.n_valid:] == 0.0)
    assert tail.mask.sum() == tail.n_valid


def test_take_skip_and_indices():
    _, consumer, _ = make_stream(num_cars=50, ticks=10)  # 500 records
    bs = SensorBatches(consumer, batch_size=50, skip=2, take=3)
    batches = list(bs)
    assert len(batches) == 3
    # indices are post-skip (reference OutputCallback starts at 0 after the
    # skip slice, cardata-v3.py:243-249)
    assert [b.first_index for b in batches] == [0, 50, 100]


def test_skip_applies_once_across_drains():
    """A continuous scorer re-entering the iterator must not re-skip newly
    arrived data (skip targets the stream head only)."""
    broker, consumer, _ = make_stream(num_cars=50, ticks=2)  # 100 records
    bs = SensorBatches(consumer, batch_size=50, skip=1)
    first = list(bs)
    assert len(first) == 1  # one batch skipped, one emitted
    # more data arrives; drain again — nothing further may be skipped
    gen = FleetGenerator(FleetScenario(num_cars=50))
    gen.publish(broker, "SENSOR_DATA_S_AVRO", n_ticks=1)
    second = list(bs)
    assert sum(b.n_valid for b in second) == 50


def test_only_normal_filters_failures():
    _, consumer, _ = make_stream(num_cars=200, ticks=5, failure_rate=0.2)
    bs = SensorBatches(consumer, batch_size=32, only_normal=True, keep_labels=True)
    got = 0
    for b in bs:
        assert all(l == "false" for l in b.labels[: b.n_valid])
        got += b.n_valid
    assert 0 < got < 1000  # some rows filtered


def test_values_normalized_range():
    _, consumer, _ = make_stream(num_cars=20, ticks=5)
    for b in SensorBatches(consumer, batch_size=100):
        assert b.x.dtype == np.float32
        # normalized sensors live in ~[-1, 1]; zeroed cols exactly 0
        assert np.all(b.x[:, 0] == 0.0)
        assert np.all(np.abs(b.x[: b.n_valid, 1]) <= 1.0 + 1e-5)


def test_epoch_reread_is_deterministic():
    _, consumer, _ = make_stream(num_cars=30, ticks=4)
    bs = SensorBatches(consumer, batch_size=40)
    epochs = []
    for it in bs.epochs(2):
        epochs.append(np.concatenate([b.x[: b.n_valid] for b in it]))
    np.testing.assert_array_equal(epochs[0], epochs[1])


def test_windowed_batches_next_step_target():
    _, consumer, _ = make_stream(num_cars=10, ticks=30)  # 300 sequential records
    bs = SensorBatches(consumer, batch_size=8, window=4)
    b = next(iter(bs))
    assert b.x.shape == (8, 4, 18)
    assert b.y.shape == (8, 1, 18)
    # window shift=1: row k's window starts at record k; target = record k+4
    # => x[1,0] == x[0,1] (overlapping windows)
    np.testing.assert_array_equal(b.x[1, 0], b.x[0, 1])
    # => y[0] == x[4,3]? target of window 0 is record 4 == first row of window 4
    np.testing.assert_array_equal(b.y[0, 0], b.x[4, 0])


def test_ksql_schema_is_default():
    _, consumer, _ = make_stream(num_cars=5, ticks=2)
    bs = SensorBatches(consumer)
    assert bs.schema is KSQL_CAR_SCHEMA


# ------------------------------------------- read-ahead hint (ISSUE 31)
class _TakesConsumer:
    """A consumer duck-type with a fused decode leg and `rows` records
    to give, which keeps what the batcher asked of it."""

    fetch_decode = staticmethod(lambda *a, **kw: None)  # "has the leg"

    def __init__(self, rows=10 ** 9):
        self.broker = self
        self.rows = rows
        self.polls, self.asked = [], []

    def poll_decoded(self, codec, strip=5, max_messages=4096,
                     with_keys=False):
        n = min(max_messages, self.rows)
        self.rows -= n
        self.polls.append(max_messages)
        return (np.zeros((n, codec.n_numeric)),
                np.full((n, codec.n_strings), b"false", "S16"))

    def read_ahead(self, requests, codec, strip=5, with_keys=False):
        self.asked.append(list(requests))

    def seek_to_start(self):
        pass


needs_native = pytest.mark.skipif(not native_mod.available(),
                                  reason="C++ engine not built")


@needs_native
@pytest.mark.parametrize("kw,polls", [
    (dict(batch_size=100, take=3), [300]),
    (dict(batch_size=100, take=3, poll_chunk=256), [256, 44]),
    (dict(batch_size=4, take=4, window=64), [80]),
    (dict(batch_size=1, take=4, window=4096), [4096, 4]),
])
def test_a_bounded_take_arms_on_its_second_end_in_a_row(kw, polls):
    cons = _TakesConsumer()
    batches = SensorBatches(cons, **kw)
    assert len(list(batches)) == kw["take"]
    assert cons.asked == []            # a one-shot job reads nothing ahead
    assert len(list(batches)) == kw["take"]
    assert cons.asked == [polls]       # a loop of jobs on one cursor does
    assert len(list(batches)) == kw["take"]
    assert cons.asked == [polls, polls]
    assert cons.polls == polls * 3
    # an epoch re-read is not the next take: the count starts again
    batches.reset()
    list(batches)
    assert cons.asked == [polls, polls]


@needs_native
@pytest.mark.parametrize("window", [None, 8])
def test_an_unbounded_drain_and_a_short_take_never_arm(window):
    cons = _TakesConsumer(rows=1000)
    drain = SensorBatches(cons, batch_size=100, window=window)
    for _ in range(3):
        list(drain)
        cons.rows = 1000
    assert cons.asked == []
    # a take the stream's end cut short ends the run of full takes
    cons = _TakesConsumer(rows=250)
    batches = SensorBatches(cons, batch_size=100, take=2, window=window)
    assert len(list(batches)) == 2
    assert len(list(batches)) < 2
    cons.rows = 10 ** 9
    assert len(list(batches)) == 2
    assert cons.asked == []
    assert len(list(batches)) == 2
    assert len(cons.asked) == 1
    # and one abandoned midway does too
    next(iter(batches))
    assert len(list(batches)) == 2
    assert len(cons.asked) == 1
