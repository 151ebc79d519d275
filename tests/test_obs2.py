"""Fleet-scope observability v2 (ISSUE 13): event-time watermarks on
the columnar plane, wire-carried batch traces, metrics federation, the
consumer-lag gauge, the columnar liveness fix, hot-loop profiling
phases, and the label-cardinality bound."""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

from iotml.core.schema import KSQL_CAR_SCHEMA
from iotml.obs import federate, metrics as obs_metrics, tracing, watermark
from iotml.ops import framing
from iotml.ops.avro import AvroCodec
from iotml.store import segment as seg
from iotml.stream import native as native_mod
from iotml.stream.broker import Broker
from iotml.stream.consumer import StreamConsumer
from iotml.stream.kafka_wire import KafkaWireBroker, KafkaWireServer
from iotml.stream.producer import RawBatchProducer

NATIVE = native_mod.available()
needs_native = pytest.mark.skipif(not NATIVE,
                                  reason="C++ engine not built")

CODEC = AvroCodec(KSQL_CAR_SCHEMA)
BASE_TS = 1_700_000_000_000  # a real wall-clock ms epoch


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.configure(enabled=False, sample=1.0, path="")
    tracing.reset()


def _record(rng, label="false"):
    rec = {}
    for f in KSQL_CAR_SCHEMA.fields:
        if f.name == "FAILURE_OCCURRED":
            rec[f.name] = label
        elif f.avro_type in ("int", "long"):
            rec[f.name] = int(rng.integers(0, 40))
        else:
            rec[f.name] = float(rng.normal())
    return rec


def _frames(n=32, base_offset=0, ts0=BASE_TS, tombstone_at=()):
    rng = np.random.default_rng(5)
    out = []
    for i in range(n):
        key = f"car-{i % 5}".encode()
        if i in tombstone_at:
            out.append(seg.encode_record(base_offset + i, key, None,
                                         ts0 + i, None))
        else:
            payload = framing.frame(CODEC.encode(_record(rng)), 1)
            out.append(seg.encode_record(base_offset + i, key, payload,
                                         ts0 + i, None))
    return b"".join(out)


def _fill(broker, topic="T", n=64, partitions=1, ts0=BASE_TS):
    broker.create_topic(topic, partitions=partitions)
    rng = np.random.default_rng(3)
    for p in range(partitions):
        broker.produce_many(
            topic,
            [(f"car-{i % 5}".encode(),
              framing.frame(CODEC.encode(_record(rng)), 1), ts0 + i)
             for i in range(n)], partition=p)


# ------------------------------------------------- event-time watermarks
@needs_native
def test_frame_decoder_reports_event_time_bounds():
    """The native decoder's ts min/max out-params match the oracle,
    tombstones included (both advance the watermark)."""
    nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
    dec = nc.frame_decoder()
    buf = _frames(n=24, tombstone_at=(3, 20))
    out_n = np.zeros((64, nc.n_numeric), np.float32)
    out_l = np.zeros((64, nc.n_strings), "S16")
    rows, next_off, flags, skipped = dec.decode_into(buf, 0, out_n, out_l)
    assert rows == 22 and skipped == 2 and next_off == 24
    assert (dec.last_ts_min, dec.last_ts_max) == (BASE_TS, BASE_TS + 23)
    # oracle parity (want_ts grows the tuple; the default stays 6-wide)
    *_, py_min, py_max = framing.decode_frames_columnar_py(
        buf, 0, KSQL_CAR_SCHEMA, want_ts=True)
    assert (py_min, py_max) == (BASE_TS, BASE_TS + 23)
    # a cursor past the head only counts consumed frames
    rows, *_ = dec.decode_into(buf, 10, out_n, out_l)
    assert dec.last_ts_min == BASE_TS + 10
    # nothing consumed → -1 sentinels
    rows, *_ = dec.decode_into(b"", 0, out_n, out_l)
    assert rows == 0 and dec.last_ts_min == -1 and dec.last_ts_max == -1


@needs_native
def test_poll_into_publishes_consume_watermark(tmp_path):
    """poll_into folds decoder event time into the consumer accumulation
    AND the consume-stage watermark metric, batch-granularly."""
    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=48)
    nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
    cons = StreamConsumer(broker, ["T:0:0"], group="wm")
    out_n = np.zeros((4096, nc.n_numeric), np.float32)
    out_l = np.zeros((4096, nc.n_strings), "S16")
    key = ('iotml_watermark_lag_seconds_count'
           '{group="wm",partition="0",stage="consume",topic="T"}')
    before = obs_metrics.default_registry.collect().get(key, 0.0)
    rows, fb = cons.poll_into(nc.frame_decoder(), out_n, out_l)
    assert rows == 48
    taken = cons.take_event_time()
    assert taken == {("T", 0): (BASE_TS, BASE_TS + 47)}
    assert cons.take_event_time() == {}  # cleared on read
    after = obs_metrics.default_registry.collect().get(key, 0.0)
    assert after > before
    # the watermark gauge carries the newest processed event time,
    # group-labeled (two consumers of one partition are two frontiers)
    assert obs_metrics.watermark_event_ms.value(
        stage="consume", topic="T", partition=0,
        group="wm") == BASE_TS + 47
    broker.close()


def test_classic_poll_folds_event_time():
    """The classic message path folds batch-endpoint timestamps, so
    non-columnar consumers watermark too."""
    broker = Broker()
    _fill(broker, n=16)
    cons = StreamConsumer(broker, ["T:0:0"], group="wm2")
    msgs = cons.poll(1024)
    assert len(msgs) == 16
    assert cons.take_event_time() == {("T", 0): (BASE_TS, BASE_TS + 15)}
    assert obs_metrics.watermark_event_ms.value(
        stage="consume", topic="T", partition=0,
        group="wm2") == BASE_TS + 15


def test_observe_taken_rejects_open_vocabulary():
    with pytest.raises(ValueError):
        watermark.observe("car_17", "T", 0, BASE_TS, BASE_TS)


def test_scorer_drain_publishes_score_watermark(tmp_path):
    """A completed scorer drain takes the consumer's event-time ranges
    as the ingest→score watermark — e2e staleness with zero per-record
    cost."""
    from iotml.data.dataset import SensorBatches
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.serve.scorer import StreamScorer
    from iotml.stream.producer import OutputSequence
    from iotml.train.loop import Trainer

    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=120)
    broker.create_topic("OUT")
    cons = StreamConsumer(broker, ["T:0:0"], group="score-wm", eof=True)
    sb = SensorBatches(cons, batch_size=20, keep_labels=True)
    tr = Trainer(CAR_AUTOENCODER)
    tr._ensure_state(np.zeros((20, 18), np.float32))
    before = obs_metrics.watermark_event_ms.value(
        stage="score", topic="T", partition=0, group="score-wm")
    scorer = StreamScorer(CAR_AUTOENCODER, tr.state.params, sb,
                          OutputSequence(broker, "OUT"))
    n = scorer.score_available()
    assert n == 120
    assert obs_metrics.watermark_event_ms.value(
        stage="score", topic="T", partition=0,
        group="score-wm") == BASE_TS + 119 > before
    broker.close()


# -------------------------------------------------- columnar liveness fix
@needs_native
def test_columnar_consume_keeps_stage_liveness_fresh(tmp_path):
    """Regression (ISSUE 13 satellite): a traced session consuming over
    the COLUMNAR path materialises no records and forks no per-record
    spans — stage liveness must still see the consume stage beat, or
    /healthz reports a healthy pipeline as stalled."""
    tracing.configure(enabled=True, sample=1.0)
    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=32)
    with KafkaWireServer(broker) as srv:
        wb = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
        cons = StreamConsumer(wb, ["T:0:0"], group="live")
        out_n = np.zeros((4096, nc.n_numeric), np.float32)
        out_l = np.zeros((4096, nc.n_strings), "S16")
        rows, _fb = cons.poll_into(nc.frame_decoder(), out_n, out_l)
        assert rows == 32
        ages = tracing.liveness()
        assert "consume" in ages and ages["consume"] < 5.0
        wb.close()
    broker.close()


# ---------------------------------------------------- wire batch traces
def test_stamp_and_extract_first_frame_headers():
    buf = _frames(n=8)
    ctx = tracing.TraceContext()
    stamped = framing.stamp_first_frame(
        buf, (("iotml_trace", ctx.encode()),))
    hdrs = framing.first_frame_headers(stamped)
    assert hdrs and hdrs[0][0] == "iotml_trace"
    got = tracing.TraceContext.decode(hdrs[0][1])
    assert got is not None and got.trace_id == ctx.trace_id
    # the stamped batch still CRC-validates and restamps whole
    restamped, count, max_ts = framing.restamp_frame_batch(stamped, 100)
    assert count == 8 and max_ts == BASE_TS + 7
    # other frames untouched byte-for-byte
    entries = list(framing.iter_frame_entries(stamped))
    assert len(entries) == 8 and entries[1][4] is None


@needs_native
def test_wire_batch_trace_end_to_end(tmp_path):
    """RAW_PRODUCE → segment → RAW_FETCH → poll_into: one sampled batch
    trace survives the wire in frame headers, is marked at each hop,
    and closes with an e2e span at the pipeline closer."""
    spans = str(tmp_path / "spans.jsonl")
    tracing.configure(enabled=True, sample=1.0, path=spans)
    broker = Broker(store_dir=str(tmp_path / "store"))
    broker.create_topic("T", partitions=1)
    nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
    rng = np.random.default_rng(0)
    n = 40
    numeric = rng.normal(size=(n, nc.n_numeric))
    labels = np.full((n, nc.n_strings), b"false", "S16")
    ts = np.arange(BASE_TS, BASE_TS + n, dtype=np.int64)
    frames = nc.encode_frames(numeric, labels, timestamps=ts, schema_id=1)
    with KafkaWireServer(broker) as srv:
        wb = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        prod = RawBatchProducer(wb, "T")
        base = prod.produce_frames(0, frames, n)
        assert base == 0 and prod.engaged is True
        cons = StreamConsumer(wb, ["T:0:0"], group="bt")
        out_n = np.zeros((4096, nc.n_numeric), np.float32)
        out_l = np.zeros((4096, nc.n_strings), "S16")
        # drain in SLICES smaller than the batch: later raw reads are
        # sparse-index aligned and re-serve the stamped batch head —
        # the cursor gate must extract the context exactly ONCE
        dec = nc.frame_decoder()
        total = 0
        while True:
            rows, _fb = cons.poll_into(dec, out_n, out_l, max_rows=16)
            if rows == 0:
                break
            total += rows
        assert total == n
        traces = cons.take_batch_traces()
        assert len(traces) == 1
        for ctx in traces:
            ctx.close("score")
        wb.close()
    tracing.flush()
    stages = set()
    kinds = set()
    for line in open(spans):
        doc = json.loads(line)
        kinds.add(doc["kind"])
        if doc["kind"] == "span":
            stages.add(doc["stage"])
        assert "proc" in doc or doc["kind"] not in ("span", "e2e")
    assert {"raw_produce", "raw_produce_append", "wire_raw_produce",
            "wire_raw_fetch", "consume", "score"} <= stages
    assert "batch" in kinds and "e2e" in kinds
    broker.close()


def test_trace_cli_cross_process_reconstruction(tmp_path, capsys):
    """`iotml.obs trace --require-cross-process N` passes on a log whose
    closed trace spans N procs and fails otherwise."""
    from iotml.obs.__main__ import main as obs_main

    path = str(tmp_path / "fleet.jsonl")
    tid = "00000000deadbeef"
    lines = [
        {"kind": "span", "trace": tid, "stage": "raw_produce",
         "start_us": 0, "dur_us": 80, "wall0_ns": 1, "proc": "bridge"},
        {"kind": "span", "trace": tid, "stage": "wire_raw_fetch",
         "start_us": 120, "dur_us": 10, "wall0_ns": 1, "proc": "shard-0"},
        {"kind": "span", "trace": tid, "stage": "consume",
         "start_us": 200, "dur_us": 40, "wall0_ns": 1, "proc": "scorer"},
        {"kind": "batch", "trace": tid, "stage": "consume", "topic": "T",
         "partition": 0, "first_offset": 0, "last_offset": 39, "n": 40,
         "wall0_ns": 1, "proc": "scorer"},
        {"kind": "span", "trace": tid, "stage": "score",
         "start_us": 260, "dur_us": 500, "wall0_ns": 1, "proc": "scorer"},
        {"kind": "e2e", "trace": tid, "closer": "score", "dur_us": 760,
         "wall0_ns": 1, "proc": "scorer"},
    ]
    with open(path, "w") as fh:
        fh.write("\n".join(json.dumps(d) for d in lines) + "\n")
    assert obs_main(["trace", path, "--require-cross-process", "3",
                     "--show-trace"]) == 0
    out = capsys.readouterr().out
    assert "3 process(es)" in out and "shard-0" in out
    assert "offsets 0-39" in out
    assert obs_main(["trace", path, "--require-cross-process", "4"]) == 1


# ---------------------------------------------------------- consumer lag
@needs_native
def test_raw_fetch_carries_hwm(tmp_path):
    """The columnar path feeds consumer lag with ZERO extra round
    trips: RAW_FETCH responses carry the hwm as a trailing-optional
    field, so a pure-poll_into consumer never needs end_offset."""
    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=40)
    nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
    with KafkaWireServer(broker) as srv:
        wb = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        cons = StreamConsumer(wb, ["T:0:0"], group="rawlag")
        out_n = np.zeros((16, nc.n_numeric), np.float32)
        out_l = np.zeros((16, nc.n_strings), "S16")
        rows, _fb = cons.poll_into(nc.frame_decoder(), out_n, out_l,
                                   max_rows=16)
        assert rows == 16
        assert wb.last_hwm("T", 0) == 40  # from the RAW_FETCH response
        assert cons.record_lag() == 24
        wb.close()
    broker.close()


def test_consumer_lag_gauge_wire_and_local(tmp_path):
    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=50)
    with KafkaWireServer(broker) as srv:
        wb = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        cons = StreamConsumer(wb, ["T:0:0"], group="lagg")
        cons.poll(20)
        # classic fetch cached the hwm: record_lag needs no round trip
        assert wb.last_hwm("T", 0) == 50
        total = cons.record_lag()
        assert total == 30
        assert obs_metrics.consumer_lag_records.value(
            group="lagg", topic="T", partition=0) == 30
        cons.commit()  # commit refreshes too
        wb.close()
    # in-process broker: end_offset fallback
    cons2 = StreamConsumer(broker, ["T:0:10"], group="lagh")
    assert cons2.record_lag() == 40
    broker.close()


def test_healthz_carries_watermarks_and_lag(tmp_path):
    obs_metrics.watermark_event_ms.set(BASE_TS, stage="twin", topic="T",
                                       partition=2)
    obs_metrics.consumer_lag_records.set(11, group="g2", topic="T",
                                         partition=2)
    srv = obs_metrics.start_http_server(0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.server_address[1]}/healthz").read()
        doc = json.loads(body)
        assert doc["watermarks"]["twin:T:2"]["event_time_ms"] == BASE_TS
        assert doc["watermarks"]["twin:T:2"]["lag_s"] > 0
        assert doc["consumer_lag_records"]["g2:T:2"] == 11
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------------------ federation
def test_prom_text_parser_roundtrip():
    text = ('# HELP x h\n# TYPE iotml_x_total counter\n'
            'iotml_x_total{topic="a\\"b",stage="s"} 3.5\n'
            'iotml_plain 1\n'
            'garbage line without value\n')
    types, samples = federate.parse_prom_text(text)
    assert types == {"iotml_x_total": "counter"}
    assert ("iotml_x_total", {"topic": 'a"b', "stage": "s"}, 3.5) in samples
    assert ("iotml_plain", {}, 1.0) in samples


def test_federation_merges_and_rolls_up(tmp_path):
    srv = obs_metrics.start_http_server(0)
    obs_metrics.records_scored.inc(25)
    obs_metrics.consumer_lag_records.set(4, group="fg", topic="FT",
                                         partition=1)
    addr = f"127.0.0.1:{srv.server_address[1]}"
    try:
        col = federate.FleetCollector(
            endpoints=[{"name": "a", "address": addr},
                       {"name": "b", "address": addr},
                       {"name": "dead", "address": "127.0.0.1:1"}])
        snaps = col.collect()
        text = col.render(snaps)
        assert 'iotml_cluster_up{process="dead"} 0' in text
        assert "iotml_cluster_processes 2" in text
        assert 'iotml_records_scored_total{process="a"}' in text
        assert "iotml_cluster_records_scored_total" in text
        lag_line = [l for l in text.splitlines()
                    if l.startswith("iotml_cluster_consumer_lag_records")
                    and 'group="fg"' in l]
        assert lag_line and lag_line[0].endswith(" 8.0")  # 4 × 2 procs
        hz = col.healthz(snaps)
        assert hz["up_count"] == 2 and "dead" in hz["degraded"]
        # compacted changelog: snapshot + replay
        broker = Broker()
        col.snapshot_changelog(broker, snaps)
        assert broker.topic(federate.METRICS_TOPIC).cleanup_policy == \
            "compact"
        state = federate.read_fleet_state(broker)
        assert state["a"]["up"] is True and "dead" in state
    finally:
        srv.shutdown()
        srv.server_close()


def test_fleet_cli_once_and_manifest(tmp_path, capsys):
    from iotml.obs.__main__ import main as obs_main

    srv = obs_metrics.start_http_server(0)
    man = str(tmp_path / "endpoints.json")
    addr = f"127.0.0.1:{srv.server_address[1]}"
    federate.publish_endpoint(man, "p1", addr)
    federate.publish_endpoint(man, "p2", addr)
    federate.publish_endpoint(man, "p1", addr)  # replace, not duplicate
    assert [e["name"] for e in federate.load_manifest(man)] == ["p1", "p2"]
    try:
        assert obs_main(["fleet", "--endpoints", man, "--once",
                         "--min-processes", "2"]) == 0
        capsys.readouterr()
        assert obs_main(["fleet", "--endpoints", man, "--once",
                         "--min-processes", "3"]) == 1
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------- metrics server under load
def test_metrics_server_concurrent_scrape():
    """N scraper threads hammer /metrics + /healthz while workers mutate
    every metric type: every response parses, no 5xx, no exception."""
    srv = obs_metrics.start_http_server(0)
    port = srv.server_address[1]
    stop = threading.Event()
    errors = []

    def work():
        i = 0
        while not stop.is_set():
            obs_metrics.records_consumed.inc()
            obs_metrics.watermark_event_ms.set(BASE_TS + i, stage="consume",
                                               topic="CT", partition=0)
            obs_metrics.step_seconds.observe(0.001, loop="score",
                                             phase="device_compute")
            i += 1

    def scrape(path):
        try:
            for _ in range(20):
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5).read()
                if path == "/healthz":
                    json.loads(body)
                else:
                    federate.parse_prom_text(body.decode())
        except Exception as e:  # noqa: BLE001 - collected for assert
            errors.append(e)

    workers = [threading.Thread(target=work, daemon=True)
               for _ in range(2)]
    scrapers = [threading.Thread(target=scrape, args=(p,), daemon=True)
                for p in ("/metrics", "/healthz", "/metrics")]
    try:
        for t in workers + scrapers:
            t.start()
        for t in scrapers:
            t.join(timeout=30)
    finally:
        stop.set()
        for t in workers:
            t.join(timeout=5)
        srv.shutdown()
        srv.server_close()
    assert not errors


# -------------------------------------------------- cardinality bound
def test_label_cardinality_bound(one_deployments_registry):
    """Labels come from closed sets: the default registry is clean, and
    a runaway car_id-style label fails the check before it fails
    production."""
    assert obs_metrics.cardinality_violations(
        obs_metrics.default_registry) == []
    reg = obs_metrics.Registry()
    c = reg.counter("iotml_bad_total")
    c.inc(**{"car_id": "car-1"})
    v = obs_metrics.cardinality_violations(reg)
    assert v and "car_id" in v[0][1]
    # series-count bound: one value per "entity" explodes
    reg2 = obs_metrics.Registry()
    g = reg2.gauge("iotml_worse")
    for i in range(obs_metrics.MAX_LABEL_SERIES + 1):
        g.set(1.0, **{"topic": f"t{i}"})
    v2 = obs_metrics.cardinality_violations(reg2)
    assert v2 and "cardinality bound" in v2[0][1]


# ------------------------------------------------- profiling hot loops
def test_step_seconds_phases_recorded(tmp_path):
    """A train round and a prefetcher pass populate the
    loop×phase step histogram and the occupancy gauge."""
    from iotml.data.dataset import Batch
    from iotml.data.prefetch import DevicePrefetcher

    before = obs_metrics.default_registry.collect()
    batches = [Batch(np.zeros((4, 18), np.float32), 4, i * 4)
               for i in range(3)]
    with DevicePrefetcher(iter(batches), depth=2, loop="score") as pf:
        assert len(list(pf)) == 3
    after = obs_metrics.default_registry.collect()
    key = 'iotml_step_seconds_count{loop="score",phase="host_wait"}'
    # one observation per dequeue (3 batches + the end sentinel)
    assert after.get(key, 0.0) - before.get(key, 0.0) == 4.0
    assert "iotml_prefetch_occupancy" in \
        obs_metrics.default_registry.render()


def test_fit_compiled_records_device_and_host_phases(tmp_path):
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.train.loop import Trainer
    from iotml.data.dataset import Batch

    before = obs_metrics.default_registry.collect()
    batches = [Batch(np.random.default_rng(1).normal(
        size=(8, 18)).astype(np.float32), 8, i * 8) for i in range(2)]
    Trainer(CAR_AUTOENCODER).fit_compiled(batches, epochs=1)
    after = obs_metrics.default_registry.collect()
    for phase in ("host_pipeline", "device_compute"):
        key = f'iotml_step_seconds_count{{loop="train",phase="{phase}"}}'
        assert after.get(key, 0.0) > before.get(key, 0.0), phase
