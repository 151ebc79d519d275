"""iotml.gateway — sharded scatter-gather twin serving (ISSUE 20):
key→partition→shard policy, shard ownership + 421 fencing, the smart
client (point / batch / fan-out / feature-join), the dumb-client
router REST surface, standby byte-equality across compaction and
failover, the REST serving disciplines (per-request metrics, bounded
concurrency, named handler threads, crash-shaped kill), connect /twin
pagination, and the federated multi-front fleet."""

import http.client
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from iotml.core.schema import KSQL_CAR_SCHEMA
from iotml.gateway import (FrontProcess, GatewayClient, GatewayCluster,
                           GatewayError, GatewayRouter, front_for,
                           partition_for_key, run_federated_fleet,
                           shard_for_key)
from iotml.store import StorePolicy
from iotml.stream.broker import Broker
from iotml.twin import CHANGELOG_TOPIC, TwinFeatureStore, TwinService
from iotml.utils.rest import (RestServer, rest_request_seconds,
                              rest_requests)

IN = "SENSOR_DATA_S_AVRO"
F = len(KSQL_CAR_SCHEMA.sensor_fields)


def _publish(broker, n_ticks=6, cars=8, seed=5, partitions=4):
    from iotml.gen.simulator import FleetGenerator, FleetScenario

    gen = FleetGenerator(FleetScenario(num_cars=cars, seed=seed,
                                       failure_rate=0.2))
    return gen.publish(broker, IN, n_ticks=n_ticks, partitions=partitions)


def _await(cond, timeout_s=20.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() >= deadline:
            raise TimeoutError(f"{what} not reached in {timeout_s}s")
        time.sleep(0.02)


# --------------------------------------------------------- pure policy
def test_partition_policy_matches_broker_keyed_produce():
    """partition_for_key IS the broker's keyed partitioner: a record
    produced by key lands exactly where the gateway computes it will."""
    b = Broker()
    b.create_topic("t", partitions=4)
    keys = [f"car_{i}" for i in range(32)]
    for k in keys:
        b.produce("t", b"v", key=k.encode())
    for k in keys:
        p = partition_for_key(k, 4)
        assert any(m.key == k.encode()
                   for m in b.fetch("t", p, 0, 1 << 20))
    # shard policy composes: partition % n_shards, stable for str/bytes
    for k in keys:
        assert shard_for_key(k, 4, 2) == partition_for_key(k, 4) % 2
        assert partition_for_key(k.encode(), 4) == partition_for_key(k, 4)


def test_front_for_is_consistent_and_total():
    ids = [f"car_{i}" for i in range(100)]
    assign = [front_for(c, 3) for c in ids]
    assert assign == [front_for(c, 3) for c in ids]  # pure
    assert set(assign) == {0, 1, 2}  # every front gets cars
    assert all(0 <= a < 3 for a in assign)


# --------------------------------------------------- shards + ownership
def test_shard_ownership_info_and_421_fencing():
    b = Broker()
    b.create_topic(IN, partitions=4)
    _publish(b)
    cluster = GatewayCluster(b, n_shards=2, standbys=False).start()
    try:
        client = GatewayClient(cluster)
        _await(lambda: client.count() == 8, what="shards drained")
        infos = [json.loads(urllib.request.urlopen(
            f"{s.url}/shard/info", timeout=5).read())
            for s in cluster.shards]
        assert infos[0]["partitions"] == [0, 2]
        assert infos[1]["partitions"] == [1, 3]
        assert sum(i["count"] for i in infos) == 8
        # a direct hit on the WRONG shard is fenced with 421, never an
        # answer — the smart client's refresh-and-retry cue
        car = next(c for c in client.cars() if client.shard_of(c) == 0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{cluster.shards[1].url}/shard/twin/{car}", timeout=5)
        assert ei.value.code == 421
        client.close()
    finally:
        cluster.stop()


def test_gateway_client_point_batch_and_fanout_queries():
    b = Broker()
    b.create_topic(IN, partitions=4)
    published = _publish(b)
    # reference answers come from a single unsharded read-only tap
    ref = TwinService(b, group="gw-test-ref", changelog=False)
    while ref.pump_once():
        pass
    cluster = GatewayCluster(b, n_shards=2, standbys=False).start()
    client = GatewayClient(cluster)
    try:
        _await(lambda: client.aggregate()["records"] == published,
               what="shards drained")
        cars = client.cars()
        assert cars == ref.cars() and len(cars) == 8
        # point lookups route by key hash and agree with the tap
        for car in cars:
            doc = client.get(car)
            assert doc == ref.get(car)
        assert client.get("no-such-car") is None
        # batched lookups: slim docs in request order, None = unknown
        got = client.mget(cars + ["ghost"])
        assert got[-1] is None
        for car, slim in zip(cars, got):
            full = ref.get(car)
            assert slim["car"] == car
            assert slim["offset"] == full["offset"]
            assert slim["ts"] == full["timestamp_ms"]
            assert slim["count"] > 0
            assert slim["partition"] == partition_for_key(car, 4)
        # fan-out merges equal the unsharded fold
        assert client.count() == ref.count()
        agg = client.aggregate()
        assert agg["records"] == published
        assert agg["cars"] == 8
        # pagination through the client fan-out
        assert client.cars(limit=3) == cars[:3]
        assert client.cars(limit=3, offset=6) == cars[6:]
        # retire travels to the owning shard; the car is gone fleet-wide
        assert client.retire(cars[0]) and client.get(cars[0]) is None
        assert not client.retire(cars[0])
    finally:
        client.close()
        cluster.stop()


def test_gateway_client_duck_types_feature_store():
    """StreamScorer(feature_store=client): matrix/vector/dim through
    the sharded plane match the local TwinFeatureStore join."""
    b = Broker()
    b.create_topic(IN, partitions=4)
    _publish(b)
    # same group label as the other test's tap: consumer group is a
    # watermark-series dimension, and the suite-wide registry pins a
    # cardinality bound — taps with identical topic/partition coverage
    # share one frontier name instead of minting new series
    ref = TwinService(b, group="gw-test-ref", changelog=False)
    while ref.pump_once():
        pass
    fs = TwinFeatureStore(ref)
    cluster = GatewayCluster(b, n_shards=2, standbys=False).start()
    client = GatewayClient(cluster)
    try:
        _await(lambda: client.count() == 8, what="shards drained")
        assert client.dim == fs.dim
        keys = [c.encode() for c in ref.cars()] + [None, b"ghost"]
        n = len(keys) + 2  # padding rows
        local = fs.matrix(keys, n)
        remote = client.matrix(keys, n)
        assert remote.shape == (n, fs.dim)
        assert np.allclose(remote, local, atol=1e-6)
        assert remote[:8].any() and not remote[8:].any()
        v = client.vector(keys[0])
        assert np.allclose(v, fs.vector(keys[0]), atol=1e-6)
    finally:
        client.close()
        cluster.stop()


# ------------------------------------------------------------- router
def test_gateway_router_rest_surface():
    b = Broker()
    b.create_topic(IN, partitions=4)
    _publish(b)
    cluster = GatewayCluster(b, n_shards=2, standbys=False).start()
    client = GatewayClient(cluster)
    rest = RestServer(name="iotml-gw-router-test")
    GatewayRouter(cluster, client).mount(rest)
    rest.start()
    try:
        _await(lambda: client.count() == 8, what="shards drained")
        # the routing map smart clients bootstrap from
        mp = json.loads(urllib.request.urlopen(
            f"{rest.url}/gateway/map", timeout=5).read())
        assert mp["n_shards"] == 2 and mp["n_partitions"] == 4
        assert [s["shard"] for s in mp["shards"]] == [0, 1]
        assert all(s["url"].startswith("http://") for s in mp["shards"])
        # a second smart client bootstraps from the URL, not the object
        remote = GatewayClient(rest.url)
        cars = remote.cars()
        assert len(cars) == 8
        remote.close()
        # GET /twin pagination fans out and merges
        page = json.loads(urllib.request.urlopen(
            f"{rest.url}/twin?limit=3", timeout=5).read())
        assert page["count"] == 8 and page["cars"] == cars[:3]
        assert page["next_offset"] == 3
        last = json.loads(urllib.request.urlopen(
            f"{rest.url}/twin?limit=5&offset=3", timeout=5).read())
        assert last["cars"] == cars[3:] and last["next_offset"] is None
        fast = json.loads(urllib.request.urlopen(
            f"{rest.url}/twin?count_only=1", timeout=5).read())
        assert fast == {"count": 8}
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{rest.url}/twin?limit=x", timeout=5)
        assert ei.value.code == 400
        # proxied point lookup + batched dumb-client mget
        doc = json.loads(urllib.request.urlopen(
            f"{rest.url}/twin/{cars[0]}", timeout=5).read())
        assert doc["car"] == cars[0] and "aggregates" in doc
        req = urllib.request.Request(
            f"{rest.url}/gateway/mget",
            data=json.dumps({"keys": [cars[0], "ghost"]}).encode(),
            headers={"Content-Type": "application/json"})
        got = json.loads(urllib.request.urlopen(req, timeout=5).read())
        assert got["docs"][0]["car"] == cars[0]
        assert got["docs"][1] is None
        agg = json.loads(urllib.request.urlopen(
            f"{rest.url}/gateway/aggregate", timeout=5).read())
        assert agg["cars"] == 8
        # proxied retire
        req = urllib.request.Request(f"{rest.url}/twin/{cars[0]}",
                                     method="DELETE")
        assert urllib.request.urlopen(req, timeout=5).status == 204
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{rest.url}/twin/{cars[0]}",
                                   timeout=5)
    finally:
        rest.stop()
        client.close()
        cluster.stop()


# --------------------------------- standbys: rebalance + failover (S3)
def test_standby_byte_identical_across_compaction_and_failover(tmp_path):
    """TwinService(partitions=...) under live rebalance: each shard's
    warm standby rebuilds byte-for-byte equal to its primary across a
    compaction pass, and a killed shard's standby promotes into a
    primary serving the exact pre-kill state."""
    b = Broker(store_dir=str(tmp_path),
               store_policy=StorePolicy(fsync="never",
                                        segment_bytes=8 * 1024,
                                        compact_grace_ms=10 ** 9))
    b.create_topic(IN, partitions=4)
    cluster = GatewayCluster(b, n_shards=2).start()
    client = GatewayClient(cluster)
    published = 0
    try:
        # tick-by-tick with drain barriers: every tick re-emits each
        # car's changelog record, so compaction has versions to fold
        for _ in range(4):
            published += _publish(b, n_ticks=1)
            _await(lambda: client.aggregate()["records"] == published,
                   what="shards drained")
        _await(lambda: all(s.lag() == 0
                           for s in cluster.standbys.values()),
               what="standby catch-up")
        # force a compaction pass over the changelog, then more traffic:
        # the standby replays the COMPACTED form + the live tail and
        # must still land on identical bytes
        for p in range(4):
            b.store.log_for(CHANGELOG_TOPIC, p).roll()
        stats = b.run_compaction(force=True)
        assert sum(s.records_removed for s in stats.values()) > 0
        published += _publish(b, n_ticks=2)
        _await(lambda: client.aggregate()["records"] == published,
               what="post-compaction drain")
        _await(lambda: all(s.lag() == 0
                           for s in cluster.standbys.values()),
               what="post-compaction standby catch-up")
        for shard in cluster.shards:
            assert (cluster.standbys[shard.shard_id].table.snapshot()
                    == shard.service.table.snapshot())
        # failover: kill shard 0, promote its standby, exact state
        pre_kill = cluster.shards[0].service.table.snapshot()
        pre_cars = [c for c in client.cars() if client.shard_of(c) == 0]
        cluster.kill_shard(0)
        promote_s = cluster.promote(0)
        assert promote_s < GatewayCluster.PROMOTE_SLO_S
        assert cluster.shards[0].service.table.snapshot() == pre_kill
        client.refresh()
        for car in pre_cars:
            assert client.get(car)["car"] == car
        assert client.aggregate()["records"] == published
        # the promoted primary is shadowed by a FRESH standby
        _await(lambda: cluster.standbys[0].lag() == 0,
               what="fresh standby catch-up")
        assert (cluster.standbys[0].table.snapshot()
                == cluster.shards[0].service.table.snapshot())
    finally:
        client.close()
        cluster.stop()
        b.close()


def test_client_survives_shard_kill_mid_queries():
    """A client holding persistent connections observes the kill as a
    connection error (never a zombie answer) and retries onto the
    promoted shard within its deadline."""
    b = Broker()
    b.create_topic(IN, partitions=4)
    _publish(b)
    cluster = GatewayCluster(b, n_shards=2).start()
    client = GatewayClient(cluster, retry_deadline_s=10.0)
    try:
        _await(lambda: client.count() == 8, what="shards drained")
        cars0 = [c for c in client.cars() if client.shard_of(c) == 0]
        assert client.get(cars0[0])["car"] == cars0[0]  # conn warm
        _await(lambda: cluster.standbys[0].lag() == 0,
               what="standby catch-up")
        cluster.kill_shard(0)
        cluster.promote(0)
        # same client object, same keys: answered by the new primary
        for car in cars0:
            assert client.get(car)["car"] == car
        assert client.refreshes >= 2  # the retry path actually ran
    finally:
        client.close()
        cluster.stop()


# ------------------------------------------- REST serving disciplines
def test_rest_per_request_metrics():
    srv = RestServer(name="iotml-rest-mtest")
    srv.route("GET", r"/ping", lambda m, body: (200, {"pong": True}))
    srv.start()
    try:
        base_ok = rest_requests.value(route=r"/ping", code=200)
        base_404 = rest_requests.value(route="(unmatched)", code=404)
        for _ in range(3):
            urllib.request.urlopen(f"{srv.url}/ping", timeout=5).read()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
        # the counters land in a `finally` AFTER the response bytes are
        # written — the client can observe the reply before the handler
        # thread is rescheduled, so await rather than assert instantly
        _await(lambda: rest_requests.value(route=r"/ping", code=200)
               == base_ok + 3, timeout_s=5.0, what="ping counter")
        _await(lambda: rest_requests.value(route="(unmatched)", code=404)
               == base_404 + 1, timeout_s=5.0, what="404 counter")
        # the latency series is keyed by the registered PATTERN (a
        # closed set), never by the concrete path
        assert 'route="/ping"' in rest_request_seconds.render()
    finally:
        srv.stop()


def test_rest_concurrency_guard_sheds_with_503():
    srv = RestServer(name="iotml-rest-gtest", max_concurrency=2)
    srv.route("GET", r"/ping", lambda m, body: (200, {"pong": True}))
    srv.start()
    held = []
    try:
        base = rest_requests.value(route="(guard)", code=503)
        # two keep-alive connections occupy both slots (the guard
        # bounds CONNECTIONS — each holds its handler thread)
        for _ in range(2):
            c = http.client.HTTPConnection(srv.host, srv.port, timeout=5)
            c.request("GET", "/ping")
            assert c.getresponse().read() == b'{"pong": true}'
            held.append(c)
        _await(lambda: srv.active_connections() == 2,
               what="both slots held")
        # handler threads are daemon, named and discoverable (R8)
        names = [t.name for t in threading.enumerate()
                 if t.name.startswith("iotml-rest-gtest-h")]
        assert len(names) == 2
        # the third connection is shed with a raw 503 BEFORE a handler
        # thread exists, and told not to retry on this socket
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/ping", timeout=5)
        assert ei.value.code == 503
        assert ei.value.headers["Connection"] == "close"
        # the counter lands after the response's bytes: await it
        _await(lambda: rest_requests.value(route="(guard)", code=503)
               == base + 1, what="the shed connection counted")
        # freeing a slot readmits new connections
        held.pop().close()
        _await(lambda: srv.active_connections() == 1,
               what="slot released")
        doc = json.loads(urllib.request.urlopen(
            f"{srv.url}/ping", timeout=5).read())
        assert doc == {"pong": True}
    finally:
        for c in held:
            c.close()
        srv.stop()


def test_rest_max_concurrency_env(monkeypatch):
    monkeypatch.setenv("IOTML_REST_MAX_CONCURRENCY", "7")
    srv = RestServer(name="iotml-rest-env")
    assert srv.max_concurrency == 7
    srv.httpd.server_close()
    monkeypatch.setenv("IOTML_REST_MAX_CONCURRENCY", "zero")
    with pytest.raises(ValueError, match="not an integer"):
        RestServer(name="iotml-rest-env2")
    monkeypatch.setenv("IOTML_REST_MAX_CONCURRENCY", "0")
    with pytest.raises(ValueError, match=">= 1"):
        RestServer(name="iotml-rest-env3")


def test_rest_kill_severs_established_keepalive():
    """kill() must look like a crash to clients on persistent
    connections: shutdown() alone leaves handler threads answering on
    old sockets — a zombie serving stale state is a WRONG answer."""
    srv = RestServer(name="iotml-rest-ktest")
    srv.route("GET", r"/ping", lambda m, body: (200, {"pong": True}))
    srv.start()
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=5)
    try:
        conn.request("GET", "/ping")
        assert conn.getresponse().read() == b'{"pong": true}'
        srv.kill()
        with pytest.raises((OSError, http.client.HTTPException)):
            conn.request("GET", "/ping")
            conn.getresponse()
    finally:
        conn.close()


# --------------------------------------- connect /twin pagination (S1)
def test_connect_twin_listing_paginates():
    from iotml.connect import ConnectServer, ConnectWorker

    b = Broker()
    b.create_topic(IN, partitions=2)
    _publish(b, partitions=2)
    svc = TwinService(b)
    while svc.pump_once():
        pass
    srv = ConnectServer(ConnectWorker(b)).start()
    try:
        srv.attach_twin(svc)
        cars = svc.cars()
        # count_only fast path materialises no id list
        fast = json.loads(urllib.request.urlopen(
            f"{srv.url}/twin?count_only=true", timeout=5).read())
        assert fast["count"] == 8 and "cars" not in fast
        # page walk via next_offset reconstructs the full listing
        walked, offset = [], 0
        while offset is not None:
            page = json.loads(urllib.request.urlopen(
                f"{srv.url}/twin?limit=3&offset={offset}",
                timeout=5).read())
            assert len(page["cars"]) <= 3
            walked += page["cars"]
            offset = page["next_offset"]
        assert walked == cars
        # limit is clamped to the ceiling, never a megabyte id dump
        page = json.loads(urllib.request.urlopen(
            f"{srv.url}/twin?limit=999999", timeout=5).read())
        assert page["limit"] <= 10_000
        for bad in ("limit=x", "offset=-1"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/twin?{bad}", timeout=5)
            assert ei.value.code == 400
    finally:
        srv.stop()


# ---------------------------------------------------- federation (S0)
def test_topic_mapping_stream_key_validation():
    from iotml.mqtt.bridge import TopicMapping

    keyed = TopicMapping.sensor_data_keyed()
    assert keyed.stream_key == "car" and keyed.stream_topic == IN
    assert TopicMapping.sensor_data().stream_key == "topic"
    with pytest.raises(ValueError, match="stream_key"):
        TopicMapping(("a/#",), "t", stream_key="payload")


def test_publish_many_is_qos0_only():
    from iotml.mqtt.broker import MqttBroker
    from iotml.mqtt.wire import MqttClient, MqttServer

    core = MqttBroker(name="iotml-test-front")
    srv = MqttServer(core, port=0)
    srv.start()
    try:
        cli = MqttClient("127.0.0.1", srv.port, "qos-test", keepalive=0)
        try:
            assert cli.publish_many([("t/a", b"x"), ("t/b", b"y")]) == 2
            with pytest.raises(ValueError, match="QoS 0"):
                cli.publish_many([("t/a", b"x")], qos=1)
        finally:
            cli.disconnect()
    finally:
        srv.shutdown()
        srv.server_close()


def test_federated_fleet_small_end_to_end():
    """Scaled-down ISSUE-20 acceptance: two real front PROCESSES over
    the wire protocol, one keyed stream, a sharded gateway answering
    for cars that entered through every front."""
    report = run_federated_fleet(cars=40, fronts=2, ticks=1, shards=2,
                                 partitions=4, probe_per_front=2,
                                 timeout_s=120.0)
    assert report["ok"], report
    assert report["published"] == 40
    assert report["folded"] == 40
    assert report["fleet_cars_served"] == 40
    assert report["per_front_lookups_ok"] == [True, True]


# ------------------------------------------------------------ the drill
def test_gateway_drill_smoke():
    from iotml.gateway.drill import run_gateway_drill

    report = run_gateway_drill(seed=11, records=600, cars=20)
    assert report.ok, [i.detail for i in report.invariants if not i.ok]
    assert report.storm_wrong == 0
    assert report.slos["promote_s"] < GatewayCluster.PROMOTE_SLO_S
