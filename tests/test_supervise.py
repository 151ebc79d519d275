"""iotml.supervise — supervised lifecycles, fenced failover, live drills.

The live self-healing runtime (ISSUE 4): supervisor restart/degrade
semantics, the thread registry + lint R8 discipline, fenced leader
promotion over the wire protocol (epoch stamping both directions), the
replica's pause/resume barrier and live lag gauge, the streamproc
dead-letter queue, and the end-to-end drills with recovery SLOs.
"""

import json
import threading
import time
import urllib.request

import pytest

from iotml.obs import metrics as obs_metrics
from iotml.supervise import registry
from iotml.supervise.supervisor import (CRASHED, DEGRADED, FAILED_OVER,
                                        RUNNING, STOPPED, Supervisor)
from iotml.supervise.topology import Topology


def _wait_for(cond, timeout_s=10.0, interval_s=0.01):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


# ------------------------------------------------------------- registry
def test_register_thread_enforces_daemon_and_name():
    ok = registry.register_thread(
        threading.Thread(target=lambda: None, daemon=True,
                         name="iotml-test-worker"))
    assert ok.name == "iotml-test-worker"
    with pytest.raises(ValueError):  # non-daemon refused
        registry.register_thread(
            threading.Thread(target=lambda: None, name="iotml-x"))
    with pytest.raises(ValueError):  # default Thread-N name refused
        registry.register_thread(
            threading.Thread(target=lambda: None, daemon=True))


def test_registry_tracks_live_threads():
    stop = threading.Event()
    t = registry.register_thread(
        threading.Thread(target=stop.wait, daemon=True,
                         name="iotml-test-live"))
    t.start()
    try:
        assert any(x.name == "iotml-test-live" for x in registry.threads())
    finally:
        stop.set()
        t.join(timeout=5)


# ----------------------------------------------------------- supervisor
def test_loop_unit_restarts_after_crash():
    runs = []

    def loop(unit):
        runs.append(1)
        if len(runs) == 1:
            raise RuntimeError("first incarnation dies")
        while not unit.should_stop():
            unit.heartbeat()
            time.sleep(0.01)

    with Supervisor(poll_interval_s=0.01) as sup:
        u = sup.add_loop("flappy", loop)
        # (the state reads RUNNING before the second incarnation has run
        # a line: wait for its first line too)
        assert _wait_for(lambda: u.restarts >= 1 and u.state == RUNNING
                         and len(runs) == 2)
        assert u.last_error == "RuntimeError: first incarnation dies"
    assert obs_metrics.supervisor_restarts.value(unit="flappy") >= 1


def test_restart_storm_budget_gives_up_degraded():
    def loop(unit):
        raise RuntimeError("always dies")

    with Supervisor(poll_interval_s=0.01) as sup:
        u = sup.add_loop("doomed", loop, max_restarts=3,
                         restart_window_s=30.0)
        assert _wait_for(lambda: u.state == DEGRADED)
        # budget spent, then the supervisor STOPPED retrying
        assert u.restarts == 3
        assert sup.degraded() == ["doomed"]
        assert obs_metrics.supervisor_degraded.value(unit="doomed") == 1
        time.sleep(0.1)
        assert u.restarts == 3  # no restarts after giving up


def test_clean_stop_is_not_a_crash():
    def loop(unit):
        while not unit.should_stop():
            unit.heartbeat()
            time.sleep(0.005)

    sup = Supervisor(poll_interval_s=0.01).start()
    u = sup.add_loop("steady", loop)
    assert _wait_for(lambda: u.state == RUNNING and u.alive())
    sup.stop()
    assert u.state == STOPPED and u.restarts == 0


def test_loop_returning_normally_is_a_clean_stop_not_a_crash():
    def loop(unit):
        unit.heartbeat()  # finite work, then a normal return

    with Supervisor(poll_interval_s=0.01) as sup:
        u = sup.add_loop("finite", loop)
        assert _wait_for(lambda: u.state == STOPPED)
        assert u.restarts == 0 and u.last_error is None


def test_wedged_unit_detected_and_replaced():
    wedge = threading.Event()
    incarnations = []

    def loop(unit):
        incarnations.append(unit)
        unit.heartbeat()
        if len(incarnations) == 1:
            wedge.wait(30)  # alive but silent: no more heartbeats
            return
        while not unit.should_stop():
            unit.heartbeat()
            time.sleep(0.01)

    try:
        with Supervisor(poll_interval_s=0.02) as sup:
            u = sup.add_loop("sticky", loop, heartbeat_timeout_s=0.15)
            assert _wait_for(lambda: u.restarts >= 1 and len(incarnations) >= 2)
            assert obs_metrics.supervisor_wedged.value(unit="sticky") >= 1
    finally:
        wedge.set()


def test_probed_unit_on_death_fires_failover_once():
    alive = {"ok": True}
    fired = []

    with Supervisor(poll_interval_s=0.01) as sup:
        u = sup.add_probed("leader", lambda: alive["ok"],
                           on_death=fired.append, probe_failures=2)
        assert _wait_for(lambda: u.state == RUNNING)
        alive["ok"] = False
        assert _wait_for(lambda: u.state == FAILED_OVER)
        time.sleep(0.1)  # further ticks must not re-fire the hook
        assert fired == [u]
        assert obs_metrics.supervisor_failovers.value(unit="leader") >= 1


def test_probed_unit_restart_fn_recovers():
    state = {"up": True}

    def restart():
        state["up"] = True

    with Supervisor(poll_interval_s=0.01) as sup:
        u = sup.add_probed("svc", lambda: state["up"], restart=restart,
                           probe_failures=2)
        assert _wait_for(lambda: u.state == RUNNING)
        state["up"] = False
        assert _wait_for(lambda: u.restarts >= 1 and state["up"])
        assert _wait_for(lambda: u.state == RUNNING)


def test_supervise_toggles_never_leak_into_config_tree():
    """IOTML_SUPERVISE* are process toggles in config's non_config set:
    the resolver must neither reject them (typo'd IOTML_ vars fail
    loudly by design) nor apply them anywhere in the config tree."""
    from iotml.config import load_config

    cfg, _ = load_config(argv=[], env={
        "IOTML_SUPERVISE": "1", "IOTML_SUPERVISE_POLL_S": "0.2",
        "IOTML_SUPERVISE_MAX_RESTARTS": "9"})
    clean, _ = load_config(argv=[], env={})
    assert cfg.as_dict() == clean.as_dict()
    assert cfg.applied == set()


def test_supervise_env_knobs_are_read(monkeypatch):
    monkeypatch.setenv("IOTML_SUPERVISE_MAX_RESTARTS", "2")
    monkeypatch.setenv("IOTML_SUPERVISE_POLL_S", "0.123")
    from iotml.supervise.supervisor import SupervisedUnit

    u = SupervisedUnit("env-unit", lambda unit: None)
    assert u.max_restarts == 2
    assert Supervisor().poll_interval_s == 0.123


# ------------------------------------------------------------- topology
def test_topology_publish_monotonic_and_resolve_order():
    topo = Topology("a:1", epoch=0, fallback=["b:2"])
    assert topo.resolve() == (["a:1", "b:2"], 0)
    topo.publish("b:2", 1)
    servers, epoch = topo.resolve()
    assert servers[0] == "b:2" and "a:1" in servers and epoch == 1
    with pytest.raises(ValueError):
        topo.publish("a:1", 0)  # epochs only move forward


# -------------------------------------------------------- epoch fencing
def _wire_pair(epoch=0):
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer

    broker = Broker()
    broker.create_topic("T", partitions=1)
    srv = KafkaWireServer(broker, epoch=epoch).start()
    return broker, srv


def test_stale_client_is_fenced_on_produce_and_commit():
    from iotml.stream.kafka_wire import FencedEpochError, KafkaWireBroker

    broker, srv = _wire_pair(epoch=2)
    try:
        stale = KafkaWireBroker(f"127.0.0.1:{srv.port}", epoch=1)
        with pytest.raises(FencedEpochError):
            stale.produce("T", b"x")
        with pytest.raises(FencedEpochError):
            stale.commit("g", "T", 0, 5)
        assert broker.end_offset("T", 0) == 0      # nothing appended
        assert broker.committed("g", "T", 0) is None
        # reads stay open to any epoch (consumers drain across terms)
        assert stale.end_offset("T", 0) == 0
        # legacy unstamped clients pass unfenced (standard Kafka client
        # compatibility: the tag is absent, not wrong)
        legacy = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        legacy.produce("T", b"y")
        assert broker.end_offset("T", 0) == 1
        legacy.close()
        stale.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_resurrected_old_leader_is_fenced():
    """The other direction: the SERVER is the stale party (epoch 0 after
    a crash-restart), the client carries the post-promotion epoch."""
    from iotml.stream.kafka_wire import FencedEpochError, KafkaWireBroker

    broker, srv = _wire_pair(epoch=0)
    try:
        current = KafkaWireBroker(f"127.0.0.1:{srv.port}", epoch=1)
        with pytest.raises(FencedEpochError):
            current.produce("T", b"split-brain")
        assert broker.end_offset("T", 0) == 0
        current.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_client_reresolves_topology_after_fence():
    from iotml.stream.kafka_wire import FencedEpochError, KafkaWireBroker

    broker_a, srv_a = _wire_pair(epoch=0)
    broker_b, srv_b = _wire_pair(epoch=1)
    topo = Topology(f"127.0.0.1:{srv_a.port}", epoch=0,
                    fallback=[f"127.0.0.1:{srv_b.port}"])
    try:
        client = KafkaWireBroker(f"127.0.0.1:{srv_a.port}", topology=topo)
        client.produce("T", b"term0")
        assert broker_a.end_offset("T", 0) == 1
        # promotion happens elsewhere: topology now names B at epoch 1
        topo.publish(f"127.0.0.1:{srv_b.port}", 1)
        srv_a.set_epoch(2)  # A is now stale relative to this client
        with pytest.raises(FencedEpochError):
            client.produce("T", b"stale")
        # the fence re-resolved: the SAME client now writes to B at
        # epoch 1 without being rebuilt
        client.produce("T", b"term1")
        assert broker_b.end_offset("T", 0) == 1
        assert client.epoch == 1
        client.close()
    finally:
        for s in (srv_a, srv_b):
            s.shutdown()
            s.server_close()


# ------------------------------------------------- replica promote/pause
def test_follower_fenced_until_promoted_then_serves():
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import (FencedEpochError, KafkaWireBroker,
                                         KafkaWireServer)
    from iotml.stream.replica import FollowerReplica

    leader = Broker()
    leader.create_topic("T")
    for i in range(5):
        leader.produce("T", f"m{i}".encode())
    lsrv = KafkaWireServer(leader).start()
    rep = FollowerReplica(f"127.0.0.1:{lsrv.port}", topics=["T"])
    rep.server.start()
    try:
        while rep.sync_once() > 0:
            pass
        stamped = KafkaWireBroker(f"127.0.0.1:{rep.port}", epoch=0)
        with pytest.raises(FencedEpochError):
            # pre-promotion the follower is NOT a leader: an
            # epoch-stamped produce must not fork the replicated log
            stamped.produce("T", b"fork")
        addr = rep.promote(3)
        assert rep.promoted and addr.endswith(f":{rep.port}")
        assert obs_metrics.failover_epoch.value() == 3
        promoted_client = KafkaWireBroker(f"127.0.0.1:{rep.port}", epoch=3)
        off = promoted_client.produce("T", b"post-failover")
        assert off == 5  # appended right after the mirrored log
        with pytest.raises(RuntimeError):
            rep.promote(4)  # promotion is once
        promoted_client.close()
        stamped.close()
    finally:
        rep.server.shutdown()
        rep.server.server_close()
        lsrv.shutdown()
        lsrv.server_close()


def test_pause_resume_is_a_real_barrier():
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer
    from iotml.stream.replica import FollowerReplica

    leader = Broker()
    leader.create_topic("T")
    leader.produce("T", b"a")
    lsrv = KafkaWireServer(leader).start()
    rep = FollowerReplica(f"127.0.0.1:{lsrv.port}", topics=["T"],
                          poll_interval_s=0.005).start()
    try:
        assert rep.caught_up(timeout_s=10)
        assert rep.pause()
        rounds = rep.rounds
        leader.produce("T", b"b")
        time.sleep(0.1)
        # parked: the background loop ran no round, so the new record
        # is NOT mirrored until someone syncs explicitly
        assert rep.rounds == rounds
        assert rep.local.end_offset("T", 0) == 1
        rep.sync_once()
        assert rep.local.end_offset("T", 0) == 2
        rep.resume()
        leader.produce("T", b"c")
        assert _wait_for(lambda: rep.local.end_offset("T", 0) == 3)
    finally:
        rep.stop()
        lsrv.shutdown()
        lsrv.server_close()


def test_replica_lag_gauge_is_live():
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer
    from iotml.stream.replica import FollowerReplica

    leader = Broker()
    leader.create_topic("lagT")
    for i in range(7):
        leader.produce("lagT", b"x")
    lsrv = KafkaWireServer(leader).start()
    rep = FollowerReplica(f"127.0.0.1:{lsrv.port}", topics=["lagT"],
                          poll_interval_s=0.005, commit_interval_s=0.01)
    try:
        rep.sync_once()
        assert rep.lag() == {"lagT": 0}
        assert obs_metrics.replica_lag.value(topic="lagT") == 0
        leader.produce("lagT", b"y")
        assert rep.lag() == {"lagT": 1}
        assert obs_metrics.replica_lag.value(topic="lagT") == 1
        # the background loop probes the gauge on its own cadence
        rep.start()
        assert _wait_for(
            lambda: obs_metrics.replica_lag.value(topic="lagT") == 0)
    finally:
        rep.stop()
        lsrv.shutdown()
        lsrv.server_close()


# --------------------------------------------------------------- healthz
def test_healthz_reports_supervisor_and_failover_state():
    def loop(unit):
        while not unit.should_stop():
            unit.heartbeat()
            time.sleep(0.005)

    srv = obs_metrics.start_http_server(port=0)
    sup = Supervisor(poll_interval_s=0.01).start()
    try:
        sup.add_loop("healthz-probe-unit", loop)
        obs_metrics.failover_epoch.set(2)
        obs_metrics.replica_lag.set(4, topic="T")
        _wait_for(lambda: sup.unit("healthz-probe-unit").alive())
        port = srv.server_address[1]
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read())
        assert "healthz-probe-unit" in doc["supervisor"]
        assert doc["supervisor"]["healthz-probe-unit"]["state"] == RUNNING
        assert doc["failover_epoch"] == 2
        assert doc["replica_lag_records"]["T"] == 4
        # the metrics endpoint exports the same families
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "iotml_supervisor_unit_up" in body
        assert "iotml_failover_epoch 2" in body
    finally:
        sup.stop()
        srv.shutdown()
        srv.server_close()
        obs_metrics.failover_epoch.set(0)


# ------------------------------------------------------------------- DLQ
def test_json_to_avro_dead_letters_poisoned_records():
    from iotml.stream.broker import Broker
    from iotml.streamproc.dlq import decode_envelope, dlq_topic
    from iotml.streamproc.tasks import JsonToAvro

    broker = Broker()
    broker.create_topic("sensor-data")
    task = JsonToAvro(broker, src="sensor-data", dst="J2A_OUT")
    good = {"coolant_temp": 1.0, "intake_air_temp": 2.0}
    before = obs_metrics.dlq_total.value(source="sensor-data")
    broker.produce("sensor-data", json.dumps(good).encode(), key=b"car1")
    broker.produce("sensor-data", b"{not json", key=b"car2")
    broker.produce("sensor-data", b'["array", "not", "object"]')
    broker.produce("sensor-data",
                   json.dumps({"coolant_temp": "NaN-ish-text"}).encode())
    n = task.process_available()
    assert n == 1  # the good record flowed; poison did not halt it
    dlq = dlq_topic("sensor-data")
    assert dlq in broker.topics()
    letters = [decode_envelope(m.value)
               for m in broker.fetch(dlq, 0, 0, 100)]
    assert len(letters) == 3
    assert {d["task"] for d in letters} == {"JsonToAvro"}
    by_raw = {d["raw"] for d in letters}
    assert b"{not json" in by_raw
    assert all(d["source"] == "sensor-data" for d in letters)
    assert all("error" in d and d["error"] for d in letters)
    assert obs_metrics.dlq_total.value(source="sensor-data") == before + 3


def test_delimited_to_avro_dead_letters_but_skips_header():
    from iotml.core.schema import CAR_SCHEMA
    from iotml.stream.broker import Broker
    from iotml.streamproc.dlq import dlq_topic
    from iotml.streamproc.tasks import DelimitedToAvro

    broker = Broker()
    broker.create_topic("car-data-csv")
    task = DelimitedToAvro(broker, src="car-data-csv", dst="CSV_OUT")
    n_cols = 2 + len(CAR_SCHEMA.fields)
    header = ",".join(["time", "car"] + ["c"] * (n_cols - 2))
    good = ",".join(["1", "car9"] + ["1.5"] * (n_cols - 2))
    broker.produce("car-data-csv", header.encode())   # expected: skipped
    broker.produce("car-data-csv", good.encode())
    broker.produce("car-data-csv", b"\xff\xfe\xff")   # bad utf-8
    broker.produce("car-data-csv", b"1,car1,too,short")
    broker.produce("car-data-csv",
                   ",".join(["1", "car2"] + ["xyz"] * (n_cols - 2)).encode())
    assert task.process_available() == 1
    letters = broker.fetch(dlq_topic("car-data-csv"), 0, 0, 100)
    assert len(letters) == 3  # header line is NOT poison


def test_sql_engine_select_task_dead_letters_undecodable_avro():
    from iotml.stream.broker import Broker
    from iotml.streamproc import SqlEngine
    from iotml.streamproc.dlq import decode_envelope, dlq_topic
    from iotml.streamproc.sql import install_reference_pipeline

    broker = Broker()
    broker.create_topic("sensor-data", partitions=1)
    engine = SqlEngine(broker)
    install_reference_pipeline(engine)
    good = {"coolant_temp": 3.3, "car": "car1"}
    broker.produce("sensor-data", json.dumps(good).encode(), key=b"car1")
    broker.produce("sensor-data", b"\x00garbage-not-json", key=b"car2")
    engine.pump()
    dlq = dlq_topic("sensor-data")
    assert dlq in broker.topics()
    letters = [decode_envelope(m.value)
               for m in broker.fetch(dlq, 0, 0, 100)]
    assert any(d["raw"] == b"\x00garbage-not-json" for d in letters)
    # the AVRO leg still produced the good record
    assert broker.end_offset("SENSOR_DATA_S_AVRO", 0) >= 1


def test_obs_dlq_cli_peeks_over_the_wire(capsys):
    from iotml.obs.__main__ import main as obs_main
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer
    from iotml.streamproc.tasks import JsonToAvro

    broker = Broker()
    broker.create_topic("sensor-data")
    task = JsonToAvro(broker, src="sensor-data", dst="J2A_OUT2")
    broker.produce("sensor-data", b"not json at all", key=b"carX")
    task.process_available()
    # non-envelope garbage on the open DLQ topic (valid JSON non-object
    # included) must render as a fallback row, never crash the CLI
    broker.produce("sensor-data_DLQ", b"[1]")
    broker.produce("sensor-data_DLQ", b"not even json")
    srv = KafkaWireServer(broker).start()
    try:
        rc = obs_main(["dlq", "--bootstrap", f"127.0.0.1:{srv.port}",
                       "--topic", "sensor-data"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "sensor-data_DLQ" in out and "JsonToAvro" in out
        assert "not json at all" in out
        # missing DLQ topic is a clean empty answer, not an error
        rc = obs_main(["dlq", "--bootstrap", f"127.0.0.1:{srv.port}",
                       "--topic", "never-poisoned"])
        assert rc == 0
        assert "does not exist" in capsys.readouterr().out
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------------------------ lint
def test_lint_r8_fixture_findings():
    import os

    from iotml.analysis.lint import lint_file

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "analysis", "bad_thread.py")
    findings = [f for f in lint_file(fixture) if f.rule == "R8"]
    # fire_and_forget (all three problems), named_but_unregistered
    # (wrapper only), aliased_evasion (t.Thread dodge — wrapper only)
    assert len(findings) == 3
    msgs = {f.line: f.message for f in sorted(findings,
                                              key=lambda f: f.line)}
    lines = sorted(msgs)
    assert "daemon=True" in msgs[lines[0]] and "name=" in msgs[lines[0]] \
        and "register_thread" in msgs[lines[0]]
    for ln in lines[1:]:
        assert "register_thread" in msgs[ln]
        assert "daemon" not in msgs[ln]


def test_lint_r8_clean_on_production_tree():
    from iotml.analysis.lint import default_root, lint_paths

    r8 = [f for f in lint_paths([default_root()], rules={"R8"})]
    assert r8 == [], "\n".join(str(f) for f in r8)


# ---------------------------------------------------------- live drills
def test_live_drill_scorer_crash_heals():
    from iotml.supervise.drill import drill_scorer_crash

    report = drill_scorer_crash(seed=11, records=300)
    assert report.ok, "\n".join(report.lines())
    assert report.restarts["scorer"] >= 1
    assert report.scored >= report.published


def test_live_drill_leader_kill_promotes_and_fences():
    from iotml.supervise.drill import drill_leader_kill

    report = drill_leader_kill(seed=5, records=400)
    assert report.ok, "\n".join(report.lines())
    by_name = {i.name: i for i in report.invariants}
    assert by_name["old_leader_fenced"].ok
    assert by_name["promotion_loss_bounded"].ok
    assert report.slos["time_to_promote_s"] is not None
    assert report.slos["time_to_promote_s"] <= 10.0


def test_drill_cli_list_and_unknown(capsys):
    from iotml.supervise.__main__ import main as sup_main

    assert sup_main(["list"]) == 0
    out = capsys.readouterr().out
    for name in ("leader-kill", "mqtt-flap", "scorer-crash"):
        assert name in out
    assert sup_main(["drill", "--drill", "no-such-drill"]) == 2


# --------------------------------------------------- platform supervision
def test_platform_supervised_restarts_dead_pump():
    from iotml.cli.up import Platform

    plat = Platform(partitions=2)
    plat.start()
    sup = plat.supervised(poll_interval_s=0.02).start()
    try:
        names = {u.name for u in sup.units()}
        assert {"kafka-wire", "mqtt-front", "ksql-tasks",
                "connect-driver"} <= names
        assert _wait_for(
            lambda: sup.unit("ksql-tasks").state == RUNNING)
        # kill the continuous-query pump thread the way a bug would:
        # stop flag set, thread exits, nobody restarts it by hand
        plat.ksql._stop.set()
        assert _wait_for(lambda: sup.unit("ksql-tasks").restarts >= 1)
        plat.ksql._stop.clear()
        assert _wait_for(
            lambda: plat.ksql._pump_thread.is_alive()
            and sup.unit("ksql-tasks").state == RUNNING)
    finally:
        sup.stop()
        plat.stop()
