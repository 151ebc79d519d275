"""Multi-chip streaming training (ISSUE 15): partition-parallel feeds,
sharded step, device-side normalization, rebalance coverage, and the
atomic multi-device checkpoint manifest — on the suite's 8-virtual-
device CPU mesh (conftest)."""

import numpy as np
import pytest

import jax

from iotml.core.normalize import CAR_NORMALIZER, RAW_COLUMNS
from iotml.gen.simulator import FleetGenerator, FleetScenario
from iotml.parallel.distributed import assign_partitions
from iotml.parallel.mesh import make_mesh
from iotml.parallel.streaming import (MeshFeeds, ShardedStreamTrainer,
                                      data_axis_devices, shard_mean_losses)
from iotml.store.log import StorePolicy
from iotml.stream.broker import Broker


def _fill(broker, topic="S", n_ticks=100, partitions=8, num_cars=50,
          failure_rate=0.01):
    gen = FleetGenerator(FleetScenario(num_cars=num_cars,
                                       failure_rate=failure_rate))
    return gen.publish(broker, topic, n_ticks=n_ticks,
                       partitions=partitions)


def _mesh(n):
    return make_mesh((n,), ("data",), devices=jax.devices()[:n])


# ------------------------------------------------- partition assignment
def test_assign_partitions_disjoint_exhaustive():
    """The static device→partition split is a partition of the
    partition set for every (P, D)."""
    for n_parts in (1, 3, 8, 10, 16):
        for n_dev in (1, 2, 4, 8):
            subsets = [assign_partitions(n_parts, n_dev, d)
                       for d in range(n_dev)]
            flat = [p for s in subsets for p in s]
            assert sorted(flat) == list(range(n_parts))  # exhaustive
            assert len(flat) == len(set(flat))           # disjoint


def test_mesh_feeds_static_ownership_and_coverage():
    """4 feeds over 8 partitions: disjoint+exhaustive ownership, and a
    full drain consumes every filtered record exactly once."""
    broker = Broker()
    n = _fill(broker, n_ticks=40)
    feeds = MeshFeeds(broker, "S", 4, group="own", only_normal=False,
                      batch_size=50)
    owned = [set(p) for p in feeds.partitions]
    assert set().union(*owned) == set(range(8))
    assert sum(len(p) for p in owned) == 8
    total = 0
    for row in feeds.rounds():
        total += sum(b.n_valid for b in row if b is not None)
    assert total == n


def test_feed_rebalance_member_death_stays_disjoint_exhaustive():
    """The consumer-group mode under a mid-epoch member death — the
    cluster fleet's kill(i) semantics (stop driving WITHOUT leaving the
    group): after the session timeout expires the member, survivors'
    partition subsets must still be disjoint AND exhaustive, and the
    dead feed's partitions must keep flowing."""
    from iotml.stream.group import GroupCoordinator

    clock = [0.0]
    broker = Broker()
    n = _fill(broker, n_ticks=40)
    coord = GroupCoordinator(broker, "mesh-elastic",
                             session_timeout_s=5.0,
                             clock=lambda: clock[0])
    feeds = MeshFeeds(broker, "S", 4, group="mesh-elastic",
                      coordinator=coord, only_normal=False,
                      batch_size=50)
    assigned = feeds.assignments()
    flat = [tp for a in assigned for tp in a]
    assert len(flat) == 8 and len(set(flat)) == 8
    # mid-epoch: every member consumes a little, then member 2 dies
    seen = set()
    for c in feeds.consumers:
        for m in c.poll(60):
            seen.add((m.topic, getattr(m, "partition", 0), m.offset))
    dead = 2
    dead_parts = set(tp for tp in feeds.consumers[dead].assignment)
    # kill(i): the member is never driven again, never leaves cleanly.
    # Survivors keep heartbeating while the wall clock passes the dead
    # member's session timeout (sub-timeout steps: only the corpse
    # expires), then converge on the post-expiry generation.
    survivors = [c for i, c in enumerate(feeds.consumers) if i != dead]
    for _ in range(14):
        clock[0] += 0.5
        for c in survivors:
            c.poll(1)
    for c in survivors:
        c.poll(1)  # adopt the converged post-expiry assignment
    live = [sorted(c.assignment) for c in survivors]
    flat = [tp for a in live for tp in a]
    assert sorted(flat) == sorted((("S", p)) for p in range(8)), live
    assert len(flat) == len(set(flat))  # disjoint across survivors
    # the dead member's partitions moved, not vanished
    inherited = set(flat) & dead_parts
    assert inherited == dead_parts
    # and records keep flowing from them
    drained = 0
    for _ in range(200):
        got = sum(len(c.poll(256)) for c in survivors)
        drained += got
        if not got:
            break
    assert drained > 0


# ---------------------------------------------- prefetcher placement
def test_prefetcher_whole_batch_follows_sharding():
    """The satellite fix pinned: x, y AND mask (the per-row weights)
    all land with the given sharding — none stays on the default
    device."""
    from iotml.data.dataset import Batch
    from iotml.data.prefetch import DevicePrefetcher

    mesh = _mesh(4)
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data"))
    bs = [Batch(x=np.zeros((8, 18), np.float32), n_valid=6,
                first_index=0, y=np.ones((8, 18), np.float32))
          for _ in range(2)]
    for (x, y, mask), b in DevicePrefetcher(iter(bs), sharding=sharding):
        for arr in (x, y, mask):
            assert arr.sharding.is_equivalent_to(sharding, arr.ndim), \
                arr.sharding
        assert float(np.asarray(mask).sum()) == b.n_valid
    # without a sharding everything lands on the default device,
    # mask included
    for (x, y, mask), _b in DevicePrefetcher(iter([bs[0]])):
        assert x.devices() == mask.devices() == y.devices()


def test_global_put_lands_shards_on_their_devices():
    """Feed d's rows must live ONLY on data-axis device d."""
    from iotml.models.autoencoder import CAR_AUTOENCODER

    broker = Broker()
    _fill(broker, n_ticks=20)
    mesh = _mesh(4)
    feeds = MeshFeeds(broker, "S", 4, group="placement",
                      batch_size=10, only_normal=False)
    tr = ShardedStreamTrainer(CAR_AUTOENCODER, mesh, feeds)
    shards = [np.full((10, 18), float(d), np.float32) for d in range(4)]
    arr = tr._global_put(shards)
    assert arr.shape == (40, 18)
    devs = data_axis_devices(mesh)
    by_dev = {s.device: s for s in arr.addressable_shards}
    for d, dev in enumerate(devs):
        piece = np.asarray(by_dev[dev].data)
        assert np.all(piece == float(d))
        assert by_dev[dev].index[0] == slice(d * 10, (d + 1) * 10)


# ------------------------------------------- device-side normalization
def test_device_normalize_bit_comparable_losses():
    """The acceptance pin: device-side normalization (raw columns +
    affine fold in the jitted step, float32) against the host-
    normalized baseline (float64 math rounded once to float32) — the
    normalized inputs agree to ~1 ulp and the training losses are
    bit-comparable at every step."""
    from iotml.models.autoencoder import CAR_AUTOENCODER

    broker = Broker()
    _fill(broker, n_ticks=60, failure_rate=0.0)
    mesh = _mesh(4)

    def run(device_normalize, group):
        feeds = MeshFeeds(broker, "S", 4, group=group, batch_size=50,
                          take_batches=3, only_normal=True,
                          device_normalize=device_normalize)
        tr = ShardedStreamTrainer(
            CAR_AUTOENCODER, mesh, feeds,
            normalizer=CAR_NORMALIZER if device_normalize else None)
        losses = []
        for _ in range(4):  # 4 rounds x 3 batches/feed
            h = tr.fit_round()
            losses.extend(h["step_loss"])
        return losses

    host = run(False, "norm-host")
    dev = run(True, "norm-dev")
    assert len(host) == len(dev) and len(host) >= 8
    diffs = np.abs(np.asarray(host) - np.asarray(dev))
    # first step: pure normalization rounding (params identical)
    assert diffs[0] <= 5e-6, (host[0], dev[0])
    # whole run: divergence stays at float32-rounding scale
    assert diffs.max() <= 5e-4, diffs
    # and the map itself agrees to ~1 ulp on raw decoded columns
    raw = np.random.default_rng(0).uniform(-40, 260,
                                           (64, 18)).astype(np.float32)
    host_norm = CAR_NORMALIZER.np(raw)
    dev_norm = np.asarray(
        (raw * CAR_NORMALIZER.scale + CAR_NORMALIZER.shift)
        * CAR_NORMALIZER.mask, np.float32)
    assert np.abs(host_norm - dev_norm).max() <= 4e-5


def test_raw_columns_normalizer_is_passthrough():
    x = np.random.default_rng(1).normal(size=(5, 18)).astype(np.float32)
    out = RAW_COLUMNS.np(x)
    assert out is x  # cast-only view: zero host work
    assert np.array_equal(np.asarray(RAW_COLUMNS(x)), x)


# ----------------------------------------------------- sharded training
@pytest.fixture(params=[False, True], ids=["in-memory", "durable"])
def stream_broker(request, tmp_path):
    """The in-memory broker, and one with segmented partitions on disk
    (the feeds' columnar leg where the native engine is built)."""
    broker = Broker(store_dir=str(tmp_path),
                    store_policy=StorePolicy(fsync="never")) \
        if request.param else Broker()
    yield broker
    broker.close()


def test_sharded_stream_trainer_trains_and_tracks(stream_broker, request):
    from iotml.models.autoencoder import CAR_AUTOENCODER

    broker = stream_broker
    n = _fill(broker, n_ticks=120, failure_rate=0.0)
    mesh = _mesh(4)
    feeds = MeshFeeds(broker, "S", 4, group="train", batch_size=50,
                      only_normal=True, device_normalize=True)
    request.addfinalizer(feeds.close)
    tr = ShardedStreamTrainer(CAR_AUTOENCODER, mesh, feeds,
                              normalizer=CAR_NORMALIZER)
    h = tr.fit_round()
    assert h["records"][0] == n
    assert h["step_loss"][-1] < h["step_loss"][0]
    # positions advanced over every partition, per-chip losses published
    assert feeds.positions() and all(off > 0
                                     for _t, _p, off in feeds.positions())
    assert tr.last_shard_losses is not None
    assert len(tr.last_shard_losses) == 4
    assert np.all(np.isfinite(tr.last_shard_losses))


def test_feeds_device_normalize_requires_step_normalizer():
    from iotml.models.autoencoder import CAR_AUTOENCODER

    broker = Broker()
    _fill(broker, n_ticks=5)
    feeds = MeshFeeds(broker, "S", 2, group="guard",
                      device_normalize=True)
    with pytest.raises(ValueError, match="raw columns"):
        ShardedStreamTrainer(CAR_AUTOENCODER, _mesh(2), feeds)


def test_streaming_mesh_refuses_model_axis():
    mesh = make_mesh((4, 2), ("data", "model"),
                     devices=jax.devices()[:8])
    with pytest.raises(ValueError, match="data-parallel"):
        data_axis_devices(mesh)


# -------------------------------------- continuous trainer integration
def test_continuous_trainer_mesh_manifest_is_atomic(tmp_path):
    """One checkpoint manifest stamps EVERY device's partition cursors
    (the PR 7 checkpointer gathering the sharded state host-side), and
    a second incarnation resumes from it."""
    from iotml.mlops import ModelRegistry
    from iotml.train.live import ContinuousTrainer

    broker = Broker()
    _fill(broker, n_ticks=200)
    mesh = _mesh(4)
    reg = ModelRegistry(str(tmp_path / "reg"))
    tr = ContinuousTrainer(broker, "S", None, registry=reg, mesh=mesh,
                           device_normalize=True, take_batches=4,
                           batch_size=50)
    assert tr.train_round()["records"] > 0
    v = tr.checkpointer.write_once()
    m = reg.manifest(v)
    stamped = {(t, p) for t, p, _ in m.offsets}
    assert stamped == {("S", p) for p in range(8)}  # ALL devices' parts
    # committed trails (never leads) the manifest
    for t, p, off in m.offsets:
        assert (broker.committed(tr.group, t, p) or 0) <= off
    tr.close()

    tr2 = ContinuousTrainer(broker, "S", None, registry=reg, mesh=mesh,
                            device_normalize=True, take_batches=4,
                            batch_size=50)
    assert tr2.restored_version == v
    pos = dict(((t, p), o) for t, p, o in tr2.consumer.positions())
    for t, p, off in m.offsets:
        assert pos[(t, p)] >= off  # forward-only resume
    tr2.close()


def test_continuous_trainer_mesh_rejects_multi_epoch_rounds():
    from iotml.train.live import ContinuousTrainer

    broker = Broker()
    _fill(broker, n_ticks=5)
    with pytest.raises(ValueError, match="single-epoch"):
        ContinuousTrainer(broker, "S", None, registry=object(),
                          mesh=_mesh(2), epochs_per_round=2)
    # same contract as OnlineLearner: no silent host-normalize fallback
    with pytest.raises(ValueError, match="needs a mesh"):
        ContinuousTrainer(broker, "S", None, registry=object(),
                          device_normalize=True)


# ------------------------------------------------ online per-chip drift
def test_online_mesh_per_chip_drift_coordinates_one_episode():
    """A chip-LOCAL drift (one shard's rows off-distribution) trips
    that chip's detector while the dulled global monitor stays quiet;
    the learner opens exactly ONE coordinated episode (tagged with the
    chip), boosts, and stages a forced registry publish."""
    from iotml.data.dataset import Batch
    from iotml.online.detectors import ADAPTING, DriftMonitor
    from iotml.online.learner import OnlineLearner

    broker = Broker()
    _fill(broker, n_ticks=10)
    mesh = _mesh(4)
    # global monitor deliberately blind (huge threshold, level rule off)
    blind = DriftMonitor(detector="ph", ph_threshold=1e9, level_ratio=0)
    lr = OnlineLearner(broker, "S", mesh=mesh, device_normalize=True,
                       window=100, monitor=blind,
                       chip_monitors=[DriftMonitor(burn_in=4)
                                      for _ in range(4)])
    rng = np.random.default_rng(0)

    def window(chip_spike=None):
        x = rng.normal(0, 0.1, (100, 18)).astype(np.float32)
        if chip_spike is not None:
            x[chip_spike * 25:(chip_spike + 1) * 25] += 60.0
        return Batch(x=x, n_valid=100, first_index=0)

    for _ in range(16):  # establish per-chip baselines
        loss = lr._update(window())
        lr._after_update(loss)
    assert lr.adaptations == []
    for _ in range(8):  # chip-3-local drift
        loss = lr._update(window(chip_spike=3))
        lr._after_update(loss)
    assert len(lr.adaptations) == 1, lr.adaptations
    _idx, signal, _action = lr.adaptations[0]
    assert signal.startswith("chip3-"), signal
    assert lr.monitor.state == ADAPTING  # ONE coordinated episode
    assert lr.current_lr > lr.base_lr   # boost applied
    assert lr._publish_pending and lr._publish_force  # registry push


def test_online_mesh_trains_from_stream():
    from iotml.online.learner import OnlineLearner

    broker = Broker()
    n = _fill(broker, n_ticks=60, failure_rate=0.0)
    lr = OnlineLearner(broker, "S", mesh=_mesh(4),
                       device_normalize=True, window=100)
    got = lr.process_available()
    assert got > 0 and lr.records_trained == n
    assert lr.last_chip_losses is not None
    assert len(lr.last_chip_losses) == 4
    d = lr.describe()
    assert len(d["chips"]) == 4


def test_cardata_cli_honors_mesh_knob_env(tmp_path, monkeypatch, capsys):
    """The deploy manifests' contract (deploy/model-training*.yaml:
    env IOTML_MESH_DATA=N ⇒ the Job trains over an N-data-axis mesh)
    must survive the knob's move into non_config: cli/_app reads the
    process knob and still builds the mesh."""
    from iotml.cli import cardata

    monkeypatch.setenv("IOTML_MESH_DATA", "2")
    rc = cardata.main(["--train.epochs=1", "--train.take_batches=2",
                       "--train.batch_size=50", "emulator:500",
                       "SENSOR_DATA_S_AVRO", "0", "model-predictions",
                       "train", "mesh-knob-model",
                       str(tmp_path / "arts")])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "mesh: {'data': 2, 'model': 1}" in out, out


# --------------------------------------------------------------- knobs
def test_mesh_knobs_never_leak_into_config_tree():
    """IOTML_MESH_DATA / IOTML_DEVICE_NORMALIZE are process toggles in
    config's non_config set: neither rejected nor applied."""
    from iotml.config import load_config

    cfg, _ = load_config(argv=[], env={"IOTML_MESH_DATA": "4",
                                       "IOTML_DEVICE_NORMALIZE": "1"})
    clean, _ = load_config(argv=[], env={})
    assert cfg.as_dict() == clean.as_dict()
    assert cfg.applied == set()


def test_mesh_knob_validation(monkeypatch):
    from iotml.data import pipeline as pl

    monkeypatch.setenv("IOTML_MESH_DATA", "4")
    monkeypatch.setenv("IOTML_DEVICE_NORMALIZE", "1")
    assert pl.mesh_data() == 4
    assert pl.device_normalize() is True
    monkeypatch.setenv("IOTML_MESH_DATA", "-1")
    with pytest.raises(ValueError):
        pl.mesh_data()
    monkeypatch.setenv("IOTML_DEVICE_NORMALIZE", "maybe")
    with pytest.raises(ValueError):
        pl.device_normalize()
    monkeypatch.delenv("IOTML_MESH_DATA")
    monkeypatch.delenv("IOTML_DEVICE_NORMALIZE")
    assert pl.mesh_data() == 0
    assert pl.device_normalize() is False
    # the CLI bridge validates BEFORE publishing
    with pytest.raises(ValueError):
        pl.set_knobs(mesh_data=-2)
    assert "IOTML_MESH_DATA" not in __import__("os").environ
    pl.set_knobs(mesh_data=2, device_normalize=True)
    try:
        assert pl.mesh_data() == 2 and pl.device_normalize() is True
    finally:
        __import__("os").environ.pop("IOTML_MESH_DATA", None)
        __import__("os").environ.pop("IOTML_DEVICE_NORMALIZE", None)


def test_shard_mean_losses_maps_chips_in_feed_order():
    mesh = _mesh(4)
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data"))
    row = np.repeat(np.asarray([1.0, 2.0, 3.0, 4.0], np.float32), 8)
    arr = jax.device_put(row, sharding)
    out = shard_mean_losses(arr, [8, 8, 8, 8])
    assert np.allclose(out, [1.0, 2.0, 3.0, 4.0])
    # padding-aware: valid counts divide the masked sums
    out2 = shard_mean_losses(arr, [4, 8, 8, 8])
    assert np.isclose(out2[0], 2.0)
