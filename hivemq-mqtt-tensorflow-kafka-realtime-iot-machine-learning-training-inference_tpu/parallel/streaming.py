"""Partition-parallel columnar feeds into a sharded train step (ROADMAP 3).

The reference's scaling story is "Kafka partitions × consumer group →
chips" (PARITY §2.7); until now the repo only ever dry-ran it.  This
module spends the consume-side headroom for real: the columnar plane
decodes at ~8× one chip's train rate, so a data-parallel mesh is exactly
what consumes it.

Dataflow (ARCHITECTURE §24):

- `MeshFeeds` gives each local device its OWN host-side pipeline: a
  partition subset (static `assign_partitions` split, or an elastic
  consumer-group membership per device), one `SensorBatches` whose
  `poll_into` fills that feed's private `DecodeRing`, and a
  `DevicePrefetcher` staging thread so decode hides under the device
  step.  Feeds share ONE consumer group: committed offsets stay
  partition-keyed, so a checkpoint manifest stamping every feed's
  cursors is one atomic resume unit.
- `ShardedStreamTrainer` pairs feed *d* with data-axis device *d*:
  each step `jax.device_put`s every feed's rows directly onto its
  device and assembles the global batch with
  `jax.make_array_from_single_device_arrays` — no host concatenation,
  no resharding copy — then runs the jitted step whose gradient
  all-reduce XLA compiles over the mesh (ICI on real slices).
- Normalization rides the step, not the host: with
  ``device_normalize=True`` the feeds ship RAW float32 columns
  (`core.normalize.RAW_COLUMNS`) and the affine map folds into the
  jitted program (`data_parallel.make_device_normalized_step`) — the
  last per-element host work disappears from the hot loop.

The per-row pre-update loss stays sharded over 'data' in the step's
metrics (zero collective cost), which is what `iotml.online`'s per-chip
drift detectors read.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from ..data.dataset import SensorBatches
from ..stream.consumer import StreamConsumer
from ..stream.group import GroupConsumer, GroupCoordinator
from ..train.loop import TrainState, adam_cached
from .data_parallel import ShardedTrainer
from .distributed import assign_partitions


def data_axis_devices(mesh) -> list:
    """The mesh's devices in 'data'-axis order — feed *d* owns device
    *d*.  The streaming trainer is pure data parallelism by design
    (SURVEY §2.7: partitions → chips); a >1 model/seq/pipe axis would
    need every row block replicated across that axis, which defeats the
    shard-lands-on-its-device contract, so it is refused here."""
    if mesh.axis_names[0] != "data":
        raise ValueError(f"streaming mesh must lead with the 'data' axis, "
                         f"got {mesh.axis_names}")
    for name in mesh.axis_names[1:]:
        if mesh.shape[name] != 1:
            raise ValueError(
                f"streaming trainer is pure data-parallel; axis "
                f"{name!r} has size {mesh.shape[name]} (use a "
                f"('data',) or ('data', 'model'=1) mesh)")
    return list(mesh.devices.reshape(mesh.shape["data"], -1)[:, 0])


class MeshFeeds:
    """Per-device partition-parallel host pipelines over ONE group.

    Args:
      broker: Broker duck-type (in-process, wire client, ClusterClient).
      topic: the consumed stream.
      n_feeds: local data-axis size — one feed (consumer + batcher +
        decode ring) per device.
      group: the shared consumer group; commits are partition-keyed so
        all feeds' offsets live in one resume namespace.
      coordinator: None (default) = static deterministic split via
        `assign_partitions` (offset checkpoints stay device-stable
        across restarts — the multihost contract).  A
        `GroupCoordinator` (shared, in-process) or a zero-arg factory
        returning one (wire `RemoteGroupCoordinator` per member) makes
        each feed a group MEMBER instead: partition subsets stay
        disjoint and exhaustive under rebalance, and a dead feed's
        partitions move to survivors after the session timeout.
      batch_size/take_batches/only_normal/poll_chunk: per-feed
        `SensorBatches` knobs; `take_batches` bounds EACH feed's round.
      normalizer: host-side normalizer (ignored under device_normalize).
      device_normalize: ship raw float32 columns — the affine map runs
        on-device inside the jitted step (pass the real normalizer to
        `ShardedStreamTrainer(normalizer=...)`).
    """

    def __init__(self, broker, topic: str, n_feeds: int,
                 group: str = "cardata-mesh-train",
                 coordinator: Union[None, GroupCoordinator, Callable] = None,
                 batch_size: int = 100, take_batches: Optional[int] = None,
                 only_normal: bool = True, normalizer=None,
                 device_normalize: bool = False, poll_chunk: int = 8192):
        from ..core.normalize import RAW_COLUMNS

        if n_feeds < 1:
            raise ValueError(f"n_feeds must be >= 1, got {n_feeds}")
        self.broker = broker
        self.topic = topic
        self.group = group
        self.batch_size = batch_size
        self.device_normalize = device_normalize
        n_parts = broker.topic(topic).partitions
        self.consumers: List = []
        self.partitions: List[List[int]] = []
        for d in range(n_feeds):
            if coordinator is None:
                parts = assign_partitions(n_parts, n_feeds, d)
                consumer = StreamConsumer.from_committed(
                    broker, topic, parts, group=group)
            else:
                coord = coordinator() if callable(coordinator) \
                    else coordinator
                consumer = GroupConsumer(coord, [topic])
                parts = [p for _t, p in consumer.assignment]
            self.consumers.append(consumer)
            self.partitions.append(list(parts))
        if coordinator is not None:
            # members join sequentially and each join rebalances: one
            # heartbeat round lets every member adopt the CONVERGED
            # assignment before anyone consumes
            for consumer in self.consumers:
                consumer._ensure_membership()
            self.partitions = [[p for _t, p in c.assignment]
                               for c in self.consumers]
        batch_kw = {}
        if device_normalize:
            batch_kw["normalizer"] = RAW_COLUMNS
        elif normalizer is not None:
            batch_kw["normalizer"] = normalizer
        self.batchers = [
            SensorBatches(c, batch_size=batch_size, take=take_batches,
                          only_normal=only_normal, poll_chunk=poll_chunk,
                          **batch_kw)
            for c in self.consumers]

    def __len__(self) -> int:
        return len(self.consumers)

    def set_take(self, take_batches: Optional[int]) -> None:
        """Re-bound every feed's next round (None = drain to log end)."""
        for b in self.batchers:
            b.take = take_batches

    def rounds(self):
        """Yield per-step rows ``[Batch | None per feed]`` until every
        feed's bounded iteration ends.  Each feed decodes on its OWN
        staging thread (`DevicePrefetcher` with a host-side pass-
        through), so the D host pipelines overlap each other and the
        device step; all JAX dispatch stays on the consuming thread
        (the prefetcher's documented discipline)."""
        from ..data.prefetch import DevicePrefetcher

        pfs = [DevicePrefetcher(iter(b), to_device=lambda batch: batch,
                                loop="train")
               for b in self.batchers]
        its = [iter(pf) for pf in pfs]
        try:
            while True:
                row = [next(it, None) for it in its]
                if all(b is None for b in row):
                    return
                yield row
        finally:
            for pf in pfs:
                pf.close()

    # ------------------------------------------- consumer-facade surface
    def positions(self) -> List[tuple]:
        """Every feed's cursors, one flat list — what a checkpoint
        manifest stamps: ALL devices' partitions as one atomic unit."""
        out: List[tuple] = []
        for c in self.consumers:
            out.extend(tuple(p) for p in c.positions())
        return sorted(out)

    def available(self) -> int:
        return sum(self.broker.end_offset(t, p) - off
                   for t, p, off in self.positions())

    def commit(self) -> None:
        for c in self.consumers:
            c.commit()

    def seek(self, topic: str, partition: int, offset: int) -> None:
        """Route a cursor move to the feed that owns the partition —
        by LIVE ownership, not the construction-time snapshot (group
        mode reassigns under rebalance).  Group-elastic feeds have no
        absolute seek (the group's committed offset is the cursor, the
        GroupConsumer contract), so they refuse loudly instead of
        silently resuming elsewhere."""
        for c in self.consumers:
            owned = {p for _t, p in c.assignment} \
                if hasattr(c, "assignment") \
                else {p for _t, p, _ in c.positions()}
            if partition in owned:
                seek = getattr(c, "seek", None)
                if seek is None:
                    raise NotImplementedError(
                        "group-elastic feeds seek via committed offsets "
                        "(commit before rebuilding), not absolute seeks")
                seek(topic, partition, offset)
                return
        raise KeyError(f"partition {partition} not owned by any feed")

    def take_event_time(self) -> dict:
        """Merged event-time ranges across feeds (watermark publish)."""
        merged: dict = {}
        for c in self.consumers:
            take = getattr(c, "take_event_time", None)
            if take is None:
                continue
            for key, (lo, hi) in take().items():
                if key in merged:
                    mlo, mhi = merged[key]
                    merged[key] = (min(mlo, lo), max(mhi, hi))
                else:
                    merged[key] = (lo, hi)
        return merged

    def take_traces(self) -> list:
        out: list = []
        for b in self.batchers:
            out.extend(b.take_traces())
        return out

    def records_seen(self) -> int:
        return sum(b.records_seen for b in self.batchers)

    def assignments(self) -> List[List[tuple]]:
        """Per-feed (topic, partition) ownership right now — group mode
        reads the live assignment (it moves under rebalance)."""
        out = []
        for c in self.consumers:
            if hasattr(c, "assignment"):
                out.append(sorted(c.assignment))
            else:
                out.append(sorted({(t, p)
                                   for t, p, _ in c.positions()}))
        return out

    def close(self) -> None:
        for c in self.consumers:
            close = getattr(c, "close", None)
            if close is not None:
                try:
                    close()
                except (ConnectionError, RuntimeError, OSError):
                    pass


class ShardedStreamTrainer:
    """The streaming face of `ShardedTrainer`: per-device feeds in,
    mesh-sharded optimizer steps out.

    Exposes the `train.loop.Trainer` warm-start surface
    (``_ensure_state`` + ``state``) so `mlops.restore_trainer` and the
    `AsyncCheckpointer` treat it like any trainer: a restore lands in a
    HOST state that the mesh adopts (shards) on the first step, and
    ``state`` is always fully addressable to `jax.device_get` — the
    checkpoint snapshot gathers the sharded params host-side for one
    atomic manifest.
    """

    def __init__(self, model, mesh, feeds: MeshFeeds,
                 learning_rate: float = 1e-3, tx=None, rng=None,
                 normalizer=None, supervised: bool = False):
        if normalizer is None and feeds.device_normalize:
            raise ValueError(
                "feeds ship raw columns (device_normalize=True) but no "
                "device-side normalizer was given — the step would train "
                "on unnormalized data")
        self.model = model
        self.mesh = mesh
        self.feeds = feeds
        self.learning_rate = learning_rate
        self._tx_key = ("adam", learning_rate) if tx is None else None
        self.tx = tx or adam_cached(learning_rate)
        self._st = ShardedTrainer(
            model, mesh, rng=rng, tx=self.tx, supervised=supervised,
            normalizer=normalizer if feeds.device_normalize else None,
            row_loss=True)
        self._devices = data_axis_devices(mesh)
        if len(self._devices) != len(feeds):
            raise ValueError(
                f"mesh data axis {len(self._devices)} != feeds "
                f"{len(feeds)} — one feed per data-axis device")
        self._host_state: Optional[TrainState] = None
        self._zero_shard: Optional[np.ndarray] = None
        self.last_shard_losses: Optional[np.ndarray] = None
        self.records_trained = 0

    # ----------------------------------------------- Trainer-shaped state
    @property
    def state(self) -> Optional[TrainState]:
        return self._st.state if self._st.state is not None \
            else self._host_state

    @state.setter
    def state(self, st: TrainState) -> None:
        # restore path: adopt a HOST state; the mesh (re)shards it on
        # the next step
        self._host_state = st
        self._st.state = None

    def _ensure_state(self, sample_x) -> None:
        if self.state is None:
            self._host_state = TrainState.create(
                self.model, self._st.rng, sample_x, tx=self.tx,
                tx_key=self._tx_key)

    # --------------------------------------------------------- assembly
    def _global_put(self, shards: List[np.ndarray]):
        """Per-device `device_put` + metadata-only global assembly: feed
        *d*'s rows land ONLY on device *d* (the zero-copy landing the
        tentpole names), then the mesh sees one logical array."""
        import jax

        arrays = [jax.device_put(s, d)
                  for s, d in zip(shards, self._devices)]
        shape = (sum(s.shape[0] for s in shards),) + shards[0].shape[1:]
        return jax.make_array_from_single_device_arrays(
            shape, self._st.data_sharding, arrays)

    def _assemble(self, row: Sequence):
        """Per-feed batches → (x_global, mask_global, n_valid).  A feed
        with no batch this step (its partitions ran dry first, or own
        fewer records) contributes a zero shard with a zero mask — the
        masked loss ignores it, shapes stay static, no recompiles."""
        template = next(b for b in row if b is not None)
        if self._zero_shard is None or \
                self._zero_shard.shape != template.x.shape:
            self._zero_shard = np.zeros_like(template.x)
        xs, masks, n_valid = [], [], 0
        zero_mask = np.zeros((template.x.shape[0],), np.float32)
        for b in row:
            if b is None:
                xs.append(self._zero_shard)
                masks.append(zero_mask)
            else:
                xs.append(np.ascontiguousarray(b.x, np.float32))
                masks.append(b.mask)
                n_valid += b.n_valid
        return self._global_put(xs), self._global_put(masks), n_valid

    # ---------------------------------------------------------- training
    def fit_round(self) -> dict:
        """One bounded pass over the feeds (their `take` budget): step
        per assembled global batch, losses held on device until the
        round closes (one sync), per-chip row losses published on
        `last_shard_losses`.  History mirrors `Trainer.fit_compiled`'s
        shape so `ContinuousTrainer.train_round` consumes it as-is."""
        import jax

        from ..obs import metrics as obs_metrics

        t0 = time.perf_counter()
        losses: list = []
        records = 0
        dev_s = 0.0
        last_row_loss = None
        last_counts = None
        shard_records = np.zeros(len(self.feeds), np.int64)
        for row in self.feeds.rounds():
            xg, mg, n_valid = self._assemble(row)
            if self._st.state is None:
                sample = next(b for b in row if b is not None).x
                self._ensure_state(sample)
                self._st.init(sample, from_state=self._host_state)
                self._host_state = None
            t_step = time.perf_counter()
            self._st.state, m = self._st._step(
                self._st.state, xg, xg, mg)
            dev_s += time.perf_counter() - t_step
            losses.append(m["loss"])  # device scalar: no per-step sync
            last_row_loss = m["row_loss"]
            last_counts = [0 if b is None else b.n_valid for b in row]
            shard_records += last_counts
            records += n_valid
        if not losses:
            return {"loss": [], "accuracy": [], "records": [],
                    "seconds": []}
        # the PR 12 profiling contract: device_compute spans THROUGH the
        # sync (dispatch is async — per-step timers would read ~0).
        # Losses sync once per round, so the round's device leg is the
        # accumulated dispatch time plus the closing device_get wait,
        # observed as ONE sample.
        t_sync = time.perf_counter()
        losses = [float(v) for v in jax.device_get(losses)]
        dev_s += time.perf_counter() - t_sync
        obs_metrics.step_seconds.observe(dev_s, loop="train",
                                         phase="device_compute")
        if last_row_loss is not None:
            self.last_shard_losses = shard_mean_losses(
                last_row_loss, last_counts)
        self.records_trained += records
        obs_metrics.records_trained.inc(records)
        return {"loss": [float(np.mean(losses))],
                "accuracy": [float("nan")],
                "records": [records],
                "seconds": [time.perf_counter() - t0],
                "steps": len(losses), "step_loss": losses,
                "fit": "sharded", "interpret": False,
                # per data-axis device — the evidence that every chip
                # trained on rows of its own: valid rows over the round,
                # and from its last step the mean loss (0.0 for a feed
                # that had run dry by then) and the device holding it
                "shard_records": shard_records.tolist(),
                "shard_losses": self.last_shard_losses.tolist(),
                "shard_devices": [s.device.id
                                  for s in _row_shards(last_row_loss)]}

    def fit_compiled(self, _batches=None, epochs: int = 1) -> dict:
        """Trainer-API shim: the feeds ARE the batch source.  Mesh
        rounds are single-pass by design (a committed stream cursor
        cannot re-read its slice without a seek)."""
        if epochs != 1:
            raise ValueError("mesh streaming rounds are single-epoch "
                             "(the cursor is the slice)")
        return self.fit_round()


def _row_shards(row_loss) -> list:
    """The sharded [B] row vector's addressable shards in global row
    order — the feed/device order by construction."""
    return sorted(row_loss.addressable_shards,
                  key=lambda s: s.index[0].start or 0)


def shard_mean_losses(row_loss, valid_counts: Sequence[int]) -> np.ndarray:
    """Per-chip mean pre-update loss out of the sharded row-loss vector.

    ``row_loss`` is the step's [B] metric sharded over 'data' (each
    shard already lives on its chip); ``valid_counts`` are the host-side
    valid-row counts per feed (padding rows carry mask 0, so shard sums
    need only dividing by the true counts).  Shards are ordered by their
    global row index, which is the feed/device order by construction."""
    pieces = _row_shards(row_loss)
    if len(pieces) != len(valid_counts):
        # a >1 model axis replicates row blocks; streaming refuses that
        # mesh shape upstream, so this is a defensive invariant
        raise ValueError(f"{len(pieces)} row-loss shards != "
                         f"{len(valid_counts)} feeds")
    return np.asarray([float(np.asarray(p.data).sum()) / max(c, 1)
                       for p, c in zip(pieces, valid_counts)])
