"""Continuous stream training with per-round artifact publication.

The reference's training side is a K8s Job that fits one slice, uploads the
model to GCS, and exits; `run.sh:16-91` then re-runs it and restarts the
predict pods so they download the new weights — a restart loop standing in
for continuous learning.  `ContinuousTrainer` is that loop as a long-lived
process: a persistent consumer cursor over the stream, fixed-shape training
rounds (so the scanned/fused fit compiles once), and an immutable versioned
model upload + atomic "latest"-pointer flip after every round, which a
`serve.live.LiveScorer` polls to hot-swap mid-stream.

Round shape: each round trains on exactly `take_batches` full batches
(fixed [S, B, F] → one compiled program for every round).  Rounds start
only once the stream has at least `min_available` new records, so a round
never stalls mid-fit waiting on the fleet.
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Callable, Optional

import numpy as np

from ..chaos import faults as chaos
from ..data.dataset import SensorBatches
from ..obs import metrics as obs_metrics
from ..obs import tracing, watermark
from ..stream.consumer import StreamConsumer
from .artifacts import ArtifactStore
from .loop import Trainer


def commit_manifest_offsets(broker, group: str, manifest) -> None:
    """Commit a durable manifest's stamped offsets for ``group``,
    FORWARD-ONLY and commit_many-batched — the shared post-durability
    half of offsets-as-checkpoint (``committed <= newest-durable-
    manifest`` at every instant).  Runs on the checkpoint-writer
    thread for both the micro-batch ``ContinuousTrainer`` and the
    per-window ``iotml.online`` learner, so the two training modes
    keep ONE crash-consistency story."""
    by_topic: dict = {}
    for t, p, off in manifest.offsets:
        cur = broker.committed(group, t, p)
        if cur is None or off > cur:
            by_topic.setdefault(t, []).append((p, off))
    commit_many = getattr(broker, "commit_many", None)
    for t, entries in by_topic.items():
        if commit_many is not None:
            commit_many(group, t, entries)
        else:
            for p, off in entries:
                broker.commit(group, t, p, off)


class ContinuousTrainer:
    """Round-based continuous training → versioned artifacts + pointer.

    Args:
      broker: Broker duck-type (in-process or a wire client).
      topic: input stream (the reference's SENSOR_DATA_S_AVRO leg).
      store/model_name: artifact root and the h5 blob base name; round K
        uploads `{model_name}.r{K}` then flips pointer `{model_name}.latest`.
      group: consumer group; the cursor resumes from committed offsets and
        commits after each round (the `committed` contract of the CLIs).
      take_batches × batch_size: records per round (reference job: 100×100
        per epoch, cardata-v3.py:217-222 — default 20×100 keeps rounds
        sub-second so the scorer sees fresh weights quickly).
    """

    def __init__(self, broker, topic: str, store: Optional[ArtifactStore],
                 model_name: str = "cardata-live.h5",
                 group: str = "cardata-live-train",
                 model=None, batch_size: int = 100, take_batches: int = 20,
                 epochs_per_round: int = 1, only_normal: bool = True,
                 learning_rate: float = 1e-3, normalizer=None,
                 backfill_since_ms: Optional[int] = None,
                 registry=None, checkpointer=None, warm_start: bool = True,
                 checkpoint_interval_s: float = 0.0,
                 mesh=None, device_normalize: bool = False):
        if model is None:
            from ..models.autoencoder import CAR_AUTOENCODER

            model = CAR_AUTOENCODER
        if store is None and registry is None and checkpointer is None:
            raise ValueError("need an ArtifactStore, a ModelRegistry, or "
                             "an AsyncCheckpointer to publish models to")
        self.broker = broker
        self.topic = topic
        self.store = store
        self.model_name = model_name
        self.group = group
        self.model = model
        self.batch_size = batch_size
        self.take_batches = take_batches
        self.epochs_per_round = epochs_per_round
        # mesh mode (ISSUE 15): partition-parallel columnar feeds into a
        # sharded train step — each data-axis device owns a partition
        # subset and a take_batches round trains D× the records of the
        # single-chip shape.  device_normalize additionally folds the
        # affine normalization into the jitted step (feeds ship raw
        # columns).  Checkpoints/restore ride the SAME surface: the
        # sharded state gathers host-side at snapshot, so a manifest
        # stamps every device's cursors as one atomic unit.
        self.mesh = mesh
        if device_normalize and mesh is None:
            # same contract as OnlineLearner: the affine fold lives in
            # the sharded step — silently falling back to host
            # normalization would mask a misconfiguration
            raise ValueError("device_normalize needs a mesh (the affine "
                             "fold lives in the sharded step)")
        if mesh is not None:
            if epochs_per_round != 1:
                raise ValueError("mesh streaming rounds are single-epoch "
                                 "(the cursor is the slice)")
            from ..core.normalize import CAR_NORMALIZER
            from ..parallel.streaming import (MeshFeeds,
                                              ShardedStreamTrainer)

            n_dev = mesh.shape["data"]
            feeds = MeshFeeds(broker, topic, n_dev, group=group,
                              batch_size=batch_size,
                              take_batches=take_batches,
                              only_normal=only_normal,
                              normalizer=normalizer,
                              device_normalize=device_normalize,
                              poll_chunk=8192)
            self.trainer = ShardedStreamTrainer(
                model, mesh, feeds, learning_rate=learning_rate,
                normalizer=(normalizer or CAR_NORMALIZER)
                if device_normalize else None)
        else:
            self.trainer = Trainer(model, learning_rate=learning_rate)
        # versioned-registry mode (iotml.mlops): checkpoints publish
        # async into the registry, each stamped with the cursors it was
        # trained through, and the GROUP COMMIT trails checkpoint
        # durability (the writer commits the manifest's offsets after
        # publication) — so committed <= manifest offsets always, and a
        # crash resumes model + stream position as one consistent unit
        self.registry = registry
        self.checkpointer = checkpointer
        if registry is not None and checkpointer is None:
            from ..mlops.checkpoint import AsyncCheckpointer

            self.checkpointer = AsyncCheckpointer(
                registry, min_interval_s=checkpoint_interval_s)
        if self.checkpointer is not None:
            self.registry = self.checkpointer.registry
            self.checkpointer.commit_fn = self._commit_checkpointed
        parts = range(broker.topic(topic).partitions)
        self._parts = list(parts)
        # ONE persistent cursor for the process lifetime: rebuilding a
        # consumer per round (and re-reading committed offsets) was the
        # dominant cost of the naive loop.  Mesh mode: the feeds ARE the
        # cursor — one facade over every device's consumer, positions()
        # spanning all partitions so offsets-as-checkpoint still names
        # the whole trained frontier.
        if mesh is not None:
            self.consumer = self.trainer.feeds
        else:
            self.consumer = StreamConsumer.from_committed(
                broker, topic, parts, group=group)
        # registry warm start: reload the newest committed version's
        # weights (+ optimizer moments when archived) and its stamped
        # offsets — the manifest beats BOTH offset 0 and backfill for
        # its partitions, because the restored model already knows the
        # data up to those cursors (re-reading it is double-train, and
        # a timestamp seek past them is a gap in the model's knowledge)
        manifest_offsets = {}
        if self.registry is not None and warm_start:
            from ..mlops.checkpoint import restore_trainer

            m = restore_trainer(self.trainer, self.registry)
            if m is not None:
                manifest_offsets = {(t, p): off for t, p, off in m.offsets}
                self.restored_version: Optional[int] = m.version
            else:
                self.restored_version = None
        else:
            self.restored_version = None
        # cold-start backfill (the durable store's replay API): a FIRST
        # incarnation of this group — no committed cursor, no manifest —
        # starts from the log's history at `backfill_since_ms` instead
        # of offset 0 of whatever happens to be retained, so a trainer
        # deployed against a long-retained durable topic trains on
        # exactly the requested window.  Partitions WITH a committed
        # cursor or a manifest cursor are never moved (resume beats
        # replay; the committed contract stays intact).
        if backfill_since_ms is not None:
            oft = getattr(broker, "offset_for_timestamp", None)
            if oft is not None:
                for p in parts:
                    if broker.committed(group, topic, p) is None and \
                            (topic, p) not in manifest_offsets:
                        self.consumer.seek(
                            topic, p, oft(topic, p, backfill_since_ms))
        # apply manifest cursors FORWARD-ONLY: committed can trail the
        # manifest (commit follows checkpoint) but must never be
        # rewound — commits stay monotonic even across a restore
        for (t, p), off in manifest_offsets.items():
            cur = broker.committed(group, t, p) or 0
            if off > cur:
                self.consumer.seek(t, p, off)
        # large poll chunks: each wire fetch is a round trip into the
        # broker process (expensive when that process is busy), and the
        # batcher's poll budgeting (_need_rows) guarantees a bounded
        # iteration never over-polls past the `take` boundary
        if mesh is None:
            batch_kw = {} if normalizer is None \
                else dict(normalizer=normalizer)
            self.batches = SensorBatches(self.consumer,
                                         batch_size=batch_size,
                                         take=take_batches,
                                         only_normal=only_normal,
                                         poll_chunk=8192, **batch_kw)
        else:
            # the per-device batchers live inside the feeds; rounds are
            # driven through the sharded trainer's fit_compiled shim
            self.batches = None
        self.rounds = 0
        self.records_trained = 0
        self.last_loss: Optional[float] = None
        #: new records required before a round starts — padded ~10% over
        #: the round size so the label filter cannot starve the last
        #: batch; a mesh round consumes one take_batches budget PER
        #: device
        round_records = take_batches * batch_size * \
            (mesh.shape["data"] if mesh is not None else 1)
        self.min_available = int(round_records * 1.1) + 1

    # ------------------------------------------------------------ rounds
    def available(self) -> int:
        """Records between the persistent cursor and the log end."""
        return sum(self.broker.end_offset(t, p) - off
                   for t, p, off in self.consumer.positions())

    def train_round(self) -> dict:
        """One fixed-shape fit over the next slice + artifact publish."""
        with tracing.phase("train", "round", round=self.rounds + 1):
            return self._train_round()

    def _train_round(self) -> dict:
        """`train_round` inside its `iotml.train.round` span: the fit
        (a span tree of its own, see Trainer.fit_compiled), then
        `checkpoint`, `publish` and `commit` as phases of the round."""
        t0 = time.perf_counter()
        history = self.trainer.fit_compiled(self.batches,
                                            epochs=self.epochs_per_round)
        if not history["loss"]:
            return {}
        self.rounds += 1
        self.records_trained += history["records"][-1] * self.epochs_per_round
        self.last_loss = float(history["loss"][-1])
        obs_metrics.live_train_rounds.inc()
        obs_metrics.live_train_loss.set(self.last_loss)
        # the round's slice is fully trained: publish the ingest→train
        # watermark from the event-time ranges the consume paths folded
        # (ISSUE 13) — batch-granular, exact on the columnar plane
        watermark.observe_taken("train", self.consumer.take_event_time(),
                                group=self.group)
        if self.checkpointer is not None:
            # async path: capture (device->host) the state + the exact
            # cursors it was trained through and return to training —
            # serialize/fsync happen on the writer thread, and the
            # GROUP COMMIT trails durability (_commit_checkpointed runs
            # after the manifest lands), so a crash at ANY point
            # resumes model + stream position as one consistent unit
            with tracing.phase("train", "checkpoint"):
                self._snapshot()
            artifact = f"registry:r{self.rounds}"
            if self.store is not None:  # legacy pointer riders along
                with tracing.phase("train", "publish"):
                    artifact = self.publish()
        else:
            with tracing.phase("train", "publish"):
                artifact = self.publish()
            # commit AFTER the artifact is durable (the `committed`
            # resume contract: a crash re-trains the slice rather than
            # skipping it)
            with tracing.phase("train", "commit"):
                self.consumer.commit()
        stats = {"t": time.time(), "round": self.rounds,
                 "loss": self.last_loss,
                 "records": history["records"][-1],
                 "records_cum": self.records_trained,
                 "seconds": round(time.perf_counter() - t0, 4),
                 "artifact": artifact,
                 "fit": history["fit"], "interpret": history["interpret"]}
        if self.mesh is not None:
            for key in ("shard_records", "shard_losses", "shard_devices"):
                stats[key] = history[key]
        return stats

    def publish(self) -> str:
        """Upload round K's weights as an immutable blob, flip the pointer."""
        import jax

        from ..models.h5_export import autoencoder_params_to_h5

        name = f"{self.model_name}.r{self.rounds}"
        with tempfile.TemporaryDirectory(prefix="iotml_live_") as tmp:
            local = os.path.join(tmp, "model.h5")
            autoencoder_params_to_h5(
                jax.tree.map(np.asarray, self.trainer.state.params), local)
            self.store.upload(local, name)
        self.store.put_text(f"{self.model_name}.latest", name)
        return name

    def _snapshot(self, force: bool = False) -> None:
        """Enqueue the current state + cursors for the async writer.
        The checkpointer's cadence throttle may coalesce it away
        (tracked so a clean exit can force-archive the newest state)."""
        if not self.checkpointer.would_accept(force):
            # skip the capture entirely: positions() plus one broker
            # end_offset round trip per partition is wasted work on a
            # snapshot the throttle would discard — with sub-second
            # rounds that's nearly every round
            self.checkpointer.coalesced += 1
            self._last_coalesced = True
            return
        before = self.checkpointer.coalesced
        cursors = self.consumer.positions()
        ends = {(t, p): self.broker.end_offset(t, p)
                for t, p, _off in cursors}
        self.checkpointer.snapshot(
            self.trainer.state, cursors,
            metrics={"loss": self.last_loss if self.last_loss is not None
                     else float("nan"),
                     "records": float(self.records_trained)},
            end_offsets=ends, force=force)
        self._last_coalesced = self.checkpointer.coalesced > before

    def _commit_checkpointed(self, manifest) -> None:
        """The writer's post-durability hook: commit the manifest's
        stamped offsets for this group, FORWARD-ONLY (see
        ``commit_manifest_offsets``).  A skipped (dropped) snapshot
        just means the next one commits further ahead.  On the writer's
        thread, so its `commit` phase is a root span there, not a child
        of the round that took the snapshot."""
        with tracing.phase("train", "commit"):
            commit_manifest_offsets(self.broker, self.group, manifest)

    def close(self, timeout_s: float = 30.0) -> None:
        """Flush pending checkpoints and stop an owned writer thread."""
        if self.checkpointer is not None:
            self.checkpointer.stop(flush=True, timeout_s=timeout_s)

    def run(self, stop: Optional[Callable[[], bool]] = None,
            max_rounds: Optional[int] = None,
            poll_interval_s: float = 0.05,
            on_round: Optional[Callable[[dict], None]] = None) -> int:
        """Train rounds until `stop()` or `max_rounds`; returns rounds run."""
        if self.checkpointer is not None:
            # live mode owns its writer thread (idempotent; a no-op when
            # a supervisor registered unit_loop() instead); deterministic
            # tests call train_round() + write_once() directly
            self.checkpointer.start()
        start = self.rounds
        while (stop is None or not stop()) and \
                (max_rounds is None or self.rounds - start < max_rounds):
            chaos.point("trainer.poll")
            if self.available() < self.min_available:
                time.sleep(poll_interval_s)
                continue
            stats = self.train_round()
            if stats and on_round is not None:
                on_round(stats)
        if self.checkpointer is not None:
            # the newest state must not die on a clean exit: re-enqueue
            # it when the cadence throttle coalesced the last round's
            # snapshot, then drain the queue
            if self.rounds > start and getattr(self, "_last_coalesced",
                                               False):
                self._snapshot(force=True)
            self.checkpointer.flush(timeout_s=30.0)
        return self.rounds - start
