"""True online learning: per-window SGD folded into the consume loop.

The reference is explicit that it does micro-batch streaming ingestion,
*not* online learning (reference README.md:130-140): its train job
re-fits a 10k-record slice and redeploys.  ``OnlineLearner`` goes past
it — every polled window (default 100 records, one fixed-shape jitted
step) updates the model in place, a ``DriftMonitor`` watches the
step's own loss signal for distribution drift, and a drift triggers an
adaptation (learning-rate boost, detector-window reset, or a replay-
buffer re-fit) whose result is published through the PR 7
``ModelRegistry`` so the scorer fleet hot-swaps it live.

Discipline shared with ``ContinuousTrainer`` (train/live.py):

- ONE persistent committed-offsets cursor; offsets-as-checkpoint still
  holds: snapshots ride the ``AsyncCheckpointer`` with the exact
  cursors they were trained through, and the group commit trails
  manifest durability (``commit_manifest_offsets``), so a crashed
  learner resumes model + stream position as one consistent unit.
- Model updates reach scorers ONLY through the registry (lint R13): an
  in-place ``set_params`` on a serving scorer would bypass versioning,
  the rollback gate, and the swap metrics.

The drift signal is the train step's own pre-update loss — the step
computes it anyway, so detection costs zero extra device dispatches
(throughput against micro-batch training: not measured on the chip).
"""

from __future__ import annotations

import dataclasses
import collections
import time
from typing import Callable, Optional

import numpy as np

from ..chaos import faults as chaos
from ..data.dataset import SensorBatches
from ..obs import metrics as obs_metrics
from ..obs import watermark
from ..stream.consumer import StreamConsumer
from ..train.live import commit_manifest_offsets
from ..train.loop import (Trainer, adam_injectable_cached,
                          scanned_window_steps_cached)
from .detectors import ADAPTING, DriftMonitor


def _padded_shard_counts(mask: np.ndarray, n_shards: int) -> list:
    """Per-chip valid-row counts under `ShardedTrainer.put_batch`'s
    padding: rows pad up to a multiple of the data axis, shards are
    contiguous row blocks, padding rows carry mask 0."""
    b = len(mask)
    r = b if b % n_shards == 0 else b + (n_shards - b % n_shards)
    m = np.zeros((r,), np.float32)
    m[:b] = mask
    return [int(c) for c in m.reshape(n_shards, -1).sum(axis=1)]


@dataclasses.dataclass
class AdaptationPolicy:
    """Which adaptation a drift episode triggers.

    ``action``:
      boost — multiply the learning rate by ``lr_boost`` for the next
        ``boost_updates`` windows, then restore it.  Cheap; right for
        mild shifts the optimizer can chase.
      refit — replay the bounded recent-batch buffer for
        ``refit_epochs`` extra passes (a mini retrain biased to the
        post-drift distribution), THEN boost.  Right for severe
        shifts where per-window steps alone converge too slowly.
      reset — detector-window reset only (the monitor always resets
        its windows on drift; this action adds nothing else — the
        "trust the optimizer" null adaptation).
      auto — pick by measured severity: ``refit`` when the smoothed
        error rose past ``severe_ratio`` × baseline, else ``boost``.
    """

    action: str = "auto"
    lr_boost: float = 5.0
    boost_updates: int = 80
    refit_epochs: int = 2
    severe_ratio: float = 4.0

    def choose(self, severity: float, buffer_len: int) -> str:
        if self.action != "auto":
            return self.action
        if severity >= self.severe_ratio and buffer_len:
            return "refit"
        return "boost"


class OnlineLearner:
    """Per-record/small-window incremental trainer with drift-triggered
    adaptation, publishing through the model registry.

    Args:
      broker/topic/group: the stream leg, ContinuousTrainer-shaped.
      registry | checkpointer: where adapted models publish.  Pass a
        registry and an ``AsyncCheckpointer`` is built; pass a
        checkpointer to control its policy (queue depth, cadence,
        retention) — but a checkpointer is PER-TRAINER: its one commit
        hook encodes this group's cursor discipline, so trainers share
        a ModelRegistry, never a writer (enforced).  ``None`` both
        runs detect-only (tests).
      window: records per incremental update (one fixed [window, F]
        jitted step — the "small-window" in per-record/small-window;
        window=1 is true per-record SGD at per-dispatch cost).
      monitor/policy: drift detection + adaptation knobs.
      publish_every: windows between steady-state publishes (drift
        adaptations publish immediately, and again on convergence).
    """

    def __init__(self, broker, topic: str, registry=None,
                 checkpointer=None, model=None,
                 group: str = "cardata-online", window: int = 100,
                 learning_rate: float = 1e-3,
                 monitor: Optional[DriftMonitor] = None,
                 policy: Optional[AdaptationPolicy] = None,
                 normalizer=None, only_normal: bool = True,
                 publish_every: int = 20, buffer_batches: int = 32,
                 warm_start: bool = True, keep_versions: int = 0,
                 fuse: int = 8, mesh=None, device_normalize: bool = False,
                 chip_monitors: Optional[list] = None):
        if model is None:
            from ..models.autoencoder import CAR_AUTOENCODER

            model = CAR_AUTOENCODER
        self.broker = broker
        self.topic = topic
        self.group = group
        self.model = model
        self.window = int(window)
        #: catch-up fusion cap: when the stream runs ahead, up to this
        #: many windows run as ONE scanned device program (per-window
        #: losses still feed the detector) — the dispatch amortization
        #: that keeps incremental updates inside the throughput SLO.
        #: Group sizes bucket to powers of two so jit compiles at most
        #: log2(fuse)+1 variants.  1 disables fusion (pure per-window).
        self.fuse = max(1, int(fuse))
        self.base_lr = float(learning_rate)
        self.monitor = monitor or DriftMonitor()
        self.policy = policy or AdaptationPolicy()
        self.publish_every = int(publish_every)
        # injectable-LR Adam: the boost mutates opt_state.hyperparams —
        # same compiled step before, during and after a boost
        self._tx = adam_injectable_cached(learning_rate)
        self.trainer = Trainer(model, learning_rate=learning_rate,
                               tx=self._tx)
        # mesh mode (ISSUE 15): the window step runs SHARDED over the
        # data axis (rows → chips, gradient all-reduce over the mesh)
        # and each chip gets its OWN DriftMonitor fed from its shard's
        # per-row pre-update losses — a cohort drift that only one
        # chip's rows carry trips that chip's detector even when the
        # fleet-mean signal stays calm.  Coordination is ONE model +
        # ONE registry: any chip's drift begins a single global episode
        # (monitor.begin_episode) whose adaptation/publication rides
        # the exact machinery below.  device_normalize ships raw
        # columns and folds the affine map into the sharded step.
        self.mesh = mesh
        self._sharded = None
        self.chip_monitors: list = []
        self.last_chip_losses = None
        self._chip_signal: Optional[tuple] = None
        if mesh is not None:
            from ..core.normalize import CAR_NORMALIZER
            from ..parallel.data_parallel import ShardedTrainer
            from ..parallel.streaming import data_axis_devices

            n_dev = len(data_axis_devices(mesh))
            self._sharded = ShardedTrainer(
                model, mesh, tx=self._tx,
                normalizer=(normalizer or CAR_NORMALIZER)
                if device_normalize else None,
                row_loss=True)
            self.chip_monitors = list(chip_monitors) if chip_monitors \
                else [DriftMonitor() for _ in range(n_dev)]
            if len(self.chip_monitors) != n_dev:
                raise ValueError(f"{len(self.chip_monitors)} chip "
                                 f"monitors for a {n_dev}-device mesh")
        elif chip_monitors:
            raise ValueError("chip_monitors need a mesh")
        if device_normalize:
            if mesh is None:
                raise ValueError("device_normalize needs a mesh (the "
                                 "affine fold lives in the sharded step)")
            from ..core.normalize import RAW_COLUMNS

            normalizer = RAW_COLUMNS  # batcher ships raw columns
        self.checkpointer = checkpointer
        self.registry = registry
        if registry is not None and checkpointer is None:
            from ..mlops.checkpoint import AsyncCheckpointer

            self.checkpointer = AsyncCheckpointer(
                registry, keep_versions=keep_versions)
        if self.checkpointer is not None:
            self.registry = self.checkpointer.registry
            if self.checkpointer.commit_fn is not None:
                # the checkpointer has ONE commit hook, and it encodes
                # one trainer's (group, cursor) discipline — silently
                # stealing it would stall the other trainer's committed
                # cursor AND commit this group's offsets for records it
                # never trained.  A writer is per-trainer; share the
                # REGISTRY, not the checkpointer.
                raise ValueError(
                    "checkpointer is already wired to another "
                    "trainer's commit hook; each trainer owns its own "
                    "AsyncCheckpointer (they may share one "
                    "ModelRegistry)")
            # the shared crash-consistency hook: group commit trails
            # manifest durability, forward-only (train/live.py)
            self.checkpointer.commit_fn = lambda m: \
                commit_manifest_offsets(self.broker, self.group, m)
        broker.create_topic(topic)  # idempotent; a learner may boot
        # before the first producer provisions the stream
        parts = list(range(broker.topic(topic).partitions))
        self._parts = parts
        self.consumer = StreamConsumer.from_committed(broker, topic, parts,
                                                      group=group)
        # registry warm start — identical contract to ContinuousTrainer:
        # resume the lineage TIP's weights and apply its stamped cursors
        # forward-only (committed may trail the manifest, never lead it)
        self.restored_version: Optional[int] = None
        if self.registry is not None and warm_start:
            from ..mlops.checkpoint import restore_trainer

            m = restore_trainer(self.trainer, self.registry)
            if m is not None:
                self.restored_version = m.version
                for t, p, off in m.offsets:
                    cur = broker.committed(group, t, p) or 0
                    if off > cur:
                        self.consumer.seek(t, p, off)
        batch_kw = {} if normalizer is None else dict(normalizer=normalizer)
        # take-budgeted drains — ContinuousTrainer's cursor discipline:
        # each iteration emits at most `fuse` windows and the batcher's
        # poll budgeting (_need_rows) never over-polls past what it
        # will emit, so consumer.positions() at a drain boundary IS the
        # trained frontier.  Without the budget a suspended iterator
        # buffers up to poll_chunk rows past the trained frontier, and
        # a checkpoint stamped from positions() would, on crash-resume,
        # silently skip every polled-but-untrained record.
        self.batches = SensorBatches(self.consumer, batch_size=self.window,
                                     only_normal=only_normal,
                                     take=self.fuse,
                                     poll_chunk=max(self.window, 4096),
                                     **batch_kw)
        #: bounded replay buffer of recent (x, mask) windows — what a
        #: "refit" adaptation re-fits on (biased to the newest data by
        #: construction: drop-oldest)
        self.buffer: collections.deque = collections.deque(
            maxlen=max(1, int(buffer_batches)))
        self.updates = 0
        self.records_trained = 0
        self.last_loss: Optional[float] = None
        self.adaptations: list = []  # [(update_idx, signal, action)]
        self.published_versions: list = []
        self._boost_left = 0
        self._since_publish = 0
        # publish requests raised inside a group are applied at the
        # GROUP boundary: a mid-group snapshot would stamp the drain's
        # end offsets against a partially-trained state
        self._publish_pending = False
        self._publish_force = False
        obs_metrics.online_lr.set(self.base_lr)

    # -------------------------------------------------------------- lr
    @property
    def current_lr(self) -> float:
        st = self.trainer.state
        if st is None:
            return self.base_lr
        return float(st.opt_state.hyperparams["learning_rate"])

    def set_lr(self, lr: float) -> None:
        """Runtime LR mutation — an opt_state edit, no recompile."""
        import jax.numpy as jnp

        st = self.trainer.state
        if st is None:
            self.base_lr = float(lr)
            return
        hp = dict(st.opt_state.hyperparams)
        hp["learning_rate"] = jnp.asarray(lr, jnp.float32)
        self.trainer.state = st.replace(
            opt_state=st.opt_state._replace(hyperparams=hp))
        if self._sharded is not None and self._sharded.state is not None:
            # the sharded step trains from _sharded.state: the edit must
            # land there too (the scalar re-places as replicated on the
            # next dispatch — no recompile, same contract)
            self._sharded.state = self.trainer.state
        obs_metrics.online_lr.set(float(lr))

    # ----------------------------------------------------------- update
    def _update(self, b) -> float:
        """One incremental step on one window; returns the pre-update
        loss (the drift signal)."""
        if self._sharded is not None:
            return self._update_mesh(b)
        self.trainer._ensure_state(b.x)
        with obs_metrics.train_step_seconds.time(), \
                obs_metrics.step_seconds.time(loop="online",
                                              phase="device_compute"):
            self.trainer.state, m = self.trainer._step(
                self.trainer.state, b.x, b.x, b.mask)
        loss = float(m["loss"])
        self.updates += 1
        self.records_trained += b.n_valid
        self.last_loss = loss
        obs_metrics.online_updates.inc()
        obs_metrics.records_trained.inc(b.n_valid)
        self.buffer.append((b.x, b.mask))
        return loss

    def _update_mesh(self, b) -> float:
        """The sharded window step: rows shard over chips, the global
        loss is the drift signal as ever, and each chip's shard-mean
        pre-update loss additionally feeds its own monitor — a chip
        whose detector fires stages a coordinated episode that
        `_after_update` opens on the global monitor."""
        from ..parallel.streaming import shard_mean_losses

        if self._sharded.state is None:
            # adopt the (possibly warm-started) host state once
            self.trainer._ensure_state(b.x)
            self._sharded.init(b.x, from_state=self.trainer.state)
        with obs_metrics.train_step_seconds.time(), \
                obs_metrics.step_seconds.time(loop="online",
                                              phase="device_compute"):
            m = self._sharded.step(b.x, b.x, b.mask)
        loss = float(m["loss"])
        # mirror the CURRENT state: snapshot/publish and current_lr read
        # self.trainer.state, and it must never be a stale donated buffer
        self.trainer.state = self._sharded.state
        counts = _padded_shard_counts(b.mask, len(self.chip_monitors))
        chip = shard_mean_losses(m["row_loss"], counts)
        self.last_chip_losses = chip
        for i, (mon, cl, cnt) in enumerate(
                zip(self.chip_monitors, chip, counts)):
            if cnt <= 0:
                continue  # all-padding shard: no signal to judge
            sig = mon.update(float(cl))
            if sig is not None:
                self._chip_signal = (i, sig)
        self.updates += 1
        self.records_trained += b.n_valid
        self.last_loss = loss
        obs_metrics.online_updates.inc()
        obs_metrics.records_trained.inc(b.n_valid)
        self.buffer.append((b.x, b.mask))
        return loss

    def _update_group(self, bs) -> list:
        """K windows as ONE scanned dispatch (catch-up fusion): K
        sequential updates, per-window losses back for the detector."""
        self.trainer._ensure_state(bs[0].x)
        xs = np.stack([b.x for b in bs])
        masks = np.stack([b.mask for b in bs])
        scan = scanned_window_steps_cached(
            self.model, self._tx, tx_key=("online-adam", self.base_lr))
        with obs_metrics.train_step_seconds.time(), \
                obs_metrics.step_seconds.time(loop="online",
                                              phase="device_compute"):
            self.trainer.state, losses = scan(self.trainer.state, xs,
                                              masks)
        losses = [float(v) for v in np.asarray(losses)]
        n_valid = sum(b.n_valid for b in bs)
        self.updates += len(bs)
        self.records_trained += n_valid
        self.last_loss = losses[-1]
        obs_metrics.online_updates.inc(len(bs))
        obs_metrics.records_trained.inc(n_valid)
        for b in bs:
            self.buffer.append((b.x, b.mask))
        return losses

    def _take_group(self, limit: int) -> list:
        """One budgeted drain: at most ``limit`` windows, polled under
        the batcher's take/_need_rows cap so the consumer cursor never
        runs ahead of what this group will train (the offsets-as-
        checkpoint edge).  The iterator is run to completion — no
        suspended state, every drain leaves positions() == the trained
        frontier (modulo label-filtered rows, which are consumed by
        design exactly as in ContinuousTrainer)."""
        self.batches.take = max(1, limit)
        group = []
        with obs_metrics.step_seconds.time(loop="online",
                                           phase="host_pipeline"):
            for b in iter(self.batches):
                chaos.point("online.update")
                if b.n_valid:
                    group.append(b)
        return group

    def _after_update(self, loss: float) -> None:
        """The per-window control body: feed the monitor, adapt on
        drift, publish on cadence / episode end."""
        was_adapting = self.monitor.state == ADAPTING
        conv_before = self.monitor.converged
        signal = self.monitor.update(loss)
        obs_metrics.online_drift_stat.set(self.monitor.ph.stat)
        chip_signal, self._chip_signal = self._chip_signal, None
        if signal is not None:
            self._adapt(signal)
        elif chip_signal is not None and not was_adapting \
                and self.monitor.state != ADAPTING:
            # per-chip coordination (mesh mode): a chip-local drift the
            # fleet mean diluted — open ONE global episode (the model
            # is one model) and adapt at the tripping chip's severity
            i, sig = chip_signal
            tag = f"chip{i}-{sig}"
            self.monitor.begin_episode(tag)
            self._adapt(tag, severity=self.chip_monitors[i].severity())
        elif was_adapting and self.monitor.state != ADAPTING:
            # adaptation episode ended (converged or timed out):
            # restore the base LR and publish the adapted model — THIS
            # is the version the drift story promised the fleet
            if self.monitor.converged > conv_before:
                obs_metrics.online_converged.inc()
            self._boost_left = 0
            self.set_lr(self.base_lr)
            self._request_publish(force=True)
        elif self._boost_left > 0:
            self._boost_left -= 1
            if self._boost_left == 0:
                self.set_lr(self.base_lr)
        self._since_publish += 1
        if self._since_publish >= self.publish_every:
            self._request_publish()

    def _request_publish(self, force: bool = False) -> None:
        """Queue a publish for the next GROUP boundary: snapshots stamp
        consumer positions, and mid-group those describe rows the state
        has not trained through yet."""
        self._publish_pending = True
        self._publish_force = self._publish_force or force

    def process_available(self, max_updates: Optional[int] = None) -> int:
        """Consume and train on everything currently in the stream;
        returns windows processed.  A deep backlog is chewed in fused
        groups (power-of-two sizes up to ``fuse``); at the stream head
        the group degenerates to single windows — minimum latency live,
        amortized dispatch in catch-up.  Adaptation actions land
        between dispatches (a drift detected inside a fused group
        boosts/refits before the NEXT group, one group late at worst)
        and publishes land at group boundaries, where the consumer
        cursor and the trained state agree."""
        n = 0
        while True:
            want = self.fuse if max_updates is None \
                else min(self.fuse, max_updates - n)
            group = self._take_group(want)
            if not group:
                break
            while group:
                # largest power-of-two chunk: bounded compile variants.
                # Mesh mode dispatches per window — the sharded step
                # already amortizes over chips, and per-chip detectors
                # want window-granular shard losses
                k = 1 if self._sharded is not None \
                    else 1 << (len(group).bit_length() - 1)
                chunk, group = group[:k], group[k:]
                losses = [self._update(chunk[0])] if k == 1 \
                    else self._update_group(chunk)
                for loss in losses:
                    self._after_update(loss)
                n += k
            # group boundary: consumed == trained — publish the
            # ingest→train watermark from the folded event-time ranges
            watermark.observe_taken("train",
                                    self.consumer.take_event_time(),
                                    group=self.group)
            if self._publish_pending:
                force, self._publish_pending = self._publish_force, False
                self._publish_force = False
                self._publish(force=force)
            if max_updates is not None and n >= max_updates:
                break
        return n

    # ------------------------------------------------------- adaptation
    def _adapt(self, signal: str, severity: Optional[float] = None) -> None:
        if severity is None:
            severity = self.monitor.severity()
        action = self.policy.choose(severity, len(self.buffer))
        self.adaptations.append((self.updates, signal, action))
        obs_metrics.online_drifts.inc(detector=signal)
        obs_metrics.online_adaptations.inc(action=action)
        # window reset is unconditional: pre-drift detector state is
        # meaningless across a regime change (monitor.update already
        # moved to ADAPTING; reset re-arms its post-episode windows)
        self.monitor.reset_windows()
        if action == "refit":
            self._refit()
        if action in ("boost", "refit"):
            self.set_lr(self.base_lr * self.policy.lr_boost)
            self._boost_left = self.policy.boost_updates
        # ship the first adapted state at the group boundary: the
        # fleet should not score a drifted distribution on pre-drift
        # weights for a whole publish_every cadence
        self._request_publish(force=True)

    def _refit(self) -> None:
        """Replay-buffer mini-retrain: extra passes over the recent
        windows (drop-oldest buffer ⇒ post-drift biased)."""
        last = None
        for _ in range(self.policy.refit_epochs):
            for x, mask in list(self.buffer):
                if self._sharded is not None:
                    last = self._sharded.step(x, x, mask)
                    self.trainer.state = self._sharded.state
                else:
                    self.trainer.state, last = self.trainer._step(
                        self.trainer.state, x, x, mask)
                self.records_trained += int(mask.sum())
        if last is not None:
            self.last_loss = float(last["loss"])

    # ------------------------------------------------------- publishing
    def _publish(self, force: bool = False) -> None:
        self._since_publish = 0
        if self.checkpointer is None:
            return
        if not self.checkpointer.would_accept(force):
            self.checkpointer.coalesced += 1
            return
        cursors = self.consumer.positions()
        ends = {(t, p): self.broker.end_offset(t, p)
                for t, p, _off in cursors}
        self.checkpointer.snapshot(
            self.trainer.state, cursors,
            metrics={"loss": self.last_loss
                     if self.last_loss is not None else float("nan"),
                     "records": float(self.records_trained),
                     "drifts": float(self.monitor.drifts),
                     "online": 1.0},
            end_offsets=ends, force=force)

    def write_published(self) -> list:
        """Deterministically drain the checkpoint writer (tests/drills;
        live mode runs checkpointer.start() instead).  Returns the
        versions committed by this drain."""
        out = []
        if self.checkpointer is None:
            return out
        while True:
            v = self.checkpointer.write_once()
            if v is None:
                break
            out.append(v)
        self.published_versions.extend(out)
        return out

    # -------------------------------------------------------- lifecycle
    def run(self, stop: Optional[Callable[[], bool]] = None,
            max_seconds: Optional[float] = None,
            poll_interval_s: float = 0.05,
            on_update: Optional[Callable[[dict], None]] = None) -> int:
        """Consume-and-train until ``stop()``/``max_seconds``; returns
        windows processed.  Owns the checkpoint writer thread."""
        if self.checkpointer is not None:
            self.checkpointer.start()
        deadline = None if max_seconds is None else \
            time.monotonic() + max_seconds
        n = 0
        while (stop is None or not stop()) and \
                (deadline is None or time.monotonic() < deadline):
            got = self.process_available(max_updates=256)
            n += got
            if on_update is not None and got:
                on_update(self.describe())
            if not got:
                time.sleep(poll_interval_s)
        if self.checkpointer is not None:
            self._publish(force=True)  # newest state must not die
            self.checkpointer.flush(timeout_s=30.0)
        return n

    def close(self, timeout_s: float = 30.0) -> None:
        if self.checkpointer is not None:
            self.checkpointer.stop(flush=True, timeout_s=timeout_s)

    def describe(self) -> dict:
        out = {"updates": self.updates,
               "records_trained": self.records_trained,
               "loss": self.last_loss, "lr": self.current_lr,
               "adaptations": list(self.adaptations),
               "monitor": self.monitor.describe(),
               "published": list(self.published_versions)}
        if self.chip_monitors:
            out["chips"] = [m.describe() for m in self.chip_monitors]
        return out
