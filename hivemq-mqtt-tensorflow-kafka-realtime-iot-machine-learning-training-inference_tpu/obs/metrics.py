"""Metrics registry with Prometheus text exposition.

The reference observes everything through Prometheus + Grafana (operator
installed first thing, `01_installConfluentPlatform.sh:12-15`; simulator and
broker export families like `agent_publish_*`, `kafka_extension_*` — SURVEY
§5).  The framework-native equivalent: every component registers counters/
gauges/histograms here, and `render()` emits Prometheus text format, served
by `start_http_server` for scrape parity with the reference's dashboards.

Standard metric families the framework emits (see `default_registry`):
  iotml_records_consumed_total      stream records decoded
  iotml_records_trained_total       records through the train step
  iotml_records_scored_total        records through the scorer
  iotml_train_step_seconds          train-step latency histogram
  iotml_reconstruction_mse          last reconstruction error gauge
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Sequence, Tuple


def _escape_label_value(value) -> str:
    """Prometheus text-format label escaping: backslash, double-quote and
    newline must be escaped or the exposition line is unparseable (a
    label value like `car="a\nb"` silently corrupts the whole scrape)."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels: Optional[dict]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name, self.help = name, help_
        self._vals: Dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._vals[key] = self._vals.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        return self._vals.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> str:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:  # scrapes race with inc() from worker threads
            vals = dict(self._vals)
        for key, v in sorted(vals.items()):
            out.append(f"{self.name}{_fmt_labels(dict(key))} {v}")
        if not vals:
            out.append(f"{self.name} 0")
        return "\n".join(out)


class Gauge(Counter):
    def set(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._vals[key] = float(value)

    def render(self) -> str:
        return super().render().replace(" counter", " gauge", 1)


class Histogram:
    """Fixed-bucket histogram (Prometheus cumulative-bucket convention).

    Optionally labeled: ``observe(v, stage="decode")`` keeps one bucket
    series per label set (the `iotml_stage_seconds{stage=...}` family);
    unlabeled observations are the plain single-series histogram."""

    DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0)

    def __init__(self, name: str, help_: str = "",
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self.buckets = tuple(sorted(buckets))
        # label-key tuple → [bucket counts..., +Inf count]; () = unlabeled
        self._series: Dict[tuple, list] = {}
        self._sums: Dict[tuple, float] = {}
        self._ns: Dict[tuple, int] = {}
        self._lock = threading.Lock()

    def _counts_for(self, key: tuple) -> list:
        counts = self._series.get(key)
        if counts is None:
            counts = self._series[key] = [0] * (len(self.buckets) + 1)
            self._sums[key] = 0.0
            self._ns[key] = 0
        return counts

    def observe(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            counts = self._counts_for(key)
            self._sums[key] += value
            self._ns[key] += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
                    return
            counts[-1] += 1

    def time(self, **labels):
        """Context manager: observe elapsed seconds (optionally into a
        labeled series, e.g. ``checkpoint_seconds.time(phase="fsync")``)."""
        hist = self

        class _T:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                hist.observe(time.perf_counter() - self.t0, **labels)

        return _T()

    def render(self) -> str:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:  # consistent bucket/sum/count snapshot under load
            series = {k: list(v) for k, v in self._series.items()}
            sums, ns = dict(self._sums), dict(self._ns)
        if not series:
            series[()] = [0] * (len(self.buckets) + 1)
            sums[()], ns[()] = 0.0, 0
        for key in sorted(series):
            labels = dict(key)
            cum = 0
            for b, c in zip(self.buckets, series[key]):
                cum += c
                out.append(f"{self.name}_bucket"
                           f"{_fmt_labels({**labels, 'le': b})} {cum}")
            cum += series[key][-1]
            out.append(f"{self.name}_bucket"
                       f"{_fmt_labels({**labels, 'le': '+Inf'})} {cum}")
            suffix = _fmt_labels(labels)
            out.append(f"{self.name}_sum{suffix} {sums[key]}")
            out.append(f"{self.name}_count{suffix} {ns[key]}")
        return "\n".join(out)


class Registry:
    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "", **kw) -> Histogram:
        return self._get(name, lambda: Histogram(name, help_, **kw))

    def _get(self, name, factory):
        with self._lock:
            if name not in self._metrics:
                self._metrics[name] = factory()
            return self._metrics[name]

    def render(self) -> str:
        return "\n".join(m.render() for _, m in sorted(self._metrics.items())) + "\n"

    def collect(self) -> Dict[str, float]:
        """Structured snapshot: metric name (with label suffix for labeled
        series; `_sum`/`_count` for histograms) → value.  The typed
        counterpart of `render()` for programmatic consumers."""
        out: Dict[str, float] = {}
        for name, m in sorted(self._metrics.items()):
            if isinstance(m, Histogram):
                with m._lock:
                    sums, ns = dict(m._sums), dict(m._ns)
                if not sums:
                    sums[()], ns[()] = 0.0, 0
                for key in sorted(sums):
                    suffix = _fmt_labels(dict(key))
                    out[f"{name}_sum{suffix}"] = sums[key]
                    out[f"{name}_count{suffix}"] = float(ns[key])
                continue
            with m._lock:
                vals = dict(m._vals)
            for key, v in sorted(vals.items()):
                out[name + _fmt_labels(dict(key))] = v
            if not vals:
                out[name] = 0.0
        return out


default_registry = Registry()
records_consumed = default_registry.counter(
    "iotml_records_consumed_total", "stream records decoded")
records_trained = default_registry.counter(
    "iotml_records_trained_total", "records through the train step")
records_scored = default_registry.counter(
    "iotml_records_scored_total", "records through the scorer")
train_step_seconds = default_registry.histogram(
    "iotml_train_step_seconds", "train-step latency")
reconstruction_mse = default_registry.gauge(
    "iotml_reconstruction_mse", "last mean reconstruction error")
# continuous-learning loop (train/live.py ContinuousTrainer +
# serve/live.py LiveScorer): the round-4 services reported these only as
# stdout JSON for the bench harness — the operator's dashboards chart
# them from here
live_train_rounds = default_registry.counter(
    "live_train_rounds_total", "continuous-trainer rounds completed")
live_train_loss = default_registry.gauge(
    "live_train_loss", "continuous-trainer last round loss")
live_model_updates = default_registry.counter(
    "live_model_updates_total", "scorer weight hot-swaps applied")
live_detection_precision = default_registry.gauge(
    "live_detection_precision",
    "live verdict precision vs stream labels (cumulative)")
live_detection_recall = default_registry.gauge(
    "live_detection_recall",
    "live verdict recall vs stream labels (cumulative)")
# stream-plane hot-path telemetry (ISSUE 2): batch/commit shape of the
# consume path and the failure counters the serve loop's redelivery
# story turns on — alongside the per-record trace spans (obs.tracing)
fetch_batch_size = default_registry.histogram(
    "iotml_fetch_batch_size", "records returned per non-empty consumer poll",
    buckets=(1, 8, 32, 128, 512, 1024, 2048, 4096))
commit_seconds = default_registry.histogram(
    "iotml_commit_seconds", "consumer offset-commit latency")
scorer_rewinds = default_registry.counter(
    "iotml_scorer_rewinds_total",
    "scorer rewind-to-committed redeliveries after a broker failover")
consumer_autoresets = default_registry.counter(
    "iotml_consumer_autoresets_total",
    "consumer cursors auto-reset to earliest after retention trimmed "
    "past them (OffsetOutOfRange), by topic")
# the consumer's read-ahead of one take (stream/consumer.py
# `read_ahead`): rows a poll_decoded took from it (hit), rows a poll
# that found it unusable fetched in the foreground instead (miss), and
# rows fetched ahead and never delivered (dropped: a seek, a rewind, a
# poll of another size) — they stayed in the log, behind the cursors
consumer_readahead_rows = default_registry.counter(
    "iotml_consumer_readahead_rows_total",
    "rows of the consumer's read-ahead by result (hit | miss | dropped)")
replica_sync_rounds = default_registry.counter(
    "iotml_replica_sync_rounds_total", "follower replication rounds")
replica_copied = default_registry.counter(
    "iotml_replica_copied_total", "messages copied leader -> follower")
replica_sync_errors = default_registry.counter(
    "iotml_replica_sync_errors_total",
    "replication rounds that failed (leader dying / unreachable)")
# replication + failover observability (ISSUE 4): the loss window and
# the fencing epoch as LIVE gauges, so dashboards see a promotion and
# the at-risk record count without polling replica.lag() themselves
replica_lag = default_registry.gauge(
    "iotml_replica_lag_records",
    "per-topic records the leader has that the follower does not "
    "(the loss window if the leader died now)")
failover_epoch = default_registry.gauge(
    "iotml_failover_epoch",
    "current leadership fencing epoch (bumped at every promotion)")
# supervision (iotml.supervise): the kubelet-equivalent's own telemetry
supervisor_unit_up = default_registry.gauge(
    "iotml_supervisor_unit_up",
    "1 while a supervised unit is live, 0 while down/degraded")
supervisor_restarts = default_registry.counter(
    "iotml_supervisor_restarts_total",
    "restarts issued per supervised unit")
supervisor_wedged = default_registry.counter(
    "iotml_supervisor_wedged_total",
    "wedge detections (live thread, stale heartbeat/stage) per unit")
supervisor_degraded = default_registry.gauge(
    "iotml_supervisor_degraded",
    "1 when the restart-storm budget is exhausted and the supervisor "
    "gave the unit up")
supervisor_failovers = default_registry.counter(
    "iotml_supervisor_failovers_total",
    "on_death failover hooks fired (leader promotions)")
# partitioned data plane (iotml.cluster): routing health — a rising
# bounce rate means clients chronically chase a moving partition map;
# failover counters pair with the supervise gauges above
cluster_not_leader_bounces = default_registry.counter(
    "iotml_cluster_not_leader_total",
    "produce/fetch requests bounced with NOT_LEADER_FOR_PARTITION "
    "(stale client metadata; refreshed and re-routed)")
cluster_metadata_refreshes = default_registry.counter(
    "iotml_cluster_metadata_refreshes_total",
    "cluster metadata refreshes performed by routing clients")
cluster_shard_failovers = default_registry.counter(
    "iotml_cluster_shard_failovers_total",
    "per-shard leader failovers (one shard moved, not the world; "
    "label shard= says WHICH — the TSDB query surface can tell a "
    "flapping shard from spread-out churn)")
cluster_shard_epoch = default_registry.gauge(
    "iotml_cluster_shard_epoch",
    "current leadership epoch per shard (a bump = a promotion; the "
    "federated scrape carries the per-shard label into the TSDB)")
cluster_coordinator_moves = default_registry.counter(
    "iotml_cluster_coordinator_moves_total",
    "group-coordinator re-discoveries after NOT_COORDINATOR or a "
    "coordinator broker death")
# model lifecycle (iotml.mlops): the continuous-delivery loop's own
# telemetry — which model every process is running (version gauges by
# component role), how far behind the log the serving model's training
# data is, and where checkpoint wall-time goes (the "no training stall"
# claim is only a claim until phase=snapshot is measured on the train
# thread and serialize/fsync are measured OFF it)
model_version = default_registry.gauge(
    "iotml_model_version",
    "registry version currently loaded, by component "
    "(trainer = last published, scorer = serving)")
model_offsets_lag = default_registry.gauge(
    "iotml_model_offsets_lag",
    "records between the current model's stamped train offsets and the "
    "log end (staleness of the serving model's knowledge)")
checkpoint_seconds = default_registry.histogram(
    "iotml_checkpoint_seconds",
    "checkpoint wall-time by phase: snapshot (train thread, device->"
    "host), serialize + fsync (background writer thread)")
checkpoint_dropped = default_registry.counter(
    "iotml_checkpoint_dropped_total",
    "pending snapshots evicted drop-oldest from the bounded writer "
    "queue (a slow disk sheds checkpoints, never stalls training)")
registry_publishes = default_registry.counter(
    "iotml_registry_publishes_total",
    "model versions committed to the registry (manifest written)")
registry_torn_recovered = default_registry.counter(
    "iotml_registry_torn_recovered_total",
    "torn/uncommitted version dirs swept by registry recovery")
registry_pruned = default_registry.counter(
    "iotml_registry_pruned_total",
    "committed versions removed by retention (keep-newest-N; channel "
    "targets are never pruned)")
model_swaps = default_registry.counter(
    "iotml_model_swaps_total",
    "scorer hot-swaps applied by registry watchers (no restart, no "
    "dropped records)")
rollouts = default_registry.counter(
    "iotml_rollouts_total",
    "A/B rollout gate decisions, by outcome (promoted | rolled_back)")
# true online learning (iotml.online): the per-window incremental
# learner's own telemetry — update cadence, what the drift detectors
# saw, which adaptation the policy chose, and whether adaptation
# actually converged (the state machine's STABLE re-entry).  The LR
# gauge makes a boost visible while it is active; the drift-stat gauge
# is the Page-Hinkley statistic an operator alarms on BEFORE the
# threshold trips.
online_updates = default_registry.counter(
    "iotml_online_updates_total",
    "incremental (per-window) SGD updates applied by the online learner")
online_drifts = default_registry.counter(
    "iotml_online_drifts_total",
    "drift episodes detected on the reconstruction-error signal, by "
    "detector (ph | adwin | level)")
online_adaptations = default_registry.counter(
    "iotml_online_adaptations_total",
    "drift-triggered adaptations applied, by action "
    "(boost | refit | reset)")
online_converged = default_registry.counter(
    "iotml_online_converged_total",
    "adaptation episodes that converged (smoothed error back inside "
    "the stable band; the monitor re-anchored its baseline)")
online_lr = default_registry.gauge(
    "iotml_online_learning_rate",
    "the online learner's current effective learning rate (boosted "
    "while a drift adaptation is active)")
online_drift_stat = default_registry.gauge(
    "iotml_online_drift_stat",
    "current Page-Hinkley statistic over the normalized smoothed "
    "error (drift fires when it crosses the configured threshold)")
# adversarial fleet conditions (iotml.gen.scenarios): agents that
# respected an MQTT backpressure signal defer records into their own
# bounded buffer instead of letting the broker drop-oldest
fleet_deferred = default_registry.counter(
    "iotml_fleet_deferred_total",
    "fleet-agent publishes deferred under MQTT backpressure (drained "
    "on later ticks — deferred, never dropped)")
# dead-letter queue (streamproc.dlq): poisoned frames routed, by source
dlq_total = default_registry.counter(
    "iotml_dlq_total",
    "undecodable records routed to a dead-letter topic, by source topic")
dlq_route_errors = default_registry.counter(
    "iotml_dlq_route_errors_total",
    "dead letters that could not be routed (degraded to a plain drop)")
# fleet-scope observability v2 (ISSUE 13): event-time watermarks on the
# columnar plane.  Per-record spans cannot exist where zero Python
# records materialise, but every store frame carries the record's
# timestamp — so each consuming stage reports, batch-granularly, how
# far behind EVENT TIME its progress frontier sits.  Lag is observed
# for the batch's min AND max event time, so the histogram brackets the
# true per-record e2e latency from below and above at zero per-record
# cost.  `stage` is a closed set (consume | score | train | twin).
watermark_lag_seconds = default_registry.histogram(
    "iotml_watermark_lag_seconds",
    "event-time lag (now - record timestamp) at each stage's progress "
    "frontier, batch-granular (min and max event time per batch)",
    buckets=(0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0,
             60.0, 300.0))
watermark_event_ms = default_registry.gauge(
    "iotml_watermark_event_time_ms",
    "newest event timestamp (ms) each stage has fully processed — the "
    "stage's event-time watermark, by stage/topic/partition")
# consumer lag made first-class (ISSUE 13 satellite): records between
# the group's cursor and the partition high-water mark, refreshed at
# batch/commit granularity from the hwm every fetch response already
# carries (wire legs) or one end_offset read (in-process legs)
consumer_lag_records = default_registry.gauge(
    "iotml_consumer_lag_records",
    "records between a consumer group's cursor and the partition "
    "high-water mark, by group/topic/partition")
# hot-loop profiling hooks (ISSUE 13): where a train/score/online step's
# wall time actually goes — waiting on data (host_wait), inside the
# jitted program (device_compute), or in host-side decode/convert/
# format (host_pipeline).  The measured host-vs-device balance ROADMAP
# item 3 (multi-chip training) starts from.
step_seconds = default_registry.histogram(
    "iotml_step_seconds",
    "wall time by loop (train|score|online|stream, and start: a "
    "process's way to its first fit — import, backend, engine, "
    "state_init, once each or once a slow import) and phase; "
    "phases NEST, so never sum the family: train fit > host_pipeline "
    "(> fetch, decode), stack, device_compute (> transfer, dispatch, "
    "sync); train round > fit, publish, checkpoint, commit; score "
    "drain > host_pipeline (> fetch, decode), device_compute, "
    "writeback; host_wait is the prefetcher's",
    buckets=(0.0001, 0.001, 0.005, 0.02, 0.05, 0.1, 0.5, 1.0, 5.0,
             30.0))
# what JAX reports of its own compilations (utils.device listens):
# seconds by stage and program.  `backend` is one per program built OR
# loaded — it covers `cache_read`, the part spent reading an executable
# back from the persistent cache — so a count that moves mid-stream is
# a stall, whichever it was.  `program` is an iotml_* jitted function's
# name, or "other".
compile_seconds = default_registry.histogram(
    "iotml_compile_seconds",
    "JAX compilation time by stage (trace | lower | backend | "
    "cache_read; cache_read is part of backend) and program",
    buckets=(0.001, 0.01, 0.1, 0.5, 1.0, 5.0, 20.0, 60.0, 180.0))
compile_cache = default_registry.counter(
    "iotml_compile_cache_total",
    "persistent compile cache lookups by result (hit | miss) and "
    "program")
# the flash kernels' step geometry (ops/attention.py `flash_geometry`),
# set at trace time by each pallas_call site: what the last compiled
# call of each kernel (fwd | bwd_dkv | bwd_dq | bwd_fused) engaged.
flash_grid_steps = default_registry.gauge(
    "iotml_flash_grid_steps",
    "grid steps a call of a flash-attention kernel, by kernel")
flash_block_q = default_registry.gauge(
    "iotml_flash_block_q", "query rows a flash kernel's tile holds")
flash_block_k = default_registry.gauge(
    "iotml_flash_block_k", "keys a flash kernel's tile holds")
flash_heads_per_step = default_registry.gauge(
    "iotml_flash_heads_per_step",
    "neighbouring heads one grid step of a flash kernel handles")
flash_lanes_per_step = default_registry.gauge(
    "iotml_flash_lanes_per_step",
    "lanes of a flash kernel's [B, T, H*D] column block: heads a step x D")
flash_value_lanes_per_step = default_registry.gauge(
    "iotml_flash_value_lanes_per_step",
    "lanes of a flash kernel's [B, T, H*Dv] column block of v and out: "
    "heads a step x Dv (equal to iotml_flash_lanes_per_step where value "
    "heads are as wide as query heads)")
latent_assembled_operands = default_registry.gauge(
    "iotml_latent_assembled_operands",
    "operands of its attention call the last traced latent-attention "
    "mixer assembled by a copy ahead of it (k: the one rotary key head "
    "broadcast over the heads beside each head's own features); "
    "iotml_flash_operand_copies counts the flash wrapper's own")
flash_operand_copies = default_registry.gauge(
    "iotml_flash_operand_copies",
    "operands of a flash kernel's call copied ahead of it "
    "(a T pad, a repeated k or v)")
flash_backward_fused = default_registry.gauge(
    "iotml_flash_backward_fused",
    "1 where the last traced flash backward took the one kernel "
    "(iotml_flash_bwd_fused: dQ's column resident on dK/dV's grid, five "
    "products a tile), 0 where its column fits no geometry and it took "
    "the two (iotml_flash_bwd_dkv, iotml_flash_bwd_dq: seven)")
flash_bwd_column_bytes = default_registry.gauge(
    "iotml_flash_bwd_column_bytes",
    "bytes of the float32 dQ column [t_q, heads a step x D] a step of the "
    "last traced fused flash backward holds in VMEM, one buffer of the "
    "pipeline's two (0 where the backward took the two kernels)")
# the same by kernel AND by mask (kind: dense | causal | band): one
# program may hold causal calls beside band calls (a sliding window),
# and the last traced call of each kernel under each mask stands.
flash_mask_window = default_registry.gauge(
    "iotml_flash_mask_window",
    "keys a query meets under a flash kernel's mask, itself among them "
    "(a band's window; 0: its whole past, or every key), by kernel and "
    "mask")
flash_mask_tiles = default_registry.gauge(
    "iotml_flash_mask_tiles",
    "score tiles a head's grid walks in a flash kernel's call (the live "
    "tiles of the triangle or of the band), by kernel and mask")
flash_mask_walked_area = default_registry.gauge(
    "iotml_flash_mask_walked_area",
    "scores a head's walked tiles hold in a flash kernel's call: tiles x "
    "block_q x block_k, by kernel and mask")
flash_mask_live_area = default_registry.gauge(
    "iotml_flash_mask_live_area",
    "scores of a head the mask lets through at the call's length (T^2, "
    "the triangle's T(T+1)/2 or the band's), by kernel and mask: over "
    "iotml_flash_mask_walked_area, the share of the walked tiles' area "
    "that is required work")
# the chunked state-space scan (ops/ssd.py) and the hybrid model's layer
# stack (models/hybrid.py), set at trace time like the flash geometry:
# what the last traced scan and model engaged.
ssd_chunk_size = default_registry.gauge(
    "iotml_ssd_chunk_size", "positions a chunk of the state-space scan holds")
ssd_chunks = default_registry.gauge(
    "iotml_ssd_chunks", "chunks a sequence of the state-space scan is cut in")
ssd_state_bytes = default_registry.gauge(
    "iotml_ssd_state_bytes",
    "bytes of recurrent state one sequence holds in one state-space layer")
# the chunked gated delta rule (ops/delta.py `kda_scan`), at trace time as
# the state-space scan's: what the last traced call engaged
kda_chunk_size = default_registry.gauge(
    "iotml_kda_chunk_size",
    "positions a chunk of the gated delta rule's scan holds")
kda_chunks = default_registry.gauge(
    "iotml_kda_chunks",
    "chunks a window of the gated delta rule's scan is cut in")
kda_state_bytes = default_registry.gauge(
    "iotml_kda_state_bytes",
    "bytes of matrix states a call of the gated delta rule's scan keeps for "
    "its backward pass: the state entering each segment of chunks, "
    "[segments, B, H, K, V] in float32")
kda_intra_kernel = default_registry.gauge(
    "iotml_kda_intra_kernel",
    "head-chunks one call of the delta rule's inner-part kernel covers, by "
    "direction (fwd | bwd); 0 where the plain form ran")
# the convolution kernels ahead of the scan (ops/ssd.py
# `causal_conv1d_silu`), set where the calls of a direction (fwd | bwd)
# are built: what `conv_geometry` gave the last traced convolution.
conv_grid_steps = default_registry.gauge(
    "iotml_conv_grid_steps",
    "grid steps the convolution kernel's calls of one direction take, "
    "by kernel")
conv_block_t = default_registry.gauge(
    "iotml_conv_block_t", "positions a block of the convolution kernels holds")
conv_block_c = default_registry.gauge(
    "iotml_conv_block_c", "channels a block of the convolution kernels holds")
conv_taps = default_registry.gauge(
    "iotml_conv_taps", "taps a channel of the last traced convolution has")
conv_activation_fused = default_registry.gauge(
    "iotml_conv_activation_fused",
    "1 where the last traced convolution's kernels applied an activation "
    "(SiLU) to the sum, 0 where they stored it as it stood (a gated short "
    "convolution, whose gates are its caller's)")
conv_operand_copies = default_registry.gauge(
    "iotml_conv_operand_copies",
    "operands of the convolution kernels copied ahead of them by their "
    "wrapper (a run of channels sliced out of its array, positions padded "
    "to whole blocks); XLA's own layout copies around a call are not counted")
model_layers = default_registry.gauge(
    "iotml_model_layers",
    "layers of the last traced hybrid model, by the kind of their mixer "
    "(mamba | attention | mla | short_conv | window_attention | kda) and "
    "of their feed-forward part (dense_ffn | moe_ffn)")
model_mla_rope = default_registry.gauge(
    "iotml_model_mla_rope",
    "1 where the last traced latent-attention layer turned its 64-wide "
    "parts by rotary positions, 0 where it left them un-turned (a model "
    "whose latent attention carries no positions)")
model_loop_steps = default_registry.gauge(
    "iotml_model_loop_steps",
    "passes a step the last traced hybrid model makes over its one set of "
    "layers (1: a stack that is no loop)")
model_post_norms = default_registry.gauge(
    "iotml_model_post_norms",
    "norms the last traced hybrid block applied to its parts' OUTPUTS "
    "ahead of the residual adds (sandwich norms: one a part, else 0)")
# a looped stack's objective (models/hybrid.py `expected_loss`): DATA,
# read back with a fit's losses at its one sync, the last fit's means
loop_exit_mass = default_registry.gauge(
    "iotml_loop_exit_mass",
    "mean mass the exit distribution gave each pass over the last fit's "
    "valid positions, by kind (pass1 | pass2 | ...: they sum to 1)")
loop_pass_loss = default_registry.gauge(
    "iotml_loop_pass_loss",
    "mean squared error of each pass's own output over the last fit's "
    "valid positions, by kind (pass1 | pass2 | ...)")
# grouped attention's two optional parts (models/hybrid.py
# `GroupedAttention`), at trace time: what the last traced layer applied
attn_rotary_dim = default_registry.gauge(
    "iotml_attn_rotary_dim",
    "features of a head the last traced grouped-attention layer turned by "
    "rotary positions (the whole head, or 0: no positions)")
attn_rotary_kernel = default_registry.gauge(
    "iotml_attn_rotary_kernel",
    "operands (q and k: 2, or 0) the last traced grouped-attention layer "
    "turned by rotary positions inside the Pallas call iotml_rope, on the "
    "projections' own [B, T, H*D]; 0 beside a non-zero "
    "iotml_attn_rotary_dim: XLA's pair form (ops/moe.py rotary) ran, by "
    "attn_mode dense or heads that fill no whole 128-lane tiles")
attn_window = default_registry.gauge(
    "iotml_attn_window",
    "keys a query of the last traced grouped-attention layer meets, "
    "itself among them (a window_attention layer's sliding window; 0: "
    "its whole causal past)")
attn_qk_norm = default_registry.gauge(
    "iotml_attn_qk_norm",
    "1 where the last traced grouped-attention layer normed its queries "
    "and keys a head (RMSNorm), else 0")
# the sparse-expert layer (models/latent_moe.py, ops/moe.py).  Shape at
# trace time, as above; the assignments are DATA, read back with a
# fit's losses at its one sync (`Trainer.fit_compiled`).
moe_experts = default_registry.gauge(
    "iotml_moe_experts",
    "experts of the last traced expert layer, by kind (held: computed "
    "here | routed_over: the router's outputs)")
moe_router_form = default_registry.gauge(
    "iotml_moe_router_form",
    "1 beside the form of the last traced expert layer's router, 0 "
    "beside the others, by kind (sigmoid: sigmoids, a selection-only "
    "bias, the selected over their sum | softmax_topk: the largest raw "
    "logits, a softmax over the selected)")
moe_router_input = default_registry.gauge(
    "iotml_moe_router_input",
    "1 beside what the last traced expert layer's router read, 0 beside "
    "the other, by kind (ffn: the normed stream its experts read | "
    "block: the block's own input, ahead of the mixer and un-normed)")
moe_expert_form = default_registry.gauge(
    "iotml_moe_expert_form",
    "1 beside the form of the last traced expert layer's experts, 0 "
    "beside the others, by kind (ops.moe.EXPERT_FORMS)")
moe_top_k = default_registry.gauge(
    "iotml_moe_top_k", "experts a token is routed to")
moe_latent_dim = default_registry.gauge(
    "iotml_moe_latent_dim",
    "width of the latent the last traced expert layer's routed experts "
    "act in (0: at the stream's full width)")
moe_shared_dim = default_registry.gauge(
    "iotml_moe_shared_dim",
    "width of the shared expert every token of the last traced expert "
    "layer takes beside its routed experts (0: the layer has none)")
moe_dispatch_rows = default_registry.gauge(
    "iotml_moe_dispatch_rows",
    "static rows an expert layer's dispatch is built for: the worst the "
    "router can produce, tokens x min(top_k, experts held)")
moe_plan_sorted_operands = default_registry.gauge(
    "iotml_moe_plan_sorted_operands",
    "operands the sort of an expert layer's dispatch plan carries (3: the "
    "key, the index and the routing weights, which so reach their sorted "
    "places, and their cotangents come back, without a gather or a scatter)")
moe_add_rows = default_registry.gauge(
    "iotml_moe_add_rows",
    "tile loops of the last traced expert layer's walk (forward and "
    "backward: 2, or 0) that add a tile's rows back onto their tokens in "
    "this form, by kind (kernel: the Pallas call iotml_add_rows, row DMAs "
    "on the loop's carried accumulator | scatter: XLA's scatter-add, under "
    "attn_mode dense, for an accumulator small enough for XLA to keep in "
    "VMEM, or rows that fill no whole 128-lane float32 tiles)")
moe_add_rows_chunk = default_registry.gauge(
    "iotml_moe_add_rows_chunk",
    "rows of a tile a grid step of iotml_add_rows adds back in the last "
    "traced expert layer (0: XLA's scatter-add ran)")
moe_assignments = default_registry.counter(
    "iotml_moe_assignments_total",
    "token-to-expert assignments of the fits so far, all expert layers, "
    "by kind (held: to an expert computed here | elsewhere: left out)")
moe_tile_rows = default_registry.counter(
    "iotml_moe_tile_rows_total",
    "rows of the live tiles the expert layers' dispatch walked in the "
    "fits so far, by kind (live: a row that holds an assignment | "
    "padding: the rest of an expert's last tile)")
moe_expert_load = default_registry.gauge(
    "iotml_moe_expert_load_max_over_mean",
    "the busiest expert held over the mean of the experts held, by "
    "assignments of the last fit")
remat_blocks = default_registry.gauge(
    "iotml_remat_blocks",
    "blocks of the last traced model recomputed in the backward pass")
# by kind: a row of `models.hybrid.TABLE`, which says what each one is
remat_kept_bytes = default_registry.gauge(
    "iotml_remat_kept_bytes",
    "bytes a step the last traced model's recomputed blocks keep from "
    "the forward pass, by kind (the rows of models/hybrid.py TABLE: "
    "kept always, or in the layers a byte budget takes)")
remat_kept_layers = default_registry.gauge(
    "iotml_remat_kept_layers",
    "layers of the last traced model whose recomputation keeps a large "
    "value under the byte budget, by kind (the rows of models/hybrid.py "
    "TABLE the budget buys)")
remat_keepable_layers = default_registry.gauge(
    "iotml_remat_keepable_layers",
    "layers of the last traced model that make such a value, kept or "
    "not, by kind (as iotml_remat_kept_layers)")
prefetch_occupancy = default_registry.gauge(
    "iotml_prefetch_occupancy",
    "DevicePrefetcher queue fill fraction (0 = device starving on the "
    "host pipeline, 1 = host running ahead)")
# quorum replication (iotml.replication, ISSUE 14): the in-sync-replica
# set and the quorum high-water mark as live gauges — |ISR| per
# partition (leader included), how many covered partitions run below
# their target replica count, and how far the un-replicated tail
# (leader end - quorum HWM) currently reaches.  The federation
# collector rolls these up worst-of across the fleet.
isr_size = default_registry.gauge(
    "iotml_isr_size",
    "in-sync replica count per partition, leader included (acks=all "
    "commits at min(ISR positions))")
under_replicated = default_registry.gauge(
    "iotml_under_replicated_partitions",
    "replicated partitions whose ISR is below the configured replica "
    "target (followers evicted for lag/staleness and not re-admitted)")
quorum_hwm_lag = default_registry.gauge(
    "iotml_quorum_hwm_lag_records",
    "records between the leader log end and the quorum high-water mark "
    "— the tail acks=all producers are still waiting on and consumers "
    "cannot read yet, by topic/partition")


#: the CLOSED label-key vocabulary every iotml metric must draw from.
#: Metric labels multiply series: one label drawn from an unbounded set
#: (a car id, a trace id, an offset) turns a fixed-cost scrape into an
#: unbounded allocation — the cardinality-bound test (and lint R6)
#: fails such a label before production does.
ALLOWED_LABEL_KEYS = frozenset({
    "stage", "topic", "partition", "group", "phase", "loop", "process",
    "component", "detector", "action", "fault", "source", "outcome",
    "unit", "le", "slo", "window", "shard", "route", "code", "program",
    "result", "kernel", "kind", "direction",
})

#: per-metric ceiling on distinct label-value combinations.  Generous —
#: topics × partitions × stages legitimately reach dozens — but far
#: below what one runaway per-entity label produces in seconds.
MAX_LABEL_SERIES = 256

#: per-metric label DECLARATIONS: the exact label keys each labeled
#: metric may be recorded with.  ALLOWED_LABEL_KEYS bounds the
#: vocabulary; this table bounds each metric's dimensions — a record
#: site using a key missing from its row is registry drift (analysis
#: rule D2), caught before the new dimension multiplies series in
#: production.  Metrics absent from the table take no labels.
DECLARED_METRIC_LABELS = {
    "alert_transitions": ("action",),
    "canary_probes": ("outcome",),
    "chaos_injected": ("fault",),
    "checkpoint_seconds": ("phase",),
    "cluster_shard_epoch": ("shard",),
    "cluster_shard_failovers": ("shard",),
    "compile_cache": ("program", "result"),
    "compile_seconds": ("program", "stage"),
    "consumer_autoresets": ("topic",),
    "conv_grid_steps": ("kernel",),
    "conv_operand_copies": ("kernel",),
    "consumer_lag_records": ("group", "partition", "topic"),
    "consumer_readahead_rows": ("result",),
    "dlq_total": ("source",),
    "flash_block_k": ("kernel",),
    "flash_block_q": ("kernel",),
    "flash_grid_steps": ("kernel",),
    "flash_heads_per_step": ("kernel",),
    "flash_lanes_per_step": ("kernel",),
    "flash_mask_live_area": ("kernel", "kind"),
    "flash_mask_tiles": ("kernel", "kind"),
    "flash_mask_walked_area": ("kernel", "kind"),
    "flash_mask_window": ("kernel", "kind"),
    "flash_operand_copies": ("kernel",),
    "flash_value_lanes_per_step": ("kernel",),
    "gateway_promotions": ("shard",),
    "gateway_standby_lag": ("shard",),
    "isr_size": ("partition", "topic"),
    "kda_intra_kernel": ("direction",),
    "loop_exit_mass": ("kind",),
    "loop_pass_loss": ("kind",),
    "model_layers": ("kind",),
    "moe_add_rows": ("kind",),
    "moe_assignments": ("kind",),
    "moe_expert_form": ("kind",),
    "moe_experts": ("kind",),
    "moe_router_form": ("kind",),
    "moe_router_input": ("kind",),
    "moe_tile_rows": ("kind",),
    "model_offsets_lag": ("component",),
    "model_version": ("component",),
    "online_adaptations": ("action",),
    "online_drifts": ("detector",),
    "prefetch_occupancy": ("loop",),
    "quorum_hwm_lag": ("partition", "topic"),
    "remat_keepable_layers": ("kind",),
    "remat_kept_bytes": ("kind",),
    "remat_kept_layers": ("kind",),
    "replica_lag": ("topic",),
    "rest_request_seconds": ("route",),
    "rest_requests": ("route", "code"),
    "rollouts": ("outcome",),
    "slo_burn_rate": ("slo", "window"),
    "step_seconds": ("loop", "phase"),
    "supervisor_degraded": ("unit",),
    "supervisor_failovers": ("unit",),
    "supervisor_restarts": ("unit",),
    "supervisor_unit_up": ("unit",),
    "supervisor_wedged": ("unit",),
    "watermark_event_ms": ("group", "partition", "stage", "topic"),
    "watermark_lag_seconds": ("group", "partition", "stage", "topic"),
}


def cardinality_violations(registry: "Registry" = None,
                           max_series: int = MAX_LABEL_SERIES):
    """[(metric, problem)] for labels outside the closed vocabulary or
    metrics whose labeled-series count exceeds `max_series` — the
    label-cardinality bound the obs test suite pins."""
    registry = default_registry if registry is None else registry
    out = []
    with registry._lock:
        metrics = dict(registry._metrics)
    for name, m in sorted(metrics.items()):
        if isinstance(m, Histogram):
            with m._lock:
                keysets = list(m._series.keys())
        else:
            with m._lock:
                keysets = list(m._vals.keys())
        label_keys = {k for key in keysets for k, _v in key}
        bad = label_keys - ALLOWED_LABEL_KEYS
        if bad:
            out.append((name, f"label keys outside the closed "
                              f"vocabulary: {sorted(bad)}"))
        if len(keysets) > max_series:
            out.append((name, f"{len(keysets)} labeled series exceeds "
                              f"the {max_series} cardinality bound"))
    return out


def start_http_server(port: int = 9100, registry: Registry = default_registry):
    """Serve /metrics (Prometheus text format) and /healthz (per-stage
    pipeline liveness from the trace collector) on a daemon thread."""
    import http.server
    import json

    def _healthz_body() -> bytes:
        # late import: tracing imports this module for its histograms
        from . import tracing

        stages = tracing.liveness()
        doc = {
            "status": "ok",
            "tracing": tracing.ENABLED,
            # stage → seconds since its newest span: the stalled stage is
            # the one whose age grows while its upstream stays fresh
            "stages": {s: {"last_span_age_s": age}
                       for s, age in stages.items()},
        }
        # supervision + failover state (ISSUE 4): unit states from any
        # live supervisor, the replica loss window, and the fencing
        # epoch.  Late import with a guard: an unsupervised process must
        # not pay for (or crash on) the supervise package.
        try:
            from ..supervise import registry as _sup_registry

            units = _sup_registry.snapshot()
            if units:
                doc["supervisor"] = units
                doc["status"] = "degraded" if any(
                    u.get("state") == "degraded"
                    for u in units.values()) else doc["status"]
        except Exception:  # noqa: BLE001 - health endpoint stays up
            pass
        # model identity (ISSUE 7): which registry version this process
        # runs, per component role, plus the offsets staleness of that
        # model — the rollout/rollback machinery's state surfaced where
        # probes already look
        with model_version._lock:
            mv = dict(model_version._vals)
        if mv:
            with model_offsets_lag._lock:
                lag = dict(model_offsets_lag._vals)
            doc["model"] = {
                dict(k).get("component", ""): {
                    "version": int(v),
                    "offsets_lag": lag.get(k)}
                for k, v in mv.items()}
        with replica_lag._lock:
            lag_vals = dict(replica_lag._vals)
        if lag_vals:
            doc["replica_lag_records"] = {
                dict(k).get("topic", ""): v for k, v in lag_vals.items()}
        # quorum replication (ISSUE 14): ISR width per partition, the
        # under-replicated count, and the un-replicated tail — the
        # acks=all durability state where probes already look
        with isr_size._lock:
            isr_vals = dict(isr_size._vals)
        if isr_vals:
            with quorum_hwm_lag._lock:
                qlag = dict(quorum_hwm_lag._vals)
            doc["replication"] = {
                "under_replicated_partitions": int(
                    under_replicated.value()),
                "isr": {
                    (f"{dict(k).get('topic', '')}"
                     f":{dict(k).get('partition', '')}"): int(v)
                    for k, v in sorted(isr_vals.items())},
                "quorum_hwm_lag_records": {
                    (f"{dict(k).get('topic', '')}"
                     f":{dict(k).get('partition', '')}"): int(v)
                    for k, v in sorted(qlag.items())},
            }
        # event-time watermarks (ISSUE 13): per-stage event-time
        # frontier and its lag vs now — true e2e staleness on the
        # columnar paths where per-record spans cannot exist
        with watermark_event_ms._lock:
            wm_vals = dict(watermark_event_ms._vals)
        if wm_vals:
            now_ms = time.time() * 1000.0  # wallclock-ok: event
            # timestamps live in the wall domain; this is staleness
            # display, not a deadline
            doc["watermarks"] = {}
            for k, v in sorted(wm_vals.items()):
                d = dict(k)
                name = (f"{d.get('stage', '')}:{d.get('topic', '')}"
                        f":{d.get('partition', '')}")
                if d.get("group"):
                    name += f":{d['group']}"
                doc["watermarks"][name] = {
                    "event_time_ms": int(v),
                    "lag_s": round(max(now_ms - v, 0.0) / 1000.0, 3)}
        # consumer lag (ISSUE 13 satellite): group cursor vs partition
        # high-water mark, the federation rollup's input
        with consumer_lag_records._lock:
            clag_vals = dict(consumer_lag_records._vals)
        if clag_vals:
            doc["consumer_lag_records"] = {
                (f"{dict(k).get('group', '')}:{dict(k).get('topic', '')}"
                 f":{dict(k).get('partition', '')}"): v
                for k, v in sorted(clag_vals.items())}
        # SLO burn-rate alerts (ISSUE 17): firing alerts from any live
        # SloEngine in this process, surfaced where probes already
        # look.  Late import with a guard, like the supervisor block —
        # a process without the SLO engine must not pay for it.
        try:
            from . import slo as _slo

            firing = _slo.firing_alerts()
            if firing:
                doc["alerts"] = firing
                doc["status"] = "degraded"
        except Exception:  # noqa: BLE001 - health endpoint stays up
            pass
        epoch = failover_epoch.value()
        if epoch:
            doc["failover_epoch"] = epoch
        return json.dumps(doc, indent=2, sort_keys=True).encode()

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path == "/metrics":
                from . import tracing

                tracing.flush()  # spans land in the histograms per scrape
                body = registry.render().encode()
                ctype = "text/plain; version=0.0.4"
            elif self.path == "/healthz":
                body = _healthz_body()
                ctype = "application/json"
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet
            pass

    from ..supervise.registry import register_thread

    srv = http.server.ThreadingHTTPServer(("0.0.0.0", port), Handler)
    t = register_thread(threading.Thread(
        target=srv.serve_forever, daemon=True,
        name=f"iotml-metrics-{srv.server_address[1]}"))
    t.start()
    # federation auto-join (ISSUE 13): every process that serves
    # /metrics publishes its endpoint — into the in-process registry
    # always, and into the fleet's endpoints manifest when
    # IOTML_OBS_ENDPOINTS names one — so `python -m iotml.obs fleet`
    # discovers the whole fleet without per-process wiring.
    try:
        from . import federate, tracing

        name = tracing.proc_name()
        addr = f"127.0.0.1:{srv.server_address[1]}"
        manifest = federate.manifest_path()
        if manifest:
            federate.publish_endpoint(manifest, name, addr)
        else:
            federate.register_local_endpoint(name, addr)
    except Exception:  # noqa: BLE001 - metrics serving must not die on
        pass           # a manifest hiccup (read-only fs, lock contention)
    return srv
