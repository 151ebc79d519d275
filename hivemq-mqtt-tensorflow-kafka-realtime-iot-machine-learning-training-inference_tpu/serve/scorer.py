"""Continuous stream scorer with ordered write-back.

The reference's inference side is a K8s Deployment that scores a fixed slice
(batch 100 × take 100), exits, and is restarted by Kubernetes forever — its
own README calls this out as "not an ideal architecture … Python batch style"
(python-scripts/README.md:24).  The TPU-native replacement is what that
README wishes for: one long-lived process with a jit-compiled scoring step,
polling the stream, writing predictions back through the ordered
OutputSequence, and committing offsets so a crash resumes where it stopped.

Output format parity: each prediction row is serialized with
`np.array2string` exactly like the reference callback (cardata-v3.py:247), so
downstream consumers of the predictions topic see identical payloads.
"""

from __future__ import annotations

import itertools
import time
from typing import Optional

import jax
import numpy as np

from ..chaos import faults as chaos
from ..obs import metrics as obs_metrics
from ..obs import tracing, watermark
from ..data.dataset import SensorBatches
from ..stream.producer import OutputSequence
from ..train.loop import make_eval_step
from ..utils.backoff import ExpBackoff
from .fastfmt import format_rows


def format_prediction(row: np.ndarray) -> str:
    """Reference-parity payload: np.array2string of the output vector."""
    return np.array2string(row)


#: log-spaced reconstruction-error bucket edges for live quality histograms
#: (64 buckets spanning 1e-4..1e2 + one overflow bucket)
ERR_BUCKETS = np.geomspace(1e-4, 1e2, 65)


def hist_auc(anom: np.ndarray, normal: np.ndarray) -> Optional[float]:
    """ROC AUC from per-label score histograms (midpoint tie handling).

    Buckets ascend in score; AUC = P(score_anom > score_normal) with ties
    counted half — the rank-sum estimator over binned errors."""
    a_tot, n_tot = int(anom.sum()), int(normal.sum())
    if not a_tot or not n_tot:
        return None
    n_below = np.concatenate([[0], np.cumsum(normal)[:-1]])
    wins = float(np.sum(anom * (n_below + normal / 2.0)))
    return wins / (a_tot * n_tot)


class StreamScorer:
    """Score an input stream continuously; write ordered predictions back.

    Args:
      model/params: flax module + params (trained, h5-imported, or orbax).
      batches: SensorBatches over the input consumer (only_normal=False —
        the predict path scores everything, cardata-v3.py:264-268).
      out: OutputSequence onto the predictions topic.
      threshold: optional reconstruction-error threshold; when set, rows also
        get an anomaly verdict appended (the notebook's fixed-threshold
        protocol, threshold 5).

    Delivery semantics: input is at-least-once (offsets commit once per
    drain, after every polled row is scored — a mid-drain commit would
    record offsets for rows still inside the batcher's poll/filter buffers
    and lose them on crash-resume).  The flip side is that predictions are
    flushed to the output topic per super-batch, so a crash mid-drain
    re-emits every super-batch of that drain on resume: the output topic is
    at-least-once too, with a duplicate window of up to one drain.
    Duplicates are benign here — each prediction row is keyed by its global
    index through OutputSequence.setitem, so idempotent downstream
    consumers (and the reference's, which tolerates pod-restart re-scoring,
    python-scripts/README.md:24) deduplicate on key.
    """

    #: Upper bound on batches stacked into one device dispatch.  A drain of
    #: an arbitrarily deep backlog (e.g. scoring a retained topic from offset
    #: 0) proceeds in fixed-size super-batches so host+device memory stays
    #: bounded, while a typical drain (≤ this many batches) keeps the
    #: single-dispatch win.  128 batches × 100 rows × 18 features is under
    #: 1 MB on device — the bound exists for pathological backlogs, and at
    #: 64 the reference-shaped 10k-row drain was paying TWO device round
    #: trips instead of one.
    max_super_batches = 128

    def __init__(self, model, params, batches: SensorBatches,
                 out: OutputSequence, threshold: Optional[float] = None,
                 carhealth=None, carhealth_topic: Optional[str] = None,
                 verdict_mask=None, feature_store=None):
        self.model = model
        self.params = params
        self.batches = batches
        self.out = out
        self.threshold = threshold
        #: optional twin.TwinFeatureStore: per-car HISTORICAL features
        #: (rolling-window aggregates from the digital twin) are
        #: concatenated onto each live row before scoring, so the model
        #: sees [F live + K twin] inputs — its input_dim must match.
        #: Requires batches built with keep_keys=True (the join key is
        #: the car's message key); rows without a key — or cars with no
        #: twin yet — join the zero vector, the cold-start null.
        #: Batched 2-D rows only: windowed/LSTM rows have no single
        #: per-row car identity to join on.
        self.feature_store = feature_store
        #: optional boolean [F] mask restricting the per-row error MEAN
        #: (verdicts, quality histograms, car mean-EMA) to a feature
        #: subset.  Full-normalization deployments pass the PARITY mask:
        #: the threshold protocol was calibrated on the reference's
        #: feature set, and the four extra full-norm features (inherently
        #: noisy) dilute the per-record verdict signal (measured: best f1
        #: 0.50 unmasked vs 0.60 masked at the same model) — while the
        #: per-feature detector heads still see all 18.
        self.verdict_mask = (np.asarray(verdict_mask, bool)
                             if verdict_mask is not None else None)
        #: optional per-car detector (serve.carhealth.CarHealthDetector):
        #: fed each scored batch's (keys, per-row errors) when the batch
        #: source keeps keys; alert transitions publish to
        #: `carhealth_topic` on the output broker (the digital-twin feed)
        self.carhealth = carhealth
        self.carhealth_topic = carhealth_topic
        self._eval = make_eval_step(model)
        self.scored = 0
        #: calls of score_available so far: the `round` of its spans
        self.drains = 0
        #: registry version of the loaded weights (None = not registry-
        #: managed); stamped by set_params(version=) / RegistryWatcher
        self.model_version: Optional[int] = None
        #: suspended (iterator, index_base) of a max_rows-truncated drain
        self._resume = None
        #: confusion counts of the threshold verdicts against stream labels
        #: (batches built with keep_labels=True): live detection quality —
        #: the notebook's offline protocol (threshold / confusion matrix,
        #: streaming notebook cells 21-26) running against the predictions
        #: actually being written.  Padding rows are excluded; rows without
        #: a label (empty string) count as negatives, matching the training
        #: filter's reading of the label field.
        self.quality = {"tp": 0, "fp": 0, "fn": 0, "tn": 0}
        #: per-label reconstruction-error histograms (log buckets): enough
        #: to recover threshold-free quality (AUC, any operating point)
        #: from a live run without retaining per-row errors
        self.err_hist = {"true": np.zeros(len(ERR_BUCKETS) + 1, np.int64),
                         "false": np.zeros(len(ERR_BUCKETS) + 1, np.int64)}

    def set_params(self, params, version: Optional[int] = None) -> None:
        """Hot-swap model weights; takes effect at the next super-batch.

        The handoff the reference performs by restarting its predict pod
        with a fresh GCS download (cardata-v3.py:255-261) — a long-lived
        scorer swaps in place instead.  The jit eval traces params as
        arguments, so same-shaped params reuse the compiled program, and
        the swap cannot drop or reorder output: the OutputSequence index
        stream is untouched.  ``version`` (a registry id) stamps the
        scorer's model identity for /healthz + the version gauge."""
        self.params = params
        if version is not None:
            self.model_version = version
            obs_metrics.model_version.set(version, component="scorer")
        if self.carhealth is not None and \
                hasattr(self.carhealth, "notify_model_swap"):
            # new weights shift every car's error together: the detector
            # recalibrates per-update through the fold transient
            self.carhealth.notify_model_swap()

    def warm_buckets(self, row_shape: tuple) -> None:
        """Compile the eval for every super-batch bucket before the first
        drain.  Drain sizes follow arrival timing, so without this the
        set of compiled shapes — and when each compile stalls the stream
        — differs from run to run; with it a long-lived scorer compiles
        (or loads from the persistent cache) exactly the same programs
        every start, and never mid-stream."""
        out = None
        for s in sorted({self._bucket(s)
                         for s in range(1, self.max_super_batches + 1)}):
            out = self._eval(self.params, np.zeros(
                (s * self.batches.batch_size,) + tuple(row_shape),
                np.float32))
        jax.block_until_ready(out)

    @staticmethod
    def _bucket(n_batches: int) -> int:
        """Batch count padded to a power of two: drains vary in size and
        jit would otherwise recompile the eval for every distinct count."""
        return 1 << max(0, (n_batches - 1).bit_length())

    def score_available(self, max_rows: Optional[int] = None) -> int:
        """Drain whatever is currently in the stream; returns rows scored.

        Each super-batch is ONE device dispatch: up to max_super_batches
        batches are stacked and scored as a single [S*B, F] eval instead of
        a dispatch per 100-row batch — per-dispatch link latency dominates a
        model this small, so a typical drain costs one round trip instead of
        one per batch, and a deep backlog costs ceil(S/cap) round trips with
        bounded memory.

        `max_rows` bounds ONE call: when producers outpace the scorer, an
        unbounded drain never returns and the caller's control loop
        (hot-swap polling, stop flags) starves.  A bounded call that still
        had data keeps its iterator SUSPENDED — the batcher's buffered
        rows stay queued, the next call resumes exactly where it stopped,
        and offsets only commit once the drain truly reaches the stream
        end (committing at the truncation point would persist the cursor
        past polled-but-unscored rows and silently drop them)."""
        self.drains += 1
        with tracing.phase("score", "drain", round=self.drains):
            return self._drain(max_rows)

    def _drain(self, max_rows: Optional[int]) -> int:
        """`score_available` inside its `iotml.score.drain` span: each
        super-batch is a `host_pipeline`, a `device_compute` and a
        `writeback` phase of it; the closing commit is its self time."""
        start = self.scored
        if self._resume is not None:
            # continue the truncated drain: same iterator, same index base
            it, it_base = self._resume
            self._resume = None
        else:
            # batch.first_index restarts per iterator; rebase globally
            it, it_base = iter(self.batches), self.scored
        while True:
            chaos.point("scorer.poll")  # injected stall/crash lands at a
            # super-batch boundary: exactly where a real broker death
            # surfaces, upstream of the commit (redelivery covers it)
            with tracing.phase("score", "host_pipeline"):
                # the host leg: poll + columnar decode + batching (the
                # batcher's iterator does all three)
                bs = list(itertools.islice(it, self.max_super_batches))
            if not bs:
                break
            self._score_super_batch(bs, it_base)
            if max_rows is not None and self.scored - start >= max_rows:
                self._resume = (it, it_base)
                break
        if self._resume is None:
            # offsets commit once per COMPLETED drain, AFTER every polled
            # row was scored: the consumer cursor runs ahead of the scored
            # rows inside the batcher's poll/filter buffers, so an earlier
            # commit would record offsets for rows not yet scored and lose
            # them on crash-resume.  A crash mid-drain therefore redoes
            # the drain from the previous commit (at-least-once), never
            # skips data; under sustained overload (every call truncated)
            # commits simply wait for the first completed drain.
            self.batches.consumer.commit()
            # completed drain: everything consumed has been SCORED, so
            # the accumulated event-time ranges become the ingest→score
            # watermark (ISSUE 13) — true e2e staleness on the columnar
            # paths where per-record spans cannot exist
            take = getattr(self.batches.consumer, "take_event_time", None)
            if take is not None:
                watermark.observe_taken(
                    "score", take(),
                    group=getattr(self.batches.consumer, "group", ""))
            if tracing.ENABLED:
                # completed drain: every decoded record has been scored,
                # so close each trace with its e2e (ingest → score) span.
                # A truncated drain keeps traces pending with its
                # suspended iterator — rows still inside the batcher's
                # buffers must not report a score they haven't had.
                for ctx in self.batches.take_traces():
                    ctx.close("score")
        return self.scored - start

    def _score_super_batch(self, bs, base: int) -> None:
        if self.feature_store is not None and bs[0].x.ndim == 2:
            # the feature-store join: twin features ride beside the live
            # row INTO the model, so reconstruction error covers both —
            # a car whose live reading contradicts its own history
            # scores anomalous even when the reading is fleet-normal
            xs = np.stack([
                np.concatenate(
                    [b.x, self.feature_store.matrix(b.keys, b.x.shape[0])],
                    axis=1).astype(b.x.dtype)
                for b in bs])               # [S, B, F + K]
        else:
            xs = np.stack([b.x for b in bs])  # [S, B, ...] (F, or T×F)
        S, B = xs.shape[:2]
        row_shape = xs.shape[2:]
        S_pad = self._bucket(S)
        if S_pad != S:
            xs_in = np.concatenate(
                [xs, np.zeros((S_pad - S, B) + row_shape, xs.dtype)])
        else:
            xs_in = xs
        with tracing.phase("score", "device_compute"):
            preds = jax.device_get(self._eval(
                self.params, xs_in.reshape((S_pad * B,) + row_shape)))
        preds = preds.reshape((S_pad, B) + preds.shape[1:])[:S]
        with tracing.phase("score", "writeback"):
            self._write_back(bs, base, xs, preds)
            # flush per super-batch: indices are monotone so the ordered
            # flush is preserved and host memory stays bounded by one
            # super-batch of formatted predictions
            self.out.flush()

    def _write_back(self, bs, base: int, xs, preds) -> None:
        """A super-batch's host tail (the `writeback` phase, with the
        flush that follows it): errors, the per-car detector, formatting
        and the ordered write into the output sequence."""
        S, B = xs.shape[:2]
        # per-row reconstruction error over every non-batch axis
        err_axes = tuple(range(2, preds.ndim))
        sq = np.square(preds - xs)
        if self.verdict_mask is not None and sq.ndim == 3:
            mask = self.verdict_mask
            if mask.shape[0] < sq.shape[2]:
                # feature-store join widened the rows: the verdict mask
                # was calibrated on the LIVE features, so the joined
                # twin columns stay out of the verdict mean
                mask = np.concatenate(
                    [mask, np.zeros(sq.shape[2] - mask.shape[0], bool)])
            errs = sq[:, :, mask].mean(axis=2)  # [S, B]
        else:
            errs = np.mean(sq, axis=err_axes)  # [S, B]
        # per-FEATURE errors for the detector's feature heads (2-D rows
        # only: windowed rows have no single per-feature identity)
        want_ferrs = (self.carhealth is not None
                      and getattr(self.carhealth, "feature_heads", False)
                      and sq.ndim == 3)
        # one vectorized formatting pass over every valid row in the
        # super-batch (byte-identical to np.array2string per row — the
        # serve bottleneck, see fastfmt)
        flat = preds.reshape((S * B,) + preds.shape[2:])
        valid_rows = np.concatenate(
            [flat[k * B: k * B + b.n_valid] for k, b in enumerate(bs)])
        if valid_rows.ndim == 2:
            msgs = format_rows(valid_rows)
        else:  # windowed/LSTM rows are [T, F]: 2-D payloads, numpy formats
            msgs = [format_prediction(r) for r in valid_rows]
        mi = 0
        for k, b in enumerate(bs):
            err = errs[k]
            if self.threshold is not None and b.labels is not None \
                    and b.n_valid:
                flag = err[: b.n_valid] > self.threshold
                truth = b.labels[: b.n_valid] == "true"
                self.quality["tp"] += int(np.sum(flag & truth))
                self.quality["fp"] += int(np.sum(flag & ~truth))
                self.quality["fn"] += int(np.sum(~flag & truth))
                self.quality["tn"] += int(np.sum(~flag & ~truth))
                buckets = np.searchsorted(ERR_BUCKETS, err[: b.n_valid])
                for lab, sel in (("true", truth), ("false", ~truth)):
                    if np.any(sel):
                        self.err_hist[lab] += np.bincount(
                            buckets[sel], minlength=len(ERR_BUCKETS) + 1)
            if self.carhealth is not None and b.keys is not None \
                    and b.n_valid:
                # per-feature heads see the LIVE columns only: joined
                # twin features are model input, not car sensors
                n_live = b.x.shape[1] if b.x.ndim == 2 else None
                trans = self.carhealth.update(
                    b.keys[: b.n_valid], err[: b.n_valid],
                    ferrs=sq[k][: b.n_valid, : n_live]
                    if want_ferrs else None,
                    fvals=xs[k][: b.n_valid, : n_live]
                    if want_ferrs else None)
                if trans and self.carhealth_topic is not None:
                    self.carhealth.publish_transitions(
                        self.out.broker, self.carhealth_topic, trans)
            for i in range(b.n_valid):
                idx = base + b.first_index + i
                msg = msgs[mi]
                mi += 1
                if self.threshold is not None:
                    verdict = "anomaly" if err[i] > self.threshold else "normal"
                    msg = f"{msg}|{verdict}|{err[i]:.6f}"
                self.out.setitem(idx, msg)
            self.scored += b.n_valid
            obs_metrics.records_scored.inc(b.n_valid)
            if b.n_valid:
                obs_metrics.reconstruction_mse.set(float(np.mean(err[: b.n_valid])))

    def run_forever(self, poll_interval_s: float = 0.2,
                    max_rounds: Optional[int] = None):
        """The long-lived loop the reference's restart-the-pod pattern
        approximates.  max_rounds bounds it for tests.

        Failover: the wire client does NOT auto-retry non-idempotent
        produce/commit after a reconnect (kafka_wire._request) — a broker
        death mid-drain surfaces ConnectionError here, already
        reconnected to the next bootstrap server.  This loop is the
        opt-in redelivery point the contract requires: rewind the input
        to the committed offsets and re-drain.  Output duplicates are
        benign — predictions are keyed by global index (see class
        docstring), the same at-least-once window a crash-restart has."""
        rounds = 0
        # bounded exponential backoff with jitter for the rewind loop: a
        # leader that STAYS dead turned the fixed poll_interval_s retry
        # into a busy-spin of doomed reconnect+redrain attempts (chaos
        # blackout scenarios exercise exactly this); healthy idle polling
        # keeps the flat cadence
        base = max(poll_interval_s, 0.01)  # poll_interval_s=0 is a legal
        # busy-poll for tests; the FAILURE path still must not busy-spin
        backoff = ExpBackoff(base_s=base, cap_s=max(2.0, base))
        while max_rounds is None or rounds < max_rounds:
            try:
                n = self.score_available()
            except ConnectionError:
                self.batches.consumer.rewind_to_committed()
                obs_metrics.scorer_rewinds.inc()
                rounds += 1
                time.sleep(backoff.next_delay())
                continue
            backoff.reset()
            rounds += 1
            if n == 0:
                time.sleep(poll_interval_s)
