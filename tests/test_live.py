"""Continuous train → artifact pointer → live scorer hot-swap.

The closed loop the reference sequences with run.sh (train Job uploads to
GCS, predict pods download on restart, cardata-v3.py:227-232,255-261):
here the trainer publishes an immutable versioned h5 + atomic pointer per
round and the long-lived scorer swaps weights between super-batches, with
detection quality accounted live against stream labels.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from iotml.data.dataset import SensorBatches
from iotml.gen.simulator import FleetGenerator, FleetScenario
from iotml.models.autoencoder import CAR_AUTOENCODER
from iotml.serve.live import LiveScorer
from iotml.serve.scorer import StreamScorer
from iotml.stream.broker import Broker
from iotml.stream.consumer import StreamConsumer
from iotml.stream.producer import OutputSequence
from iotml.train.artifacts import ArtifactStore
from iotml.train.live import ContinuousTrainer


def _seed(broker, n_records, failure_rate=0.02, partitions=2):
    gen = FleetGenerator(FleetScenario(num_cars=100,
                                       failure_rate=failure_rate))
    return gen.publish(broker, "SENSOR_DATA_S_AVRO",
                       n_ticks=n_records // 100, partitions=partitions)


# ----------------------------------------------------------------- trainer
def test_continuous_trainer_rounds_pointer_and_resume(tmp_path):
    broker = Broker()
    _seed(broker, 3000)
    store = ArtifactStore(str(tmp_path))
    tr = ContinuousTrainer(broker, "SENSOR_DATA_S_AVRO", store,
                           take_batches=10, group="t-live")
    assert tr.available() == 3000
    ran = tr.run(max_rounds=2)
    assert ran == 2 and tr.rounds == 2
    assert tr.records_trained == 2000
    assert np.isfinite(tr.last_loss)
    # immutable per-round blobs + pointer at the newest
    assert store.exists("cardata-live.h5.r1")
    assert store.exists("cardata-live.h5.r2")
    assert store.get_text("cardata-live.h5.latest") == "cardata-live.h5.r2"
    # committed cursor advanced: a NEW trainer resumes past the consumed
    # slice (the `committed` resume contract)
    consumed = 3000 - tr.available()
    assert consumed >= 2000
    tr2 = ContinuousTrainer(broker, "SENSOR_DATA_S_AVRO", store,
                            take_batches=10, group="t-live")
    assert tr2.available() == tr.available()


def test_trainer_waits_for_min_available(tmp_path):
    broker = Broker()
    _seed(broker, 500)  # below the 10x100x1.1 threshold
    store = ArtifactStore(str(tmp_path))
    tr = ContinuousTrainer(broker, "SENSOR_DATA_S_AVRO", store,
                           take_batches=10, group="t-wait")
    done = tr.run(stop=lambda: True)  # one pass through the loop
    assert done == 0 and tr.rounds == 0
    assert store.get_text("cardata-live.h5.latest") is None


# ------------------------------------------------------------ quality math
def test_scorer_quality_confusion_counts():
    broker = Broker()
    n = _seed(broker, 2000, failure_rate=0.05)
    n_true = sum(
        1 for p in range(2) for m in broker.fetch("SENSOR_DATA_S_AVRO", p,
                                                  0, 10_000)
        if b"true" in m.value[-12:])
    assert 0 < n_true < n

    def scorer_with(threshold):
        c = StreamConsumer(broker, [f"SENSOR_DATA_S_AVRO:{p}:0"
                                    for p in range(2)])
        broker.create_topic("preds")
        return StreamScorer(
            CAR_AUTOENCODER,
            CAR_AUTOENCODER.init(__import__("jax").random.PRNGKey(0),
                                 np.zeros((1, 18), np.float32))["params"],
            SensorBatches(c, batch_size=100, keep_labels=True),
            OutputSequence(broker, "preds", partition=0),
            threshold=threshold)

    # threshold below any reconstruction error: every row flagged
    s = scorer_with(-1.0)
    assert s.score_available() == n
    assert s.quality == {"tp": n_true, "fp": n - n_true, "fn": 0, "tn": 0}
    # threshold above any error: nothing flagged
    s = scorer_with(1e9)
    s.score_available()
    assert s.quality == {"tp": 0, "fp": 0, "fn": n_true, "tn": n - n_true}


# ---------------------------------------------------------------- hot swap
def test_set_params_mid_drain_no_drop_no_reorder():
    """Swap weights BETWEEN super-batches of one drain: every input row
    still produces exactly one prediction, in order, and rows after the
    swap reflect the new weights."""
    import jax

    broker = Broker()
    n = _seed(broker, 2000, failure_rate=0.0, partitions=1)
    broker.create_topic("preds", partitions=1)
    c = StreamConsumer(broker, ["SENSOR_DATA_S_AVRO:0:0"])
    params_a = CAR_AUTOENCODER.init(jax.random.PRNGKey(0),
                                    np.zeros((1, 18), np.float32))["params"]
    params_b = jax.tree.map(np.zeros_like, params_a)  # output == bias == 0

    class SwappingScorer(StreamScorer):
        max_super_batches = 4  # force multiple super-batches per drain

        def _score_super_batch(self, bs, base):
            super()._score_super_batch(bs, base)
            if self.scored >= 800 and self.params is params_a:
                self.set_params(params_b)

    s = SwappingScorer(CAR_AUTOENCODER, params_a,
                       SensorBatches(c, batch_size=100),
                       OutputSequence(broker, "preds", partition=0))
    assert s.score_available() == n
    msgs = broker.fetch("preds", 0, 0, 10_000)
    assert len(msgs) == n  # nothing dropped, nothing duplicated
    # the tail (scored with the zero params) is the all-zeros row; the
    # head (params_a) is not
    assert not msgs[0].value.startswith(b"[0. 0. 0. 0.")
    assert msgs[-1].value.startswith(b"[0. 0. 0. 0.")
    # order preserved: rows flip from params_a output to params_b output
    # exactly once (no interleaving across the swap point)
    zeros = [m.value.startswith(b"[0. 0. 0. 0.") for m in msgs]
    flips = sum(1 for i in range(1, n) if zeros[i] != zeros[i - 1])
    assert flips == 1


def test_bounded_drain_resumes_without_loss():
    """max_rows truncation must suspend the drain, not abandon it: every
    buffered row is scored by later calls (no loss, contiguous output)
    and offsets commit only once the drain completes."""
    import jax

    broker = Broker()
    n = _seed(broker, 5000, failure_rate=0.0, partitions=3)
    broker.create_topic("preds", partitions=1)
    c = StreamConsumer(broker, [f"SENSOR_DATA_S_AVRO:{p}:0"
                                for p in range(3)], group="bounded")
    params = CAR_AUTOENCODER.init(jax.random.PRNGKey(0),
                                  np.zeros((1, 18), np.float32))["params"]
    s = StreamScorer(CAR_AUTOENCODER, params,
                     SensorBatches(c, batch_size=100),
                     OutputSequence(broker, "preds", partition=0))
    # small super-batches so the max_rows bound actually bites (the bound
    # is checked per super-batch, default 128x100 rows)
    s.max_super_batches = 4
    total = 0
    calls = 0
    while True:
        got = s.score_available(max_rows=700)
        if not got:
            break
        total += got
        calls += 1
        if s._resume is not None:
            # truncated: the cursor must NOT be committed yet
            assert broker.committed("bounded", "SENSOR_DATA_S_AVRO", 0) \
                is None or total == n
    assert calls > 1          # the bound actually triggered
    assert total == n         # nothing lost across truncations
    msgs = broker.fetch("preds", 0, 0, 10_000)
    assert len(msgs) == n     # one prediction per input row, no gaps
    # drain completed → offsets committed at the stream end
    committed = sum(broker.committed("bounded", "SENSOR_DATA_S_AVRO", p)
                    for p in range(3))
    assert committed == n


def test_live_scorer_hotswap_from_store(tmp_path):
    broker = Broker()
    _seed(broker, 3000, failure_rate=0.05)
    broker.create_topic("model-predictions", partitions=1)
    store = ArtifactStore(str(tmp_path))
    tr = ContinuousTrainer(broker, "SENSOR_DATA_S_AVRO", store,
                           take_batches=10, group="t-hs")
    sc = LiveScorer(broker, "SENSOR_DATA_S_AVRO", "model-predictions",
                    store, threshold=5.0, group="s-hs")
    with pytest.raises(TimeoutError):
        sc.wait_for_model(timeout_s=0.2)  # nothing published yet
    tr.run(max_rounds=1)
    assert sc.wait_for_model() == "cardata-live.h5.r1"
    assert sc.model_updates == 1
    n = sc.scorer.score_available()
    assert n == 3000  # scores everything incl. failure rows
    q = sc.scorer.quality
    assert sum(q.values()) == 3000
    tr.run(max_rounds=1)
    assert sc.maybe_swap() and sc.model_updates == 2
    assert sc._current_artifact == "cardata-live.h5.r2"
    assert not sc.maybe_swap()  # pointer unchanged → no re-download


# ------------------------------------------------------------------- CLI
def test_live_cli_train_and_score_over_wire(tmp_path):
    """Both services as real OS processes over the Kafka wire — the
    deploy manifests' pod separation (model-training.yaml /
    model-predictions.yaml) driven end to end."""
    from iotml.stream.kafka_wire import KafkaWireServer

    broker = Broker()
    _seed(broker, 4000, failure_rate=0.05)
    broker.create_topic("model-predictions", partitions=1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo)

    with KafkaWireServer(broker) as srv:
        addr = f"127.0.0.1:{srv.port}"
        root = str(tmp_path)
        train = subprocess.Popen(
            [sys.executable, "-m", "iotml.cli.live", "train", addr,
             "SENSOR_DATA_S_AVRO", root, "--take-batches", "10",
             "--stats", "--max-seconds", "60"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=repo, text=True)
        score = subprocess.Popen(
            [sys.executable, "-m", "iotml.cli.live", "score", addr,
             "SENSOR_DATA_S_AVRO", "model-predictions", root,
             "--stats", "--max-seconds", "60"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=repo, text=True)
        try:
            # trainer: 3 full rounds available (4000 records, 1000/round)
            deadline = time.time() + 90
            while time.time() < deadline and \
                    store_rounds(tmp_path) < 3:
                time.sleep(0.2)
            assert store_rounds(tmp_path) >= 3
            # scorer: predictions flowing
            while time.time() < deadline and \
                    broker.end_offset("model-predictions", 0) < 4000:
                time.sleep(0.2)
            assert broker.end_offset("model-predictions", 0) == 4000
            for proc in (train, score):
                proc.stdin.write("STOP\n")
                proc.stdin.flush()
            t_out, _ = train.communicate(timeout=30)
            s_out, _ = score.communicate(timeout=30)
        finally:
            for proc in (train, score):
                if proc.poll() is None:
                    proc.kill()
        assert train.returncode == 0, t_out
        assert score.returncode == 0, s_out
        # stats lines parse and carry the closed-loop evidence
        t_stats = [json.loads(l) for l in t_out.splitlines()
                   if l.startswith("{")]
        s_stats = [json.loads(l) for l in s_out.splitlines()
                   if l.startswith("{")]
        assert t_stats and t_stats[-1]["round"] >= 3
        assert s_stats
        last = s_stats[-1]
        assert last["scored"] == 4000
        assert sum(last["quality"].values()) == 4000
        assert last["model_updates"] >= 1
        assert last["artifact"].startswith("cardata-live.h5.r")
        # predictions carry the threshold verdict (reference payload +
        # |verdict|mse suffix)
        m = broker.fetch("model-predictions", 0, 0, 1)[0]
        assert m.value.startswith(b"[") and b"|" in m.value


def store_rounds(tmp_path) -> int:
    try:
        with open(os.path.join(str(tmp_path),
                               "cardata-live.h5.latest")) as fh:
            return int(fh.read().rsplit(".r", 1)[1])
    except (FileNotFoundError, ValueError, IndexError):
        return 0
