"""iotml — a TPU-native streaming-ML framework for IoT predictive maintenance.

Re-implements the capabilities of the reference system
`hivemq-mqtt-tensorflow-kafka-realtime-iot-machine-learning-training-inference`
(simulated car fleet → MQTT → Kafka → KSQL → TensorFlow train/score loop)
as an idiomatic JAX/XLA/Flax/Pallas stack:

- ``core``       typed record schemas + pure-jax normalization
- ``ops``        Avro wire codecs, windowing, Pallas kernels
- ``stream``     broker emulator, consumers/producers, CSV replay, MQTT bridge
- ``streamproc`` KSQL-equivalent stream transforms (convert / rekey / windowed aggs)
- ``data``       unbounded stream → fixed-shape device batches (static shapes for XLA)
- ``models``     flax.linen model zoo (autoencoder, LSTM seq2seq, MNIST) + h5 import
- ``train``      jit train loops, optax optimizers, orbax checkpoints + offset cursors
- ``serve``      long-lived jit scorer with ordered write-back
- ``parallel``   device mesh, data/tensor sharding, multi-host init
- ``gen``        car-fleet load generator (scenario-driven, failure modes)
- ``obs``        metrics registry (Prometheus text) + TensorBoard + generated Grafana dashboards
- ``cli``        reference-compatible entry points (cardata, lstm, creditcard, mnist_smoke)
- ``mqtt``       MQTT 5 broker/wire/bridge + scenario-driven device fleet
- ``connect``    connector runtime (file source, document sink, Avro data lake)
- ``evaluate``   anomaly eval: ROC/AUC, precision-recall, threshold confusion
- ``config``     one typed config tree (defaults < file < env < flags)
- ``utils``      host buffers, misc

The package directory on disk is
``hivemq-mqtt-tensorflow-kafka-realtime-iot-machine-learning-training-inference_tpu``;
``iotml`` is an import alias (symlink).
"""

__version__ = "0.1.0"

# first of all, before anything heavy is imported: from here on an
# import of 0.1 s or more is an `iotml.start.import` span (obs/tracing.py)
from .obs import tracing as _tracing

_tracing.time_imports()

from . import core  # noqa: F401,E402
