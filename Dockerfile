# iotml:latest — the image every manifest under deploy/ runs.
#
# The reference ships per-app images built FROM tensorflow/tensorflow with
# the tfio-kafka wheel dropped in (reference
# python-scripts/AUTOENCODER-TensorFlow-IO-Kafka/Dockerfile:1-8).  Here one
# image carries the whole framework: the Python package, the native C++
# stream engine built from source inside the image, and the test suite (so
# `docker run iotml:latest -m pytest tests/ -q` is a self-contained smoke
# test of the artifact that will run in the cluster).
#
# Accelerator flavor is a build arg:
#   docker build -t iotml:latest .                     # CPU (dev/CI)
#   docker build --build-arg JAX_FLAVOR=tpu -t iotml:latest .   # TPU pods
FROM python:3.12-slim

ARG JAX_FLAVOR=cpu

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app

# dependency layer first: rebuilds of the code don't re-resolve wheels
COPY requirements.txt .
RUN pip install --no-cache-dir -r requirements.txt \
    && if [ "$JAX_FLAVOR" = "tpu" ]; then \
         pip install --no-cache-dir "jax[tpu]" \
           -f https://storage.googleapis.com/jax-releases/libtpu_releases.html; \
       else \
         pip install --no-cache-dir "jax[cpu]"; \
       fi

COPY hivemq-mqtt-tensorflow-kafka-realtime-iot-machine-learning-training-inference_tpu \
     ./hivemq-mqtt-tensorflow-kafka-realtime-iot-machine-learning-training-inference_tpu
COPY tests ./tests
COPY deploy ./deploy
COPY __graft_entry__.py ./

# short import alias (mirrors the repo's `iotml` symlink)
RUN ln -s hivemq-mqtt-tensorflow-kafka-realtime-iot-machine-learning-training-inference_tpu iotml \
    # native stream engine: fused fetch+decode + Avro columnar decoder
    && make -C iotml/cpp \
    && python -c "import iotml, iotml.stream.native"

ENV PYTHONPATH=/app
ENTRYPOINT ["python"]
# default: the whole platform in one process (deploy/platform.yaml overrides
# args; training/predict Jobs override command+args entirely)
CMD ["-m", "iotml.cli.up", "--host=0.0.0.0"]
