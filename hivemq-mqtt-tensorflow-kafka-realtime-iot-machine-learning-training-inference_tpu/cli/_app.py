"""Shared scaffolding for the reference-contract streaming CLIs.

Both reference ML apps (`cardata-v3.py`, LSTM `cardata-v2.py`) are the same
program with a different model: positional args, a train mode that fits on
a stream slice and uploads the checkpoint, and a predict mode that restores
it and writes ordered predictions back.  `run_streaming_app` is that
program once; `cli.cardata` and `cli.lstm` supply the model and knobs.

The typed config layer (`iotml.config`) fronts the positional contract:
`--section.field=...` flags and `IOTML_*` env vars override an app's
defaults (epochs, batch size, topics, SASL credentials for the wire
client), and positionals pass through untouched — so the reference's K8s
manifests work verbatim while everything stays configurable without code
edits.
"""

from __future__ import annotations

import os
import sys
import tempfile
from typing import Callable, Optional


def _broker_for(servers: str, topic: str, cfg) -> object:
    """Resolve <servers>: 'emulator[:n]' seeds an in-process broker with
    generated fleet data; 'host:port[,...]' speaks the Kafka wire protocol
    (stream.kafka_wire) to a real cluster or the framework's wire server."""
    from ..stream.broker import Broker

    if servers.startswith("emulator"):
        n = int(servers.split(":", 1)[1]) if ":" in servers else 30_000
        from ..gen.simulator import FleetGenerator, FleetScenario

        broker = Broker()
        gen = FleetGenerator(FleetScenario(num_cars=100, failure_rate=0.01))
        gen.publish(broker, topic, n_ticks=max(1, n // 100))
        broker.create_topic("model-predictions")
        return broker
    from ..stream.kafka_wire import KafkaWireBroker

    return KafkaWireBroker(servers,
                           sasl_username=cfg.broker.sasl_username or None,
                           sasl_password=cfg.broker.sasl_password or None)


def run_streaming_app(argv, *, prog: str, usage: str, make_model: Callable,
                      group: str, epochs: int, batch_size: int,
                      take_batches: int, predict_skip: int,
                      predict_take: int, supervised: bool = False,
                      window: Optional[int] = None,
                      h5_interop: bool = False) -> int:
    from ..config import load_config

    argv = list(sys.argv[1:] if argv is None else argv)
    try:
        cfg, argv = load_config(argv)
    except ValueError as e:
        print(f"config error: {e}")
        return 1
    print("Options: ", argv)
    if len(argv) != 7:
        print(usage)
        return 1
    servers, topic, offset, result_topic, mode, model_file, artifact_root = argv
    mode = mode.strip().lower()
    if mode not in ("train", "predict"):
        print(f"Mode is invalid, must be either 'train' or 'predict': {mode}")
        return 1
    if model_file.endswith(".h5") and not h5_interop:
        # fail BEFORE training, not after: the Keras-h5 exporter maps the
        # 4-Dense autoencoder stack only — an LSTM run ending in a failed
        # export would lose the whole training run
        print(f"{prog}: '.h5' model files (Keras interop) are supported "
              f"for the autoencoder CLI only; use a plain name for an "
              f"orbax checkpoint")
        return 1
    offset = offset.strip().lower()
    if offset != "committed":
        offset = int(offset)

    applied = getattr(cfg, "applied", set())
    if "train.epochs" in applied:
        epochs = cfg.train.epochs
    if "train.batch_size" in applied:
        batch_size = cfg.train.batch_size
    if "train.take_batches" in applied:
        take_batches = cfg.train.take_batches

    from ..data.dataset import SensorBatches
    from ..stream.consumer import StreamConsumer
    from ..train.artifacts import ArtifactStore
    from ..train.checkpoint import CheckpointManager
    from ..train.loop import Trainer
    from ..utils.device import claim_device, device_text

    # restart-per-job is this CLI's deployment shape (a K8s Job per fit, a
    # restarted predict pod): every start after the first loads its
    # programs from the persistent compile cache claim_device() places
    print("Device: ", device_text(claim_device()))
    broker = _broker_for(servers, topic, cfg)
    store = ArtifactStore(artifact_root)

    # This host's partition share: on an indexed multi-host Job each pod
    # consumes a disjoint subset (reference: Kafka partitions × pods,
    # SURVEY §2.7); single-host consumes every partition.  `committed`
    # resumes from the group's offset cursor instead of an absolute offset.
    from ..parallel.distributed import assign_partitions

    try:
        n_parts = broker.topic(topic).partitions
    except KeyError:
        n_parts = 1  # topic not created yet: subscribe partition 0
    n_hosts = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    host_id = int(os.environ.get("JAX_PROCESS_ID", "0"))
    # an empty share is legitimate (more hosts than partitions): that host
    # trains on nothing rather than duplicating partition 0 under the same
    # group (which would make shards overlap and offset commits clobber)
    parts = assign_partitions(n_parts, n_hosts, host_id)
    if offset == "committed":
        consumer = StreamConsumer.from_committed(broker, topic, parts,
                                                 group=group)
    else:
        consumer = StreamConsumer(broker,
                                  [f"{topic}:{p}:{offset}" for p in parts],
                                  group=group)
    if not parts:
        print(f"host {host_id}/{n_hosts}: no partition share of "
              f"{n_parts}-partition topic {topic}; idle")
    model = make_model()

    # an explicitly-configured mesh (--mesh.* flags / config file, or the
    # IOTML_MESH_DATA process knob) means the operator reserved multiple
    # chips: train sharded over a ('data', 'model') mesh instead of
    # single-device
    use_mesh = bool({"mesh.data", "mesh.model"} & applied)
    # IOTML_MESH_DATA moved into the process-knob family (ISSUE 15,
    # data/pipeline.py non_config) and no longer reaches cfg through the
    # env resolver — but the deploy manifests' contract (that env var =
    # data-axis chip count, deploy/model-training*.yaml) must keep
    # holding, so the knob feeds the same decision here
    from ..data.pipeline import mesh_data as _mesh_data_knob

    knob = _mesh_data_knob()
    if knob >= 2 and "mesh.data" not in applied:
        # >= 2, matching the knob's contract ("1 behaves like 0") and
        # cli.live's threshold — one env var, one meaning everywhere
        cfg.mesh.data = knob
        use_mesh = True
    if use_mesh:
        import jax

        from ..parallel.data_parallel import ShardedTrainer
        from ..parallel.mesh import auto_mesh

        model_par = max(cfg.mesh.model, 1)
        n_dev = len(jax.devices()) if cfg.mesh.data in (-1, 0) \
            else cfg.mesh.data * model_par
        mesh = auto_mesh(n_dev, model_parallel=model_par)
        print(f"mesh: {dict(mesh.shape)} over {n_dev} devices")
        trainer = ShardedTrainer(model, mesh, supervised=supervised,
                                 learning_rate=cfg.train.learning_rate)
    else:
        trainer = Trainer(model, supervised=supervised,
                          learning_rate=cfg.train.learning_rate)

    if mode == "train":
        batches = SensorBatches(consumer, batch_size=batch_size,
                                take=take_batches, window=window,
                                only_normal=not supervised and
                                cfg.train.only_normal)
        history = trainer.fit(batches, epochs=epochs) if use_mesh \
            else trainer.fit_compiled(batches, epochs=epochs)
        # empty stream: fit_compiled returns an empty history; the step-loop
        # fits return placeholder losses but never initialize state — either
        # way there is nothing worth checkpointing
        if not history["loss"] or trainer.state is None:
            print("No records in this host's partition share; nothing "
                  "trained, nothing stored")
            return 0
        print(f"Training complete, final loss {history['loss'][-1]:.6f}")
        # restart-per-job: every job is a start, so say where its seconds
        # went (the spans of obs/tracing.py's `start` loop beside JAX's
        # own compile counters) — what an operator reads after a deploy
        from ..obs import tracing
        from ..utils.device import compile_report

        started = tracing.start_report()
        if started:
            print(tracing.start_line(started, compile_report()))
        # unique dir: concurrent jobs on one host must not trample each other
        ckpt_dir = tempfile.mkdtemp(prefix=f"iotml_{prog}_ckpt_")
        if model_file.endswith(".h5"):
            # reference artifact-format parity: its CLI moves Keras h5
            # blobs through the store (cardata-v3.py:227-231, model file
            # arg "model1.h5") — an .h5 name keeps that contract, so a
            # consumer still on the reference stack can load models
            # trained here
            import jax
            import numpy as _np

            from ..models.h5_export import autoencoder_params_to_h5

            local_h5 = os.path.join(ckpt_dir, "model.h5")
            autoencoder_params_to_h5(
                jax.tree.map(_np.asarray, trainer.state.params), local_h5)
            store.upload(local_h5, model_file)
        else:
            mgr = CheckpointManager(ckpt_dir)
            path = mgr.save(trainer.state, cursors=consumer.positions())
            store.upload_tree(path, model_file)
        # commit AFTER the checkpoint is durable: the group cursor is the
        # resume point the '<offset>=committed' rerun contract promises
        consumer.commit()
        print("Model stored successfully", model_file)
        return 0

    # predict
    print("Downloading model", model_file)
    local = os.path.join(tempfile.mkdtemp(prefix=f"iotml_{prog}_restore_"),
                         "ckpt")
    if model_file.endswith(".h5"):
        from ..models.h5_import import autoencoder_params_from_h5

        store.download(model_file, local)
        payload = {"params": autoencoder_params_from_h5(local)}
    else:
        store.download_tree(model_file, local)
        import orbax.checkpoint as ocp

        payload = ocp.PyTreeCheckpointer().restore(local)
    print("Loading model")
    from ..serve.scorer import StreamScorer
    from ..stream.producer import OutputSequence

    batches = SensorBatches(consumer, batch_size=batch_size,
                            window=window, skip=predict_skip,
                            take=predict_take)
    out = OutputSequence(broker, result_topic, partition=0)
    scorer = StreamScorer(model, payload["params"], batches, out)
    n = scorer.score_available()
    print(f"predict complete: {n} records → {result_topic} "
          f"(end offset {broker.end_offset(result_topic, 0)})")
    return 0
