"""Long-lived train / score services over the Kafka wire — the continuous
twin of the batch CLIs.

The reference runs training as a restarted Job and prediction as a
restarted Deployment (`run.sh:16-91`, python-scripts/README.md:24-26 calls
the restart loop out as "not an ideal architecture").  These entry points
are the long-lived form, one process each, matching the deploy manifests'
pod separation (`deploy/model-training.yaml`, `deploy/model-predictions.yaml`):

    python -m iotml.cli.live train  <servers> <topic> <artifact_root>
    python -m iotml.cli.live score  <servers> <topic> <result_topic> <artifact_root>

Both connect over the real Kafka wire protocol (native C++ client when
built, pure-Python fallback).  `--stats` prints one JSON line per round /
drain on stdout for an orchestrating process; both exit cleanly when stdin
closes or receives a STOP line (the supervisor contract), or after
`--max-seconds`.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _wire_broker(servers: str, sasl: str):
    user, pw = (sasl.split(":", 1) if sasl else (None, None))
    try:
        from ..stream.native_kafka import NativeKafkaBroker

        return NativeKafkaBroker(servers, sasl_username=user,
                                 sasl_password=pw)
    except Exception as e:
        # The fallback exists for boxes without the C++ engine; anything
        # else (bad SASL, unreachable host) will fail again in the pure
        # client with less context — say why we fell back.
        print(json.dumps({"event": "native_kafka_fallback",
                          "error": f"{type(e).__name__}: {e}"}),
              file=sys.stderr, flush=True)
        from ..stream.kafka_wire import KafkaWireBroker

        return KafkaWireBroker(servers, sasl_username=user, sasl_password=pw)


def _stopper(max_seconds: float):
    """stop() that trips on stdin EOF / a STOP line / the deadline."""
    ev = threading.Event()

    def watch_stdin():
        for line in sys.stdin:
            if line.strip() == "STOP":
                break
        ev.set()

    from ..supervise.registry import register_thread

    register_thread(threading.Thread(target=watch_stdin, daemon=True,
                                     name="iotml-stdin-watch")).start()
    deadline = time.time() + max_seconds if max_seconds else None
    return lambda: ev.is_set() or (deadline is not None
                                   and time.time() > deadline)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m iotml.cli.live",
        description="continuous train/score services over the Kafka wire")
    sub = ap.add_subparsers(dest="cmd", required=True)

    tr = sub.add_parser("train", help="continuous trainer → artifacts")
    tr.add_argument("servers")
    tr.add_argument("topic")
    tr.add_argument("artifact_root")
    tr.add_argument("--model-name", default="cardata-live.h5")
    tr.add_argument("--group", default="cardata-live-train")
    tr.add_argument("--take-batches", type=int, default=20)
    tr.add_argument("--batch-size", type=int, default=100)
    tr.add_argument("--epochs-per-round", type=int, default=1)
    tr.add_argument("--checkpoint-interval-s", type=float, default=0.5,
                    help="async-checkpoint cadence with --registry: "
                         "snapshots arriving faster are coalesced "
                         "(newest wins); 0 archives every round")
    tr.add_argument("--backfill-since-ms", type=int, default=None,
                    help="cold start: begin from the first retained "
                         "record at/after this timestamp (durable-store "
                         "replay API) instead of offset 0; partitions "
                         "with a committed cursor still resume from it")

    sc = sub.add_parser("score", help="continuous scorer with hot-swap")
    sc.add_argument("servers")
    sc.add_argument("topic")
    sc.add_argument("result_topic")
    sc.add_argument("artifact_root")
    sc.add_argument("--model-name", default="cardata-live.h5")
    sc.add_argument("--group", default="cardata-live-score")
    sc.add_argument("--threshold", type=float, default=5.0)
    sc.add_argument("--car-threshold", default="0.38",
                    help="per-car EMA alert level, or 'auto' "
                         "(fleet-quantile calibration; needs a stable "
                         "model)")
    sc.add_argument("--car-feature-heads", action="store_true",
                    help="per-feature error + value-drift heads on the "
                         "car detector (weak failure modes; pair with "
                         "--normalize full — see serve/carhealth.py)")
    sc.add_argument("--batch-size", type=int, default=100)
    sc.add_argument("--wait-model-seconds", type=float, default=120.0)

    for p in (tr, sc):
        p.add_argument("--registry", default=None, metavar="DIR",
                       help="versioned model registry root (iotml.mlops): "
                            "train publishes async checkpoints stamped "
                            "with stream offsets (crash-consistent "
                            "resume, no training stall); score follows "
                            "the registry's serving channel — promote/"
                            "rollback flips hot-swap the scorer")
        p.add_argument("--normalize", choices=("parity", "full"),
                       default="parity",
                       help="parity = the reference's normalization "
                            "(its four TODO fields zeroed); full = all "
                            "18 fields live (detection-grade — battery "
                            "faults are invisible under parity).  Train "
                            "and score must match.")
        p.add_argument("--sasl", default=None, metavar="USER:PASS")
        p.add_argument("--stats", action="store_true",
                       help="print one JSON line per round/drain")
        p.add_argument("--max-seconds", type=float, default=0.0,
                       help="exit after this long (0 = until stdin closes)")
        p.add_argument("--wait-topic-seconds", type=float, default=60.0,
                       help="wait this long for the input topic to appear")
        p.add_argument("--prefetch-depth", type=int, default=None,
                       help="host→device prefetch queue depth (sets "
                            "IOTML_PREFETCH_DEPTH; default 2)")
        p.add_argument("--decode-ring-buffers", type=int, default=None,
                       help="reusable columnar decode buffers (sets "
                            "IOTML_DECODE_RING_BUFFERS; default 4)")
        p.add_argument("--raw-batch-bytes", type=int, default=None,
                       help="max bytes per raw frame fetch (sets "
                            "IOTML_RAW_BATCH_BYTES; default 1 MiB)")
        p.add_argument("--raw-produce", default=None,
                       choices=("auto", "on", "off"),
                       help="zero-copy produce plane (sets "
                            "IOTML_RAW_PRODUCE; default auto)")
        p.add_argument("--produce-batch-bytes", type=int, default=None,
                       help="max frame bytes per RAW_PRODUCE request "
                            "(sets IOTML_PRODUCE_BATCH_BYTES; default "
                            "1 MiB)")
        p.add_argument("--metrics-port", type=int, default=0,
                       help="serve /metrics + /healthz on this port "
                            "(0 = off); with IOTML_OBS_ENDPOINTS set "
                            "the endpoint auto-joins the fleet's "
                            "federation manifest (iotml.obs fleet)")
        p.add_argument("--mesh-data", type=int, default=None,
                       help="multi-chip streaming training: data-axis "
                            "size of the device mesh (sets "
                            "IOTML_MESH_DATA; 0/absent = single-chip). "
                            "Each device consumes its own partition "
                            "subset and the jitted step all-reduces "
                            "gradients over the mesh (train only)")
        p.add_argument("--device-normalize", default=None,
                       choices=("0", "1"),
                       help="fold the affine normalization into the "
                            "jitted step so the host ships raw columns "
                            "(sets IOTML_DEVICE_NORMALIZE; needs "
                            "--mesh-data >= 2)")

    args = ap.parse_args(argv)
    from ..data.pipeline import device_normalize as _dev_norm_knob
    from ..data.pipeline import mesh_data as _mesh_knob
    from ..data.pipeline import set_knobs

    try:
        set_knobs(prefetch_depth=args.prefetch_depth,
                  decode_ring_buffers=args.decode_ring_buffers,
                  raw_batch_bytes=args.raw_batch_bytes,
                  produce_batch_bytes=args.produce_batch_bytes,
                  raw_produce=args.raw_produce,
                  mesh_data=args.mesh_data,
                  device_normalize=None if args.device_normalize is None
                  else args.device_normalize == "1")
        mesh_devices = _mesh_knob()
        dev_norm = _dev_norm_knob()
    except ValueError as e:
        ap.error(str(e))
    if dev_norm and mesh_devices < 2:
        ap.error("IOTML_DEVICE_NORMALIZE=1 needs IOTML_MESH_DATA >= 2 "
                 "(the affine fold lives in the sharded step)")
    # this process owns a device: place the compile cache, start the
    # backend now (a missing accelerator fails here, before any topic or
    # artifact is touched) and keep what it got for the banner and stats
    from ..stream import native
    from ..utils.device import claim_device, device_text

    device = dict(claim_device(), native_engine=native.available())
    if args.metrics_port:
        from ..obs.metrics import start_http_server

        start_http_server(args.metrics_port)
    broker = _wire_broker(args.servers, args.sasl)
    stop = _stopper(args.max_seconds)

    # the input topic may be created by an upstream stage (the KSQL CSAS
    # materializes SENSOR_DATA_S_AVRO only once records flow): wait for it
    deadline = time.time() + args.wait_topic_seconds
    while True:
        try:
            refresh = getattr(broker, "refresh_topic", None)
            if (refresh(args.topic) if refresh is not None
                    else broker.topic(args.topic)) is not None:
                break
        except KeyError:
            pass
        if stop() or time.time() > deadline:
            print(f"topic {args.topic} not available after "
                  f"{args.wait_topic_seconds}s")
            return 1
        time.sleep(0.1)

    # the first line of a stats stream names the device it ran on
    first_line = {"device": device}

    def emit(stats: dict) -> None:
        if args.stats:
            print(json.dumps({**stats, **first_line}), flush=True)
            first_line.clear()

    from ..core.normalize import CAR_NORMALIZER, FULL_NORMALIZER
    from ..train.artifacts import ArtifactStore

    normalizer = (FULL_NORMALIZER if args.normalize == "full"
                  else CAR_NORMALIZER)
    store = ArtifactStore(args.artifact_root)
    registry = None
    checkpointer = None
    if args.registry:
        from ..config import load_config
        from ..mlops import ModelRegistry

        registry = ModelRegistry(args.registry)
        if args.cmd == "train":
            from ..mlops.checkpoint import AsyncCheckpointer

            registry.recover()  # sweep torn publishes from a prior kill
            # env-resolved mlops policy (IOTML_MLOPS_*): queue depth,
            # promote-on-publish vs gate-owned, optimizer archival,
            # retention — the CLI flag only owns the cadence
            mcfg = load_config([])[0].mlops
            checkpointer = AsyncCheckpointer(
                registry, queue_depth=mcfg.queue_depth,
                save_opt_state=mcfg.save_opt_state,
                auto_promote=mcfg.auto_promote,
                keep_versions=mcfg.keep_versions,
                min_interval_s=args.checkpoint_interval_s)
    if args.cmd == "train":
        from ..train.live import ContinuousTrainer

        mesh = None
        if mesh_devices >= 2:
            # the multi-chip path (IOTML_MESH_DATA): one data-axis mesh
            # over the first N local devices, partition-parallel feeds,
            # sharded jitted step — ARCHITECTURE §24
            import jax

            from ..parallel.mesh import make_mesh

            if mesh_devices > len(jax.devices()):
                ap.error(f"IOTML_MESH_DATA={mesh_devices} but only "
                         f"{len(jax.devices())} local devices")
            mesh = make_mesh((mesh_devices,), ("data",),
                             devices=jax.devices()[:mesh_devices])
        svc = ContinuousTrainer(broker, args.topic, store,
                                model_name=args.model_name, group=args.group,
                                batch_size=args.batch_size,
                                take_batches=args.take_batches,
                                epochs_per_round=args.epochs_per_round,
                                normalizer=normalizer,
                                backfill_since_ms=args.backfill_since_ms,
                                registry=registry,
                                checkpointer=checkpointer,
                                mesh=mesh, device_normalize=dev_norm)
        print(f"live train: {args.topic} rounds of "
              f"{args.take_batches}x{args.batch_size} -> "
              f"{args.artifact_root}/{args.model_name}"
              + (f" + registry {args.registry}" if registry else "")
              + (f" [mesh data={mesh_devices}"
                 f"{', device-normalize' if dev_norm else ''}]"
                 if mesh is not None else "")
              + f" on {device_text(device)}",
              flush=True)
        rounds = svc.run(stop=stop, on_round=emit)
        svc.close()  # flush pending checkpoints, stop the writer
        print(f"live train done: {rounds} rounds, "
              f"{svc.records_trained} records, last loss {svc.last_loss}",
              flush=True)
    else:
        from ..serve.live import LiveScorer

        car_th = args.car_threshold if args.car_threshold == "auto" \
            else float(args.car_threshold)
        svc = LiveScorer(broker, args.topic, args.result_topic, store,
                         model_name=args.model_name, group=args.group,
                         threshold=args.threshold,
                         car_threshold=car_th,
                         car_feature_heads=args.car_feature_heads,
                         batch_size=args.batch_size,
                         normalizer=normalizer, registry=registry)
        artifact = svc.wait_for_model(args.wait_model_seconds)
        svc.scorer.warm_buckets((svc.model.input_dim,))
        print(f"live score: model {artifact} loaded; "
              f"{args.topic} -> {args.result_topic} on "
              f"{device_text(device)}", flush=True)
        n = svc.run(stop=stop, on_drain=emit)
        q = svc.scorer.quality
        print(f"live score done: {n} rows, {svc.model_updates} model "
              f"updates, quality {q}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
