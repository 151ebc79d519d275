"""One host's role in a multi-host training rehearsal.

Run as `python -m iotml.parallel.multihost_worker <coordinator> <nprocs>
<pid> <servers> <topic> <n_partitions> [steps]` — what each pod of
`deploy/model-training-multihost.yaml` does, scaled down to a 2-process
CPU rehearsal (SURVEY §2.7: `jax.distributed` over DCN for the process
group; per-host stream consumers for the data plane):

1. join the process group via `parallel.distributed.initialize`;
2. consume ONLY this host's partition share (`assign_partitions`) from
   the Kafka wire server over TCP — the reference's consumer-group model
   with a deterministic assignment;
3. drive a `ShardedTrainer` whose mesh spans every process's devices —
   each host contributes its local rows, `put_global` assembles the
   global batch, and the compiled gradient all-reduce crosses processes;
4. assert the loss DECREASES and print a `MULTIHOST ... ok` line the
   spawner greps.

The spawner (tests/test_multihost.py, or dryrun_multichip — on by
default, IOTML_DRYRUN_MULTIHOST=0 opts out) must set JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=<local devices> in the
child environment BEFORE this module imports jax.
"""

from __future__ import annotations

import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) < 6:
        print("usage: multihost_worker <coordinator> <nprocs> <pid> "
              "<servers> <topic> <n_partitions> [steps]")
        return 1
    coordinator, nprocs, pid, servers, topic, n_parts = argv[:6]
    nprocs, pid, n_parts = int(nprocs), int(pid), int(n_parts)
    steps = int(argv[6]) if len(argv) > 6 else 6

    import jax

    jax.config.update("jax_platforms", "cpu")

    from iotml.parallel.distributed import (assign_partitions, consumer_specs,
                                            initialize)

    assert initialize(coordinator, nprocs, pid), "initialize() was a no-op"
    assert jax.process_count() == nprocs, jax.process_count()

    import numpy as np

    from iotml.data.dataset import SensorBatches
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.parallel.data_parallel import ShardedTrainer
    from iotml.parallel.mesh import make_mesh
    from iotml.stream.consumer import StreamConsumer
    from iotml.stream.kafka_wire import KafkaWireBroker

    # the DCN data plane: this host consumes only its partition share
    parts = assign_partitions(n_parts, nprocs, pid)
    client = KafkaWireBroker(servers)
    consumer = StreamConsumer(client, consumer_specs(topic, parts),
                              group=f"multihost-{pid}")
    # pad_tail=False DROPS the ragged tail, so every batch is exactly
    # batch_size rows — the fixed local shape the multi-host put_batch
    # contract requires on every host
    batches = list(SensorBatches(consumer, batch_size=32, only_normal=True,
                                 pad_tail=False))
    assert batches, f"host {pid}: no data in partitions {parts}"

    # the ICI/collective plane: one mesh over every process's devices
    mesh = make_mesh((jax.device_count(),), ("data",),
                     devices=jax.devices())
    trainer = ShardedTrainer(CAR_AUTOENCODER, mesh)

    import time

    losses = []
    t0 = None
    rows = 0
    for i in range(steps):
        b = batches[i % len(batches)]
        if i == 1:
            # step 0 compiles: the timed window (per-leg records/sec,
            # ISSUE 15) covers warm steps only
            t0 = time.perf_counter()
            rows = 0
        m = trainer.step(b.x, b.x, b.mask)
        # the loss is replicated but not fully addressable from one
        # process: read the local replica
        losses.append(float(np.asarray(m["loss"].addressable_data(0))))
        rows += b.n_valid
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
    dt = (time.perf_counter() - t0) if t0 is not None else 0.0
    rate = rows / dt if dt > 0 else 0.0
    print(f"MULTIHOST pid={pid}/{nprocs} devices={jax.device_count()} "
          f"partitions={parts} loss {losses[0]:.6f}->{losses[-1]:.6f} "
          f"rate={rate:.1f} rows={rows} ok", flush=True)
    return 0


def spawn_rehearsal(steps: int = 6, timeout: float = 420.0,
                    n_partitions: int = 4, n_procs: int = 2):
    """Spawn the n-process rehearsal and return (procs, outs).

    Shared by tests/test_multihost.py and __graft_entry__'s
    IOTML_DRYRUN_MULTIHOST leg so the two cannot drift: seeds a broker,
    serves it over the Kafka wire, pins the workers to CPU devices with
    no inherited pod topology, spawns them, and ALWAYS kills stragglers
    — a worker that dies early must not leave its peers pinned in the
    coordinator barrier."""
    import os
    import socket
    import subprocess

    from iotml.gen.simulator import FleetGenerator, FleetScenario
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    broker = Broker()
    gen = FleetGenerator(FleetScenario(num_cars=40, failure_rate=0.02))
    gen.publish(broker, "SENSOR", n_ticks=60, partitions=n_partitions)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "PYTHONPATH": repo})
    # no inherited pod topology: the rehearsal names its own coordinator
    for k in list(env):
        if k.startswith(("JAX_COORDINATOR", "JAX_NUM_PROCESSES",
                         "JAX_PROCESS_ID")):
            env.pop(k)

    with KafkaWireServer(broker) as srv:
        procs = [subprocess.Popen(
            [sys.executable, "-m", "iotml.parallel.multihost_worker",
             coord, str(n_procs), str(pid), f"127.0.0.1:{srv.port}",
             "SENSOR", str(n_partitions), str(steps)],
            env=env, cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
            for pid in range(n_procs)]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=timeout)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    return procs, outs


if __name__ == "__main__":
    raise SystemExit(main())
