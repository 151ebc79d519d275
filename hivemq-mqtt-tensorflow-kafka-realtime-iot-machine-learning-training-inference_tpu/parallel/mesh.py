"""Device mesh construction.

The reference's parallelism is systems-level — Kafka partitions × consumer
groups, scalable predict Deployments (SURVEY §2.7) — with single-process
training.  The TPU rebuild makes tensor-level parallelism first-class: one
`jax.sharding.Mesh` whose `data` axis carries the Kafka-partition →
device-shard assignment (gradient all-reduce rides ICI) and whose `model`
axis is the tensor-parallel hook for wider models.

`auto_mesh` gives a sane default on any device count; tests run it on the
8-virtual-CPU-device trick (conftest), the driver dry-runs it at arbitrary N.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = 1
    for s in shape:
        n *= s
    if n != len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} needs {n} devices, "
                         f"have {len(devices)}")
    import numpy as np

    return Mesh(np.asarray(devices).reshape(shape), axis_names)


def auto_mesh(n_devices: Optional[int] = None, model_parallel: int = 1) -> Mesh:
    """('data', 'model') mesh over the first n devices; model axis optional."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                     devices[:n])


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over 'data'; replicate the rest."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
