"""iotml.mlops — model lifecycle: async checkpoints, versioned
registry, zero-downtime rollout, rollback-on-regression.

The reference's model lifecycle is "save to GCS, redeploy the pod"
(PAPER L5/L6) — a restart-shaped gap in an otherwise self-healing
system.  This package closes it:

- ``ModelRegistry``: monotonic versions, manifest-as-commit-marker
  publication (``iotml.store`` atomic-write discipline), offsets +
  metrics + lineage per version, torn-publish recovery, channel
  pointers with promote/rollback history;
- ``AsyncCheckpointer``: device→host snapshot on the train thread,
  serialize+fsync on a supervised writer thread behind a bounded
  drop-oldest queue, so the train thread never waits on a write
  (the stall: not measured on the chip); group offsets commit only
  AFTER the checkpoint is durable, so model state and stream position
  always resume consistently;
- ``RegistryWatcher``: scorers hot-swap to a newly promoted version
  between super-batches with zero dropped/double-scored records,
  single scorer or the PR 6 partition-parallel fleet alike;
- ``ABRollout`` + ``RolloutGate``: two versions score the same stream
  into compared prediction topics; the r04 detection-quality protocol
  auto-promotes or auto-rolls-back.

Proof lives in ``iotml.mlops.drill`` (live drills) and the seeded
chaos scenarios ``trainer-crash-mid-checkpoint`` /
``rollout-regression-rollback``.  Lint rule R11 keeps registry writes
inside this package.
"""

from .checkpoint import AsyncCheckpointer, restore_trainer
from .registry import Manifest, ModelRegistry
from .rollout import ABRollout, RegistryWatcher, RolloutGate, scorer_quality

__all__ = ["AsyncCheckpointer", "restore_trainer", "Manifest",
           "ModelRegistry", "ABRollout", "RegistryWatcher", "RolloutGate",
           "scorer_quality"]
