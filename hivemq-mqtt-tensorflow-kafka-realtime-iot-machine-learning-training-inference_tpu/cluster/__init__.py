"""iotml.cluster — partitioned multi-broker data plane.

One leader serves every partition of a single broker, so the data
plane saturates before the compute does (neither rate is measured on
the chip).  This package shards topic partitions
across N live brokers — the reference's 10-partitions / 3-brokers shape
(PAPER.md L3) — and makes every client partition-aware:

- ``PartitionMap``: (topic, partition) → (broker, epoch); per-shard
  ``supervise.Topology`` cells, so failover moves ONE shard's entry.
- ``ShardBroker``: a ``Broker`` materializing only the partitions its
  shard owns (store dirs included); unowned touches answer
  NOT_LEADER_FOR_PARTITION.
- ``ClusterController``: boots the brokers, provisions topics
  cluster-wide, runs per-shard followers, promotes on death
  (``supervised()`` wires this into iotml.supervise).
- ``ClusterClient``: the Broker duck-type, routed — produce/fetch to
  the owning broker with cached metadata refreshed on NOT_LEADER;
  group/offset APIs pinned to the coordinator broker.
- ``ScorerFleet`` / ``PumpFleet``: partition-parallel scorer members
  and KSQL pumps as consumer groups over the wire group protocol.

Boundary rule (lint R10): outside this package, production code must
not address broker instances directly (``controller.shards`` /
``ShardBroker(...)``) — route through ``ClusterClient`` and the
``PartitionMap`` so the ownership and fencing invariants hold.
"""

from .client import ClusterClient
from .controller import ClusterController, ShardView
from .fleet import PumpFleet, ScorerFleet
from .partition_map import PartitionMap
from .shard import ShardBroker

__all__ = ["ClusterClient", "ClusterController", "PartitionMap",
           "PumpFleet", "ScorerFleet", "ShardBroker", "ShardView",
           "main"]


def main(argv=None) -> int:
    """CLI entry (`python -m iotml.cluster`); see cluster.__main__."""
    from .__main__ import main as _main

    return _main(argv)
