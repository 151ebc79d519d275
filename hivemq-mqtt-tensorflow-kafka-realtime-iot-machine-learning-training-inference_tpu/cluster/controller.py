"""ClusterController — boot, watch, and fail over a partitioned cluster.

One controller owns N shard brokers (``ShardBroker`` + ``KafkaWireServer``
each), the shared ``PartitionMap``, and optionally one ``FollowerReplica``
per shard.  It is the ZooKeeper-controller role of the reference's
3-broker deployment (PAPER.md L3), scoped the way this rebuild scopes
infrastructure: in-process objects speaking the real wire protocol, so
the same code drives tests, chaos drills and the CLI.

Topology on disk (``store_root=``)::

    <store_root>/broker-0/          shard 0's store (its partitions only)
    <store_root>/broker-1/
    ...
    <store_root>/broker-0-replica/  shard 0's follower (replicated=True)

Failover is PER SHARD: a dead shard leader's follower is promoted at a
bumped epoch and only that shard's map entry moves — clients of every
other shard never notice.  Group coordination is pinned to one shard's
live leader; if THAT shard fails over, the promoted follower serves the
mirrored committed offsets and groups re-form against it (membership is
in-memory by design — exactly a Kafka coordinator change).
"""

from __future__ import annotations

import os
import socket
import time
from typing import Dict, List, Optional, Tuple

from ..obs import metrics as obs_metrics
from ..stream.kafka_wire import KafkaWireServer
from ..stream.replica import FollowerReplica
from .partition_map import PartitionMap
from .shard import ShardBroker


def _split(addr: str) -> Tuple[str, int]:
    host, port = addr.rsplit(":", 1)
    return host, int(port)


class ShardView:
    """One node's view of the cluster — what its wire server consults to
    answer Metadata (per-partition leaders), FIND_COORDINATOR (the
    pinned node) and to advertise the broker list."""

    def __init__(self, pmap: PartitionMap, node_id: int):
        self.pmap = pmap
        self.node_id = node_id

    def brokers(self) -> List[Tuple[int, str, int]]:
        return [(i, *_split(addr))
                for i, addr in enumerate(self.pmap.addresses())]

    def leader_node(self, topic: str, partition: int) -> int:
        return self.pmap.shard_for(topic, partition)

    def coordinator(self) -> Tuple[int, str, int]:
        shard, addr = self.pmap.coordinator()
        return (shard, *_split(addr))


class ClusterController:
    """Boot N shard brokers behind one PartitionMap.

    Args:
      brokers: shard count (the reference ran 3).
      store_root: durable mode — each shard mounts
        ``<store_root>/broker-<i>`` (cold restart resumes every shard
        from its own dirs).
      replicated: one FollowerReplica per shard, enabling
        ``fail_shard`` / supervised per-shard failover.
      replica_sync: "thread" starts each follower's background sync
        loop; "manual" leaves stepping to the caller
        (``sync_replicas_once`` — deterministic runners).
      mirror_groups: consumer groups whose committed offsets the
        followers mirror (survive a shard/coordinator failover).
      coordinator_shard: which shard's live leader holds group state.
      base_port: fixed listen ports — shard *i* binds ``base_port + i``
        and its follower ``base_port + n + i`` (deployments expose a
        known port range); default lets the OS pick ephemeral ports.
      advertise_host: the hostname clients should dial (a k8s Service
        name, a LB address) when it differs from the bind ``host`` —
        Metadata, the PartitionMap, and failover publishes all carry
        it.  A wildcard bind (0.0.0.0/::) is never advertised: local
        clients get 127.0.0.1 when no advertise_host is given.
    """

    def __init__(self, brokers: int = 3, host: str = "127.0.0.1",
                 store_root: Optional[str] = None, store_policy=None,
                 replicated: bool = False, replica_sync: str = "thread",
                 mirror_groups: Tuple[str, ...] = (),
                 coordinator_shard: int = 0,
                 base_port: Optional[int] = None,
                 advertise_host: Optional[str] = None,
                 replication_factor: Optional[int] = None,
                 min_isr: int = 2, max_lag_s: float = 0.5):
        if brokers < 1:
            raise ValueError("brokers must be >= 1")
        if replica_sync not in ("thread", "manual"):
            raise ValueError("replica_sync is 'thread' or 'manual'")
        if replication_factor is not None:
            if replication_factor < 2:
                raise ValueError("replication_factor must be >= 2 "
                                 "(1 is the unreplicated default)")
            # quorum mode implies per-shard followers; the legacy
            # single-follower flag becomes redundant
            replicated = False
        self.n = int(brokers)
        self.host = host
        self._store_root = store_root
        self._replica_sync = replica_sync
        self._mirror_groups = tuple(mirror_groups)
        # the address brokers REACH EACH OTHER at (follower sync) vs the
        # one clients are TOLD to dial (Metadata / PartitionMap)
        connect_host = "127.0.0.1" if host in ("0.0.0.0", "::") else host
        self._adv_host = advertise_host or connect_host
        self.brokers: List[ShardBroker] = []
        self.servers: List[KafkaWireServer] = []
        self._killed = [False] * self.n
        for i in range(self.n):
            owns = self._owns_fn(i)
            store_dir = os.path.join(store_root, f"broker-{i}") \
                if store_root else None
            b = ShardBroker(owns, shard_id=i, store_dir=store_dir,
                            store_policy=store_policy)
            self.brokers.append(b)
            self.servers.append(KafkaWireServer(
                b, host=host,
                port=(base_port + i) if base_port else 0))
        addresses = [f"{self._adv_host}:{s.port}" for s in self.servers]
        local_addresses = [f"{connect_host}:{s.port}"
                           for s in self.servers]
        self.pmap = PartitionMap(addresses,
                                 coordinator_shard=coordinator_shard)
        for i, srv in enumerate(self.servers):
            srv.cluster = ShardView(self.pmap, i)
        # durable cold restart: the manifests already re-created each
        # shard's topics during mount — surface them in the map so
        # clients and assignors see the full width immediately
        for b in self.brokers:
            for t in b.topics():
                self.pmap.register_topic(t, b.topic(t).partitions)
        #: per shard: the broker currently SERVING it (the leader until
        #: a failover, then the promoted follower's local broker)
        self.serving: List[ShardBroker] = list(self.brokers)
        self.replicas: List[Optional[FollowerReplica]] = [None] * self.n
        #: quorum mode (ISSUE 14): one ReplicaSet per shard — RF-1
        #: ISR-tracked followers, acks=all at the quorum HWM, consumer
        #: reads bounded by it, ISR-restricted failover, and the
        #: elastic add-broker/drain-broker verbs.
        self.replica_sets: List = [None] * self.n
        self.replication_factor = replication_factor
        self._store_policy = store_policy
        self.reassignments: List = []  # completed/failed move reports
        if replication_factor is not None:
            from ..replication import ReplicaSet
            from ..store.hwm import hwm_file_for

            for i in range(self.n):
                owns = self._owns_fn(i)
                groups = self._mirror_groups \
                    if i == coordinator_shard else ()
                leader_dir = os.path.join(store_root, f"broker-{i}") \
                    if store_root else None

                def factory(i=i, counter=[0]):
                    owns_i = self._owns_fn(i)
                    k = counter[0]
                    counter[0] += 1
                    rep_dir = os.path.join(
                        store_root, f"broker-{i}-replica-{k}") \
                        if store_root else None
                    return ShardBroker(owns_i, shard_id=i,
                                       store_dir=rep_dir,
                                       store_policy=store_policy)

                def port_for(j, i=i):
                    return (base_port + self.n * (1 + j) + i) \
                        if base_port else 0

                rset = ReplicaSet(
                    leader_broker=self.brokers[i],
                    leader_server=self.servers[i],
                    n_followers=replication_factor - 1,
                    min_isr=min_isr, max_lag_s=max_lag_s, host=host,
                    groups=groups, partition_filter=owns,
                    topology=self.pmap.cell(i),
                    follower_local_factory=factory,
                    follower_port_fn=port_for,
                    hwm_file=hwm_file_for(leader_dir),
                    leader_addr=local_addresses[i])
                for rep in rset.followers.values():
                    # a promoted follower must keep answering cluster-
                    # shaped metadata, exactly like the legacy path
                    rep.server.cluster = ShardView(self.pmap, i)
                self.replica_sets[i] = rset
        for srv in self.servers:
            srv.admin = self  # CLUSTER_ADMIN verbs route here
        if replicated:
            for i in range(self.n):
                owns = self._owns_fn(i)
                rep_dir = os.path.join(store_root, f"broker-{i}-replica") \
                    if store_root else None
                local = ShardBroker(owns, shard_id=i, store_dir=rep_dir,
                                    store_policy=store_policy)
                # only the COORDINATOR shard's follower mirrors group
                # offsets: the cluster pins all offset state to the
                # coordinator broker (other brokers answer
                # NOT_COORDINATOR), and a promoted coordinator-follower
                # inherits the whole table with the role
                groups = self._mirror_groups \
                    if i == coordinator_shard else ()
                rep = FollowerReplica(
                    local_addresses[i], groups=groups, host=host,
                    port=(base_port + self.n + i) if base_port else 0,
                    partition_filter=owns, local=local)
                # a promoted follower must keep answering cluster-shaped
                # metadata (per-partition leaders, pinned coordinator)
                rep.server.cluster = ShardView(self.pmap, i)
                self.replicas[i] = rep
        self._compactors: list = []
        self.started = False

    def _owns_fn(self, shard: int):
        n = self.n
        return lambda t, p, _i=shard: p % n == _i

    # ---------------------------------------------------------- lifecycle
    def start(self) -> "ClusterController":
        for srv in self.servers:
            srv.start()
        for rep in self.replicas:
            if rep is None:
                continue
            if self._replica_sync == "thread":
                rep.start()          # sync loop + serving follower
            else:
                rep.server.start()   # serve only; caller steps sync
        for rset in self.replica_sets:
            if rset is not None:
                rset.start(sync=self._replica_sync)
        # durable shards reclaim their compacted topics in the
        # background, each shard compacting only the partitions it leads
        # (run_compaction skips unowned placeholders)
        if self._store_root:
            from ..store import StoreCompactor
            for b in self.brokers:
                if b.store is not None:
                    self._compactors.append(StoreCompactor(
                        b, interval_s=b.store.policy.compact_interval_s,
                    ).start())
        # per-shard scrape labels: every shard's current epoch is a
        # labeled series from boot, so the federated scrape (and the
        # TSDB behind it) can tell shards apart before any failover
        for i in range(self.n):
            obs_metrics.cluster_shard_epoch.set(
                self.pmap.epoch(i), shard=str(i))
        self.started = True
        return self

    def stop(self) -> None:
        for c in self._compactors:
            c.stop()
        self._compactors = []
        for rep in self.replicas:
            if rep is not None:
                try:
                    rep.stop()
                except (OSError, RuntimeError):
                    pass
        for rset in self.replica_sets:
            if rset is not None:
                try:
                    rset.stop()
                except (OSError, RuntimeError):
                    pass
        for i, srv in enumerate(self.servers):
            if not self._killed[i]:
                try:
                    srv.kill()
                except (OSError, RuntimeError):
                    pass
                self._killed[i] = True
        for b in self.brokers:
            try:
                b.close()
            except (OSError, RuntimeError):
                pass
        self.started = False

    def __enter__(self) -> "ClusterController":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------- topics
    def create_topic(self, name: str, partitions: int = 1,
                     **retention) -> None:
        """Provision a topic CLUSTER-WIDE: every shard broker learns the
        full spec (and mounts only its own partitions); the map records
        the width for clients and assignors."""
        for b in self.brokers:
            b.create_topic(name, partitions=partitions, **retention)
        for b in self.serving:
            # after a failover/reassignment the serving broker is a
            # promoted ex-follower that is in neither list above — a
            # topic it never learns answers UNKNOWN_TOPIC forever on
            # its shard (cluster servers do not auto-create)
            if b not in self.brokers:
                b.create_topic(name, partitions=partitions, **retention)
        for rep in self.replicas:
            if rep is not None:
                rep.local.create_topic(name, partitions=partitions,
                                       **retention)
        for rset in self.replica_sets:
            if rset is not None:
                for rep in rset.followers.values():
                    rep.local.create_topic(name, partitions=partitions,
                                           **retention)
        self.pmap.register_topic(name, partitions)

    # ------------------------------------------------------------ clients
    def bootstrap(self) -> str:
        return ",".join(self.pmap.addresses())

    def client(self, **kw):
        """A routing client sharing this controller's live map."""
        from .client import ClusterClient

        return ClusterClient(partition_map=self.pmap, **kw)

    def endpoints(self) -> Dict[str, str]:
        out = {f"broker-{i}": addr
               for i, addr in enumerate(self.pmap.addresses())}
        shard, addr = self.pmap.coordinator()
        out["coordinator"] = f"{addr} (shard {shard})"
        if self._store_root:
            out["store"] = self._store_root
        return out

    # ---------------------------------------------------------- failover
    def sync_replicas_once(self) -> int:
        """Step every live follower one replication round (deterministic
        runners; replica_sync='manual')."""
        copied = 0
        for i, rep in enumerate(self.replicas):
            if rep is not None and not rep.promoted:
                copied += rep.sync_once()
        for rset in self.replica_sets:
            if rset is not None:
                copied += rset.sync_once()
        return copied

    def kill_shard(self, shard: int) -> None:
        """Abruptly kill a shard's LEADER server (drills): established
        connections are severed exactly like a crashed process."""
        if not self._killed[shard]:
            self.servers[shard].kill()
            self._killed[shard] = True

    def fail_shard(self, shard: int) -> str:
        """Promote the shard's follower into its serving leader at a
        bumped epoch and publish ONLY this shard's map entry.  Returns
        the new serving address.  In quorum mode the election is
        ISR-RESTRICTED: only a follower in sync for every partition may
        serve — acked records cannot be lost by construction."""
        rset = self.replica_sets[shard]
        was_coordinator = self.pmap.coordinator()[0] == shard
        if rset is not None:
            self.kill_shard(shard)
            epoch = self.pmap.epoch(shard) + 1
            rid, _bind = rset.promote(epoch)  # ISR-restricted
            addr = f"{self._adv_host}:{rset.server.port}"
            self.pmap.publish(shard, addr, epoch)
            self.serving[shard] = rset.leader
            self.servers[shard] = rset.server
            # the promoted server inherits the full serving surface:
            # admin verbs must survive every failover, not just boot
            rset.server.admin = self
            obs_metrics.cluster_shard_failovers.inc(shard=str(shard))
            obs_metrics.cluster_shard_epoch.set(epoch, shard=str(shard))
            if was_coordinator:
                obs_metrics.cluster_coordinator_moves.inc()
            return addr
        rep = self.replicas[shard]
        if rep is None:
            raise RuntimeError(
                f"shard {shard} has no follower (replicated=False): "
                f"nothing to promote")
        self.kill_shard(shard)
        epoch = self.pmap.epoch(shard) + 1
        rep.promote(epoch)
        # publish the ADVERTISED address (promote() reports the bind
        # address, which may be a wildcard under a deployment)
        addr = f"{self._adv_host}:{rep.port}"
        self.pmap.publish(shard, addr, epoch)
        self.serving[shard] = rep.local
        obs_metrics.cluster_shard_failovers.inc(shard=str(shard))
        obs_metrics.cluster_shard_epoch.set(epoch, shard=str(shard))
        if was_coordinator:
            # the pinned shard moved WITH its follower: clients re-find
            # the coordinator at the promoted address; membership state
            # restarts empty (groups re-form), committed offsets were
            # mirrored by the follower
            obs_metrics.cluster_coordinator_moves.inc()
        return addr

    # --------------------------------------------------------- elasticity
    def _require_rset(self, shard: int):
        if not 0 <= shard < self.n:
            raise ValueError(f"no shard {shard} (0..{self.n - 1})")
        rset = self.replica_sets[shard]
        if rset is None:
            raise RuntimeError(
                "elastic reassignment needs quorum mode: boot the "
                "cluster with replication_factor >= 2")
        return rset

    def add_broker(self, shard: int, store_dir: Optional[str] = None,
                   port: int = 0, catch_up_timeout_s: float = 60.0,
                   retire_old: bool = True) -> dict:
        """Online reassignment: move `shard`'s leadership onto a NEW
        broker node with zero downtime.

        The new node starts as one more follower of the shard: it
        bootstraps the whole segment log over zero-copy RAW_FETCH
        mirroring (batches append verbatim), catches up, earns ISR
        admission, and only THEN is promoted at epoch+1 — the shard's
        Topology cell republishes, clients re-resolve on their next
        reconnect/fence, consumers keep their cursors (offsets are
        identical by the mirror contract), the remaining followers
        re-point through the same cell, and the old leader retires
        (``retire_old``).  Returns the reassignment report
        (state/catch_up_s/move_s)."""
        from ..replication.reassign import (CATCHING_UP, IN_SYNC, MOVED,
                                            RETIRED, ShardReassignment)

        rset = self._require_rset(shard)
        move = ShardReassignment(shard=shard,
                                 old_leader=self.pmap.leader(shard))
        self.reassignments.append(move)
        if store_dir is None and self._store_root:
            store_dir = os.path.join(
                self._store_root,
                f"broker-{shard}-gen{self.pmap.epoch(shard) + 1}")
        # ALWAYS a ShardBroker (store-backed or in-memory): a plain
        # Broker local would materialise unowned partitions and serve
        # them EMPTY after promotion instead of bouncing NOT_LEADER —
        # a stale client would read silence where it must read the
        # re-route signal
        local = ShardBroker(self._owns_fn(shard), shard_id=shard,
                            store_dir=store_dir,
                            store_policy=self._store_policy)
        try:
            rid = rset.add_follower(local=local,
                                    sync=self._replica_sync)
            move.target_rid = rid
            new_rep = rset.followers[rid]
            new_rep.server.cluster = ShardView(self.pmap, shard)
            move.advance(CATCHING_UP)  # the mirror is live; an
            # operator polling `status` watches lag shrink from here
            # catch-up: ISR admission is the bar (lag within the
            # staleness window for EVERY partition), not merely lag==0
            # at one instant
            deadline = time.monotonic() + catch_up_timeout_s
            while time.monotonic() < deadline:
                if self._replica_sync == "manual":
                    rset.sync_once()
                if rid in rset.state.isr_follower_ids():
                    break
                time.sleep(0.0 if self._replica_sync == "manual"
                           else 0.02)
            else:
                raise RuntimeError(
                    f"new replica {rid} did not reach the ISR within "
                    f"{catch_up_timeout_s}s")
            move.records_mirrored = sum(
                new_rep.local.end_offset(t, p)
                for t in new_rep.local.topics()
                for p in range(new_rep.local.topic(t).partitions)
                if self._owns_fn(shard)(t, p))
            move.raw_mirrored = new_rep.raw_mirrored
            move.advance(IN_SYNC)
            self._move_leadership(shard, rid, move,
                                  retire_old=retire_old)
            move.advance(RETIRED if retire_old else MOVED)
        except Exception as e:
            move.fail(f"{type(e).__name__}: {e}")
            raise
        return move.to_dict()

    def drain_broker(self, shard: int,
                     retire_old: bool = True) -> dict:
        """Drain `shard`'s current leader: leadership moves to an
        EXISTING ISR follower (no bootstrap needed — it already holds
        the log), the cell republishes at epoch+1, and the drained
        leader retires.  The capacity-removal half of elasticity."""
        from ..replication.reassign import (IN_SYNC, MOVED, RETIRED,
                                            ShardReassignment)

        rset = self._require_rset(shard)
        move = ShardReassignment(shard=shard,
                                 old_leader=self.pmap.leader(shard))
        self.reassignments.append(move)
        try:
            rid = rset.elect()  # ISR-restricted by construction
            move.target_rid = rid
            move.advance(IN_SYNC)  # already in sync: nothing to copy
            self._move_leadership(shard, rid, move,
                                  retire_old=retire_old)
            move.advance(RETIRED if retire_old else MOVED)
        except Exception as e:
            move.fail(f"{type(e).__name__}: {e}")
            raise
        return move.to_dict()

    def _move_leadership(self, shard: int, rid: int, move,
                         retire_old: bool = True) -> None:
        """The MOVED step both verbs share: promote `rid` at epoch+1,
        publish the cell, update serving state, retire the old leader
        (its server would answer FENCED anyway — its epoch is stale)."""
        from ..replication.reassign import MOVED

        rset = self.replica_sets[shard]
        old_server = self.servers[shard]
        was_coordinator = self.pmap.coordinator()[0] == shard
        epoch = self.pmap.epoch(shard) + 1
        # step down FIRST: from here the old server answers every write
        # with NOT_LEADER, so nothing can land in the retired log
        # during the drain grace — even from unstamped legacy producers
        old_server.retiring = True
        old_broker = self.brokers[shard]
        rset.promote(epoch, rid=rid)
        addr = f"{self._adv_host}:{rset.server.port}"
        self.pmap.publish(shard, addr, epoch)
        self.serving[shard] = rset.leader
        self.servers[shard] = rset.server
        # the promoted broker REPLACES the retired one everywhere the
        # controller fans out (create_topic, stop) — the old one is
        # closed below, and a closed durable broker must not keep
        # receiving manifest writes (or hold its store flock forever)
        self.brokers[shard] = rset.leader
        # admin verbs must survive the move (a cluster whose every
        # shard has moved once must still be reachable for the NEXT
        # add-broker/drain-broker/status)
        rset.server.admin = self
        move.new_leader = addr
        move.epoch = epoch
        move.advance(MOVED)
        obs_metrics.cluster_shard_failovers.inc(shard=str(shard))
        obs_metrics.cluster_shard_epoch.set(epoch, shard=str(shard))
        if was_coordinator:
            obs_metrics.cluster_coordinator_moves.inc()
        if retire_old:
            # graceful retirement: the map already points elsewhere and
            # the old epoch is fenced for writes; severing reads forces
            # the one reconnect consumers already treat as failover.
            # The kill is DEFERRED a beat: the admin verb driving this
            # move may have arrived on the very server being retired
            # (drain-broker against its own shard's leader), and an
            # immediate kill would sever the admin connection before
            # the response flushes.
            import threading

            from ..supervise.registry import register_thread

            # the retired broker's compactor (durable clusters) must
            # stop BEFORE its store closes, or it errors every interval
            # against closed segment logs forever
            old_compactors = [c for c in self._compactors
                              if c.broker is old_broker]
            self._compactors = [c for c in self._compactors
                                if c.broker is not old_broker]

            def _retire(srv=old_server, b=old_broker,
                        compactors=old_compactors):
                time.sleep(0.25)
                try:
                    srv.kill()
                except OSError:
                    pass
                for c in compactors:
                    try:
                        c.stop()
                    except (OSError, RuntimeError):
                        pass
                try:
                    # release the store: open segment fds, the dir
                    # flock, the offsets file — weekly reassignments on
                    # a long-lived process must not leak one mount each
                    b.close()
                except (OSError, RuntimeError):
                    pass

            register_thread(threading.Thread(
                target=_retire, daemon=True,
                name=f"iotml-retire-shard-{shard}")).start()
            self._killed[shard] = True

    def admin_command(self, command: str, args: dict) -> dict:
        """CLUSTER_ADMIN dispatch (the wire server's `admin` hook) —
        what `python -m iotml.cluster add-broker/drain-broker/status`
        drive from another process."""
        if command == "status":
            doc = {"brokers": self.n,
                   "addresses": self.pmap.addresses(),
                   "epochs": [self.pmap.epoch(i)
                              for i in range(self.n)],
                   "replication_factor": self.replication_factor,
                   "reassignments": [m.to_dict()
                                     for m in self.reassignments]}
            if self.replication_factor is not None:
                doc["shards"] = {
                    str(i): self.replica_sets[i].describe()
                    for i in range(self.n)
                    if self.replica_sets[i] is not None}
            return doc
        if command == "add-broker":
            return self.add_broker(
                shard=int(args.get("shard", 0)),
                store_dir=args.get("store_dir"),
                catch_up_timeout_s=float(
                    args.get("catch_up_timeout_s", 60.0)))
        if command == "drain-broker":
            return self.drain_broker(shard=int(args.get("shard", 0)))
        raise ValueError(f"unknown admin command {command!r} "
                         f"(have: status, add-broker, drain-broker)")

    # -------------------------------------------------------- supervision
    def _shard_alive(self, shard: int) -> bool:
        host, port = _split(self.pmap.leader(shard))
        try:
            with socket.create_connection((host, port), timeout=1.0):
                return True
        except OSError:
            return False

    def supervised(self, poll_interval_s: Optional[float] = None,
                   probe_failures: int = 3):
        """A Supervisor probing every shard leader over TCP; a dead
        leader fires per-shard failover (``fail_shard``) — one shard
        moves, the rest of the cluster keeps serving untouched.  The
        caller starts/stops the returned Supervisor."""
        from ..supervise.supervisor import Supervisor

        sup = Supervisor(poll_interval_s=poll_interval_s,
                         name="cluster-supervisor")
        for i in range(self.n):
            if self.replicas[i] is None and self.replica_sets[i] is None:
                sup.add_probed(f"shard-{i}",
                               (lambda i=i: self._shard_alive(i)),
                               probe_failures=probe_failures)
            else:
                # quorum mode fails over through the same hook — the
                # election inside fail_shard is ISR-restricted
                sup.add_probed(
                    f"shard-{i}", (lambda i=i: self._shard_alive(i)),
                    probe_failures=probe_failures,
                    on_death=(lambda _u, i=i: self.fail_shard(i)))
        return sup

    def await_failover(self, shard: int, timeout_s: float = 10.0) -> bool:
        """Block until the shard's map entry moves (a supervised
        failover completed) or timeout."""
        cell = self.pmap.cell(shard)
        start_gen = cell.generation
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if cell.generation != start_gen:
                return True
            time.sleep(0.02)
        return False
