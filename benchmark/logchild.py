"""Process B of every cell: the log, its fleet and, in an open-loop
cell, the paced producer and the watcher.  Pinned to the CPU by its
parent (`JAX_PLATFORMS=cpu`); speaks JSON lines on stdin/stdout.

The log is mounted the way `cli.up --durable` mounts it (cli/up.py:
`Broker(store_dir=, store_policy=StorePolicy.from_config(cfg.store))`
behind `KafkaWireServer`, the background compactor beside it), with the
store's default policy.  Records enter it as they do under `cli.up`,
from a thread of the process that hosts it (there the KSQL pump, here
the paced producer), and leave it over the Kafka wire.  The fleet is the
benchmark's own (`fleet.py`).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import fleet  # noqa: E402


#: the producer wakes this often and publishes what has come due
PACE_S = 0.005


def warp(t: np.ndarray, burst) -> np.ndarray:
    """Schedule seconds at the mean rate -> real seconds under bursts
    (`every_s`, `for_s`, `factor`: `for_s` of every `every_s` run at
    `factor` times the quiet rate, the mean rate unchanged)."""
    if not burst:
        return t
    every, dur, k = burst["every_s"], burst["for_s"], burst["factor"]
    share = k * dur / (every - dur + k * dur)  # of a period's records
    period, frac = np.divmod(t / every, 1.0)
    inside = np.interp(frac, [0.0, share, 1.0], [0.0, dur, every])
    return period * every + inside


class Stream:
    """Records of `ticks` ticks, encoded, in publication order."""

    def __init__(self, spec: dict, cars: int, ticks: int, burst=None):
        dep = spec["deployment"]
        f = fleet.Fleet(spec["seed"], cars, spec["failure_rate"],
                        dep["interval_s"])
        keys = [fleet.car_key(i) for i in range(cars)]
        self.keys, self.values, due = [], [], []
        for k in range(ticks):
            raw, failing, car = f.step()
            self.values += fleet.encode(raw, failing)
            self.keys += [keys[c] for c in car.tolist()]
            due.append(f.due_s(k))
        self.due = warp(np.concatenate(due), burst)

    def entries(self, lo: int, hi: int, t0_ms: float) -> list:
        ts = (t0_ms + self.due[lo:hi] * 1000.0).astype(np.int64).tolist()
        return list(zip(self.keys[lo:hi], self.values[lo:hi], ts))


class Live:
    """Open loop: publish each record when it is due, never waiting for
    the scorer; sample the predictions topic's end every 10 ms."""

    def __init__(self, broker, stream: Stream, topic: str, out_topic: str):
        self.broker, self.stream = broker, stream
        self.topic, self.out_topic = topic, out_topic
        self.stop_producing = threading.Event()
        self.stop_watching = threading.Event()
        self.late = []       # per publish: (first index, count, sent at)
        self.samples = []    # (time, predictions end offset)
        self.sent = 0
        self.marks = {}

    def start(self) -> float:
        self.base = self.broker.end_offset(self.out_topic, 0)
        self.t0 = time.time() + 0.05
        self.threads = [threading.Thread(target=self._produce, daemon=True),
                        threading.Thread(target=self._watch, daemon=True)]
        for t in self.threads:
            t.start()
        return self.t0

    def _produce(self) -> None:
        due = self.stream.due + self.t0
        i, n_all = 0, len(due)
        while not self.stop_producing.is_set() and i < n_all:
            n = int(np.searchsorted(due, time.time(), "right"))
            if n > i:
                self.broker.produce_many(
                    self.topic, self.stream.entries(i, n, self.t0 * 1000.0))
                self.late.append((i, n - i, time.time()))
                i = n
            self.sent = i
            time.sleep(PACE_S)
        self.exhausted = i >= n_all

    def _watch(self) -> None:
        while not self.stop_watching.is_set():
            self.samples.append(
                (time.time(), self.broker.end_offset(self.out_topic, 0)))
            time.sleep(0.01)

    def result(self, grace_s: float) -> dict:
        self.stop_producing.set()
        self.stop_watching.set()
        for t in self.threads:
            t.join(timeout=10)
        due = self.stream.due[:self.sent] + self.t0
        t0, t1 = self.marks["window_start"], self.marks["window_end"]
        lo, hi = (int(np.searchsorted(due, t, "left")) for t in (t0, t1))
        st = np.array([s[0] for s in self.samples])
        done = np.array([s[1] for s in self.samples]) - self.base
        # record n (0-based) has its prediction once n + 1 are on the topic
        k = np.searchsorted(done, np.arange(lo, hi) + 1, "left")
        ok = k < len(st)
        at = st[np.minimum(k, len(st) - 1)]
        lat = (at - due[lo:hi])[ok & (at <= t1 + grace_s)]
        sent_at = np.concatenate(
            [np.full(c, t) for _i, c, t in self.late]) if self.late \
            else np.zeros(0)
        late = (sent_at - due[:len(sent_at)])[lo:hi]

        def lag(t):  # records published and not yet predicted, at t
            j = int(np.searchsorted(st, t, "right")) - 1
            return int(np.searchsorted(due, t, "right") - done[max(j, 0)])

        inside = (st >= t0) & (st <= t1)
        lags = np.searchsorted(due, st[inside], "right") - done[inside]
        pct = lambda a, q: float(np.percentile(a, q)) if len(a) else None  # noqa: E731
        return {"offered": hi - lo, "failed": int(hi - lo - len(lat)),
                "latency_p50_ms": pct(lat * 1e3, 50),
                "latency_p95_ms": pct(lat * 1e3, 95),
                "late_p95_ms": pct(late * 1e3, 95),
                "watch_samples": int(inside.sum()),
                "lag_median": float(np.median(lags)) if len(lags) else None,
                "lag_mid": lag((t0 + t1) / 2), "lag_end": lag(t1),
                "sent": self.sent, "exhausted": self.exhausted}


def main() -> int:
    spec = json.loads(sys.argv[1])
    dep = spec["deployment"]
    from iotml.config import load_config
    from iotml.store import StoreCompactor, StorePolicy
    from iotml.stream.broker import Broker
    from iotml.stream.kafka_wire import KafkaWireServer

    t_begin = time.time()
    cfg, _ = load_config([])
    broker = Broker(store_dir=spec["store_dir"],
                    store_policy=StorePolicy.from_config(cfg.store))
    compactor = StoreCompactor(
        broker, interval_s=broker.store.policy.compact_interval_s)
    compactor.start()
    broker.create_topic(dep["topic"], partitions=dep["partitions"],
                        retention_ms=dep["retention_ms"])
    broker.create_topic(dep["predictions_topic"],
                        partitions=dep["partitions"])
    server = KafkaWireServer(broker, host="127.0.0.1", port=0)
    server.start()

    live = None
    stream = Stream(spec, spec["cars"], spec["ticks"], spec.get("burst"))
    if spec["arrivals"] == "backlog":
        step = 100_000
        for lo in range(0, len(stream.values), step):
            broker.produce_many(dep["topic"], stream.entries(
                lo, lo + step, 1_600_000_000_000.0))
        del stream
        # the backlog is on disk before anyone reads it: left dirty in the
        # page cache, its write-back lands in the window and stalls the
        # trainer's own fsyncs for seconds in one run of six
        broker.flush()
        os.sync()
    else:
        # four threads share this interpreter (producer, watcher, the
        # wire server's handlers): hand the lock over in 1 ms, not 5
        sys.setswitchinterval(0.001)
        live = Live(broker, stream, dep["topic"], dep["predictions_topic"])
    ends = [broker.end_offset(dep["topic"], p)
            for p in range(dep["partitions"])]
    print(json.dumps({"port": server.port, "ends": ends,
                      "fsync": broker.store.policy.fsync,
                      "fill_s": time.time() - t_begin}), flush=True)

    for line in sys.stdin:
        req = json.loads(line)
        cmd = req["cmd"]
        if cmd == "quit":
            break
        if cmd == "live_start":
            out = {"t0": live.start()}
        elif cmd == "mark":
            live.marks[req["name"]] = time.time()
            out = {"t": live.marks[req["name"]]}
        elif cmd == "live_stop":
            live.stop_producing.set()
            live.threads[0].join(timeout=10)
            out = {"sent": live.sent}
        elif cmd == "live_result":
            out = live.result(req["grace_s"])
        else:
            out = {"error": f"unknown command {cmd}"}
        print(json.dumps(out), flush=True)
    compactor.stop()
    server.kill()
    broker.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
