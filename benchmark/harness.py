"""What every cell's run shares: the run's context, the log child, host
spans, registry deltas, the profiler window, row matching and the
numbers a check compares.  Knows no cell, configuration or metric by
name; `run.py` finds those as files."""

from __future__ import annotations

import collections
import glob
import importlib.util
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str):
    return next((w for w in bench["workloads"] if w["name"] == name), None)


def load_module(path: str):
    """Import a benchmark file by path (its name may hold a dash)."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def say(*parts) -> None:
    """A line of the run's story: stdout, before the result line."""
    print(*parts, flush=True)


# ---------------------------------------------------------------- spans
class Spans:
    """Host-clock spans around the calls into each layer, summed by
    name; while a profiler window is open they also go into its trace."""

    def __init__(self):
        self.total = collections.defaultdict(float)
        self.count = collections.defaultdict(int)
        self.annotate = None  # jax.profiler.TraceAnnotation while tracing

    def wrap(self, name: str, fn):
        def spanned(*args, **kw):
            note = self.annotate
            t0 = time.perf_counter()
            try:
                if note is None:
                    return fn(*args, **kw)
                with note(name):
                    return fn(*args, **kw)
            finally:
                self.total[name] += time.perf_counter() - t0
                self.count[name] += 1
        return spanned

    def snapshot(self) -> dict:
        return {k: (self.total[k], self.count[k]) for k in self.total}


def delta(after: dict, before: dict) -> dict:
    """after - before, per key; span snapshots hold (sum, count) pairs."""
    out = {}
    for k, v in after.items():
        b = before.get(k, (0.0, 0) if isinstance(v, tuple) else 0.0)
        out[k] = tuple(x - y for x, y in zip(v, b)) \
            if isinstance(v, tuple) else v - b
    return out


def registry() -> dict:
    """The program's metric registry, every series by its text name."""
    from iotml.obs.metrics import default_registry

    return default_registry.collect()


# ------------------------------------------------------------ log child
class LogChild:
    """Process B: the log behind the Kafka wire, the seeded fleet that
    fills it and, in an open-loop cell, the paced producer and the
    watcher — pinned to the CPU, started first, gone at exit."""

    def __init__(self, run, spec: dict):
        self.log_path = run.path("logchild.err")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self._err = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "logchild.py"),
             json.dumps(dict(spec, store_dir=run.path("store"),
                             seed=run.seed,
                             deployment=run.cfg["deployment"],
                             failure_rate=run.cfg["assumed"]["failure_rate"]
                             ))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=self._err,
            env=env, text=True, cwd=ROOT)
        self._ready = None

    @property
    def ready(self) -> dict:
        """The child's first line: blocks until its set-up is done."""
        if self._ready is None:
            self._ready = self._read()
        return self._ready

    def _read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            self.close()
            with open(self.log_path) as fh:
                tail = fh.read()[-3000:]
            raise RuntimeError(f"the log child died:\n{tail}")
        return json.loads(line)

    def ask(self, cmd: str, **kw) -> dict:
        self.proc.stdin.write(json.dumps(dict(kw, cmd=cmd)) + "\n")
        self.proc.stdin.flush()
        return self._read()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write('{"cmd": "quit"}\n')
                self.proc.stdin.flush()
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self._err.close()


# ---------------------------------------------------------- the context
class Run:
    """One run of one cell: what drivers, adapters and readers share."""

    def __init__(self, bench: dict, cell: dict, seed: int, seconds: float,
                 trace: bool, t_start: float):
        self.bench, self.cell = bench, cell
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start = t_start
        config = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
        self.cfg = load_json(os.path.join(ROOT, config["file"]))
        self._adapter_path = os.path.join(
            ROOT, config["file"][:-len(".json")] + ".py")
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json"))
        # a mix may state its own job shape (window, batch, volume)
        self.cfg["job"].update(self.traffic.get("job", {}))
        self.out_dir = os.path.join(BENCH, "out", cell["name"])
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.spans = Spans()
        self.device = None   # claim_device()'s report
        self.log = None      # process B
        self.broker = None
        self.checks = []     # (name, value, limit, ok)
        self.notes = {}      # what readers of per-layer metrics read

    def override(self, items) -> None:
        """By hand only: `key=value` sets a number of the traffic file,
        `cfg.<group>.<key>=value` one of the configuration's."""
        for item in items:
            key, value = item.split("=", 1)
            *groups, leaf = key.split(".")
            target = self.cfg if groups else self.traffic
            for g in groups[1:]:
                target = target[g]
            target[leaf] = json.loads(value)

    def lap(self, what: str) -> None:
        """Where set-up's seconds go: a line per stage."""
        say(f"set-up +{time.time() - self.t_start:7.2f} s  {what}")

    def path(self, name: str) -> str:
        return os.path.join(self.out_dir, name)

    def on_chip(self) -> bool:
        return self.device["platform"] != "cpu"

    @property
    def adapter(self):
        """The configuration's reference and adapter (imports JAX); one
        that takes its sizes from the file is handed them."""
        mod = load_module(self._adapter_path)
        if hasattr(mod, "use"):
            mod.use(self.cfg)
        return mod

    def spawn_log(self, spec: dict) -> None:
        """Start process B now, so that it fills the log while this
        process imports and starts its backend."""
        self.log_spec = spec
        self.log = LogChild(self, spec)

    def connect_log(self) -> dict:
        """Wait for the filled log and connect over the Kafka wire with
        the native client, as `cli.live` does."""
        from iotml.cli.live import _wire_broker

        self.broker = _wire_broker(f"127.0.0.1:{self.log.ready['port']}",
                                   None)
        if type(self.broker).__name__ != "NativeKafkaBroker":
            raise RuntimeError("the native wire client did not load")
        return self.log.ready

    def close(self) -> None:
        if self.broker is not None:
            self.broker.close()
        if self.log is not None:
            self.log.close()
        shutil.rmtree(self.path("store"), ignore_errors=True)

    # --------------------------------------------------------- checking
    def check(self, name: str, value, limit, exact: bool = False) -> bool:
        """One number compared beside its limit, printed in every run."""
        ok = (value == limit) if exact else bool(value <= limit)
        self.checks.append((name, value, limit, ok))
        say(f"check {name}: {value!r} "
            f"{'==' if exact else '<='} {limit!r} -> "
            f"{'ok' if ok else 'NOT CORRECT'}")
        return ok

    def correct(self) -> bool:
        return bool(self.checks) and all(c[3] for c in self.checks)

    def setup_done(self) -> float:
        """Called at the first measured operation; returns setup_s."""
        self.setup_s = time.time() - self.t_start
        return self.setup_s


def positions(consumer) -> dict:
    return {p: off for _t, p, off in consumer.positions()}


class Wrapper:
    """Seeks a caught-up group back to the log's start and counts it:
    the backlog cells' closed loop over a log of fixed size."""

    def __init__(self, consumer, topic: str, ends: dict, slack: int):
        self.consumer, self.topic = consumer, topic
        self.ends, self.slack = ends, slack
        self.wraps = 0
        self.skipped = 0   # records left unread at the ends
        self.rewound = 0   # offsets given back by the seeks

    def left(self) -> int:
        pos = positions(self.consumer)
        return sum(self.ends[p] - pos[p] for p in self.ends)

    def wrap_if_short(self) -> bool:
        left = self.left()
        if left > self.slack:
            return False
        pos = positions(self.consumer)
        for p in self.ends:
            self.consumer.seek(self.topic, p, 0)
        self.wraps += 1
        self.skipped += left
        self.rewound += sum(pos.values())
        return True


# ------------------------------------------------------ recorded batches
class Recorder:
    """Stands where the program's batcher stands and keeps some of the
    batches it hands on: what the timed path was fed, for the check."""

    def __init__(self, inner, keep):
        self.inner = inner
        self.keep = keep   # (ordinal) -> bool
        self.rows = 0      # valid rows handed on so far
        self.ordinal = 0
        self.kept = []     # (rows before it, n_valid, x, y)

    def _watch(self, it):
        for b in it:
            if self.keep is not None and self.keep(self.ordinal):
                self.kept.append((self.rows, b.n_valid, np.array(b.x),
                                  None if b.y is None else np.array(b.y)))
            self.ordinal += 1
            self.rows += b.n_valid
            yield b

    def __iter__(self):
        return self._watch(iter(self.inner))

    def epochs(self, n: int):
        for it in self.inner.epochs(n):
            yield self._watch(it)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class Reservoir:
    """A seeded sample of `k` batch ordinals out of a stream of unknown
    length; the newest kept batch replaces a random older one."""

    def __init__(self, seed: int, k: int):
        self.rng = np.random.default_rng(seed)
        self.k = k

    def attach(self, rec: Recorder):
        self.rec = rec
        rec.keep = self._keep
        return rec

    def _keep(self, ordinal: int) -> bool:
        if ordinal < self.k:
            return True
        j = int(self.rng.integers(0, ordinal + 1))
        if j < self.k:
            del self.rec.kept[j]
            return True
        return False


def match_rows(rows: np.ndarray, ref: np.ndarray, col: int,
               tol: float = 1e-5):
    """For each of `rows`, the nearest of the reference's rows (widest
    gap over the fields), found through one continuous field.  Returns
    (index into ref, gap) per row."""
    order = np.argsort(ref[:, col], kind="stable")
    key = ref[order, col]
    lo = np.searchsorted(key, rows[:, col] - tol, "left")
    hi = np.searchsorted(key, rows[:, col] + tol, "right")
    width = int((hi - lo).max()) if len(rows) else 0
    if width > 4096:
        raise RuntimeError("row matching: the key field does not tell "
                           "records apart")
    best = np.full(len(rows), -1, np.int64)
    gap = np.full(len(rows), np.inf)
    for k in range(width):
        j = np.minimum(lo + k, len(key) - 1)
        cand = order[j]
        g = np.abs(ref[cand] - rows).max(axis=1)
        g[lo + k >= hi] = np.inf
        better = g < gap
        best[better], gap[better] = cand[better], g[better]
    return best, gap


def reference_rows(run, ticks: int, cars: int) -> np.ndarray:
    """The log's rows as the benchmark made them, normalized by the
    configuration's own ranges: [ticks * cars, 18] float32."""
    from benchmark import fleet

    f = fleet.Fleet(run.seed, cars, run.cfg["assumed"]["failure_rate"],
                    run.cfg["deployment"]["interval_s"])
    ranges = run.cfg["normalization"]["ranges"]
    return np.concatenate([fleet.normalize(f.step()[0], ranges)
                           for _ in range(ticks)])


# ------------------------------------------------------- norms and gaps
def norm_gaps(prog, ref) -> tuple:
    """The gap between the program's norm and the reference's, against
    the reference's: (over all leaves as one vector, by the worst leaf —
    against that leaf's norm or the median leaf's, whichever is larger)."""
    import jax

    pn = [float(np.linalg.norm(np.asarray(a).ravel()))
          for a in jax.tree.leaves(prog)]
    rn = [float(np.linalg.norm(np.asarray(a).ravel()))
          for a in jax.tree.leaves(ref)]
    if len(pn) != len(rn):
        raise RuntimeError("parameter trees differ")
    floor = statistics.median(rn)
    worst = max(abs(p - r) / max(r, floor, 1e-30) for p, r in zip(pn, rn))
    whole_p, whole_r = np.linalg.norm(pn), np.linalg.norm(rn)
    return float(abs(whole_p - whole_r) / max(whole_r, 1e-30)), worst


def tree_sub(a, b):
    import jax

    # in the arrays' own type: a float64 copy of a 303M-parameter tree
    # is seconds of the host's time, and the gaps compared are far
    # above float32's rounding
    return jax.tree.map(lambda x, y: np.asarray(x) - np.asarray(y), a, b)


# ------------------------------------------------------ profiler window
TRACE_MARGIN = 1.05  # a job may outlast the longest seen by this much


def trace_due_from(t_end: float, length_s: float, longest_s: float) -> float:
    """The instant from which a call finds the profiler's window due:
    `length_s` before `t_end` or, where the calls have lain further
    apart than that, the longest stretch between two of them (and a
    margin)."""
    return t_end - max(length_s, TRACE_MARGIN * longest_s)


def trace_due(now: float, t_end: float, length_s: float,
              longest_s: float) -> bool:
    """Is the profiler's window due at a call made at `now`?  From the
    first call at which a job started now could be the window's last.
    Calls lie no further apart than the longest stretch, so one falls
    there; `length_s` is the traced stretch's floor, and its length
    wherever the jobs are shorter."""
    return now >= trace_due_from(t_end, length_s, longest_s)


class TraceWindow:
    """A profiler trace of the window's last whole job or jobs (its
    last `length_s` seconds where they are shorter), stopped after the
    window has closed so that writing it costs the window nothing."""

    def __init__(self, run, length_s: float):
        self.run, self.length_s = run, length_s
        self.dir = run.path("trace")
        self.t0 = None
        self.longest_s, self.calls = 0.0, 0
        # the newest calls, in seconds of the window: what a run that
        # took no trace has to say for itself
        self.seen = collections.deque(maxlen=16)

    def due(self, now: float, t_end: float) -> bool:
        """One call from the driver's loop, at a boundary between jobs
        (or a poll while it waits for records): keeps the longest
        stretch between calls and says whether the trace starts here."""
        at = now - (t_end - self.run.seconds)
        if self.seen:
            self.longest_s = max(self.longest_s, at - self.seen[-1])
        self.seen.append(at)
        self.calls += 1
        return trace_due(now, t_end, self.length_s, self.longest_s)

    def maybe_start(self, now: float, t_end: float) -> None:
        if self.t0 is None and self.run.trace and self.due(now, t_end):
            import jax

            jax.profiler.start_trace(self.dir)
            self.run.spans.annotate = jax.profiler.TraceAnnotation
            self.window = jax.profiler.TraceAnnotation("bench.window")
            self.window.__enter__()
            self.t0 = time.perf_counter()

    def close_window(self) -> None:
        """The measured window has closed: what follows is not analysed."""
        if self.t0 is not None:
            self.run.spans.annotate = None
            self.window.__exit__(None, None, None)

    def why_none(self, what: str) -> str:
        due_from = trace_due_from(self.run.seconds, self.length_s,
                                  self.longest_s)
        return (f"a traced run with no trace to report: {what}.  The "
                f"window's loop asked {self.calls} times whether the "
                f"trace was due, last at "
                f"{', '.join(f'{t:.3f}' for t in self.seen)} s of its "
                f"{self.run.seconds:g} s; the longest stretch between "
                f"two calls was {self.longest_s:.3f} s, so the trace "
                f"was due from {due_from:.3f} s: the end less the "
                f"larger of trace_seconds, {self.length_s:g}, and "
                f"{TRACE_MARGIN:g} such stretches")

    def stop(self) -> dict:
        """Stop and reduce, once nothing is in flight any more; {} where
        no trace was asked for, and in a rehearsal on the CPU, which has
        no device plane and no device metric.  On a chip a traced run
        with no trace to reduce ends here and says why: a result line
        without `busy_s` and `window_s` is no traced run's."""
        if self.t0 is None:
            if self.run.trace and self.run.on_chip():
                raise SystemExit(self.why_none(
                    "the profiler never started"))
            return {}
        import jax

        from benchmark import trace_reduce

        jax.profiler.stop_trace()
        try:
            if not self.run.on_chip():
                return {}
            pbs = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
            if not pbs:
                raise SystemExit(self.why_none(
                    f"the profiler wrote no .xplane.pb under {self.dir}"))
            return trace_reduce.reduce_file(pbs[0])
        except ValueError as e:  # the reduction found no device plane
            raise SystemExit(self.why_none(
                f"it started at {self.seen[-1]:.3f} s, but {e}")) from e
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_report(run, traced: dict) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        # what lives on the chip: the arrays in use and, where the
        # backend keeps it apart (the TPU's does), the scratch memory
        # it reserves for the loaded programs
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    say("memory:", json.dumps(devs[0].memory_stats() or {}))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if run.trace and traced and run.on_chip():
        out["busy_s"] = traced["busy_s"]
        out["window_s"] = traced["window_s"]
    return out
