"""Record-level trace context + per-stage telemetry for the pipeline.

The reference observes its pipeline only from the *outside* — Prometheus
scraping broker and simulator gauges (SURVEY §5) — so nobody can answer
the question that matters for a no-data-lake streaming trainer: how long
does one sensor reading take device → MQTT → broker → bridge → KSQL →
consumer → train-step/score, and which stage ate the budget?  tf.data's
pipeline analysis (PAPERS.md) makes the same point: stage-level
telemetry is what turns "it's slow" into "it's input-bound at the
decode stage".

Design:

- A `TraceContext` is injected where a record is born (MQTT publish /
  devsim produce), carried through the pipeline via *record headers*
  (`Message.headers`, key ``iotml_trace``) so the Avro payload is
  untouched, and closed at the train step or the scorer.
- Time domain is **monotonic** (PR 1's R1 rule): spans are durations
  from the injection instant, never wall-clock differences.  One wall
  clock read at injection timestamps the trace for the span log.
- Stage marks record spans into a **lock-free collector**: a per-thread
  `deque` (GIL-atomic append, bounded drop-oldest) registered once per
  thread; nothing on the record path takes a lock — verified by the
  lockcheck plugin and lint rule R6.
- Exporters run at *drain* time (`flush()`, the /metrics scrape, the
  /healthz probe, atexit): spans land in the Prometheus histograms
  ``iotml_stage_seconds{stage=...}`` and
  ``iotml_e2e_ingest_to_*_seconds``, and — when a path is configured —
  in a JSONL span log the ``python -m iotml.obs trace`` CLI summarizes.

Between a histogram's sum and a record sits the **phase span**: one
span per phase per round of a hot loop (`phase()`), with a parent and a
round number.  It observes ``iotml_step_seconds{loop,phase}``, lands in
a per-thread ring `phases()` reads, rides the span log where a path is
set, and is a ``jax.profiler.TraceAnnotation`` for its duration — only
where `jax` is already imported: this module never imports it.  Phase
spans are unconditional and round-granular (per round, per consumer
call; never per record, never inside a compiled step).

The loop ``start`` is a process's way to its first fit: ``backend``
(`utils.device.claim_device`), ``state_init`` (`Trainer._ensure_state`),
``engine`` (the native engine's build, where it builds) and ``import`` —
one span per OUTERMOST import of 0.1 s or more, its module in the
span's `note`, timed by a finder `time_imports()` puts ahead of
``sys.meta_path`` (the package's ``__init__`` calls it first of all).
`start_report()` lays them beside the first ``iotml.train.fit``.

The record level is off by default, zero-ish cost: every instrumentation
site guards on the module flag (`tracing.ENABLED`, which gates the
record-level contexts only) and allocates nothing when it is False.
Enable with ``IOTML_TRACE=1``; sample with ``IOTML_TRACE_SAMPLE=0.01``;
log spans to ``IOTML_TRACE_PATH=/tmp/spans.jsonl``.  These are process
toggles, not pipeline config — registered in `iotml.config`'s
``non_config`` set.

Header wire format (for transports that carry bytes, not objects):
``iotml1;<trace_id hex16>;<t0 unix ns>;<elapsed ns>`` — `encode()` /
`decode()` round-trip it.  In-process brokers carry the live context
object itself; the Kafka wire protocol's MessageSet v1 has no header
slot, so traces end at a TCP broker boundary (graceful degradation,
like the native-engine fallback).
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import os
import random
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from . import metrics as _metrics

#: module flag every hot-path site guards on.  Mutated only via
#: configure(); reading a module attribute is the whole disabled cost.
ENABLED = False

#: probability a newborn record is traced (1.0 = every record).
_SAMPLE = 1.0

#: JSONL span-log path (None = histograms only).
_PATH: Optional[str] = None

#: header key the context rides under in Message.headers.
HEADER_KEY = "iotml_trace"

_WIRE_PREFIX = "iotml1"


def proc_name() -> str:
    """This process's identity in cross-process span logs: IOTML_PROC
    when the operator names the role (scorer/trainer/broker-0/...),
    else pid-derived.  Several fleet processes append to ONE span log
    (O_APPEND line writes); the proc field is what lets the trace CLI
    reconstruct which process ran which stage."""
    return os.environ.get("IOTML_PROC") or f"pid{os.getpid()}"

#: per-thread span buffer bound — overload drops oldest, counted below.
_BUFFER_BOUND = 65536

#: per-thread ring of phase spans: round-granular, so hours of a loop
_PHASE_BOUND = 4096
#: buffers registered before a new thread's registration prunes those of
#: exited threads (see `_Collector.buffer`)
_THREADS_BOUND = 256

#: loop label of a phase opened with no loop and no parent (a batcher
#: iterated outside any loop's phase)
_NO_LOOP = "stream"

#: an outermost import shorter than this makes no span: a start imports
#: a few thousand modules, and the ring holds 4,096 spans a thread
IMPORT_FLOOR_S = 0.1

#: the module's one wall-clock anchor: a phase span carries monotonic
#: times, and (monotonic - anchor) + wall anchor lays a span log over a
#: profiler trace
_ANCHOR_MONO = time.monotonic()
_ANCHOR_WALL_NS = time.time_ns()  # wallclock-ok: the anchor the span log carries, not a deadline

# ------------------------------------------------------------- exporters
stage_seconds = _metrics.default_registry.histogram(
    "iotml_stage_seconds", "per-stage pipeline latency (label: stage)",
    buckets=(0.00001, 0.0001, 0.001, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0))
e2e_ingest_to_score_seconds = _metrics.default_registry.histogram(
    "iotml_e2e_ingest_to_score_seconds",
    "end-to-end latency, record ingest to scorer close")
e2e_ingest_to_train_seconds = _metrics.default_registry.histogram(
    "iotml_e2e_ingest_to_train_seconds",
    "end-to-end latency, record ingest to train-step close")
spans_dropped = _metrics.default_registry.counter(
    "iotml_trace_spans_dropped_total",
    "spans dropped by the bounded per-thread collector")
log_write_errors = _metrics.default_registry.counter(
    "iotml_trace_log_write_errors_total",
    "span-log appends that failed (unwritable path, full disk)")


# ------------------------------------------------------------- collector
class _Buf:
    """One thread's span buffer + its local overload-drop count.  The
    drop count is a plain int mutated only by the owning thread (folded
    into the shared counter at drain) so the record path touches no
    shared lock even when the buffer is saturated."""

    __slots__ = ("q", "drops", "thread", "phases", "stack", "logged")

    def __init__(self, thread: threading.Thread):
        self.q: collections.deque = collections.deque(maxlen=_BUFFER_BOUND)
        self.drops = 0
        self.thread = thread
        # phase spans: a ring that drains never empty (phases() reads
        # it), the open phases of this thread, and the id of the newest
        # span the log exporter has written
        self.phases: collections.deque = collections.deque(
            maxlen=_PHASE_BOUND)
        self.stack: list = []
        self.logged = 0


class _Collector:
    """Per-thread bounded deques; append is GIL-atomic (no lock on the
    record path), the registry of buffers is locked only at thread
    registration and drain — never while a span is recorded."""

    def __init__(self):
        self._tls = threading.local()
        self._buffers: List[_Buf] = []
        self._reg_lock = threading.Lock()

    def buffer(self) -> _Buf:
        buf = getattr(self._tls, "buf", None)
        if buf is None:
            buf = _Buf(threading.current_thread())
            self._tls.buf = buf
            with self._reg_lock:
                if len(self._buffers) >= _THREADS_BOUND:
                    # a process nobody drains (no scrape, no span log)
                    # that starts short-lived threads — the consumer's
                    # read-ahead is one a take — must not keep a ring a
                    # thread for good: the exited threads' drained
                    # buffers go, their phase spans with them (the
                    # histogram has them; `drain` prunes the logged ones
                    # sooner)
                    self._buffers = [b for b in self._buffers
                                     if b.q or b.thread.is_alive()]
                self._buffers.append(buf)
        return buf

    def record(self, entry: tuple) -> None:
        buf = self.buffer()
        if len(buf.q) == buf.q.maxlen:
            buf.drops += 1  # thread-local; folded in at drain (no lock)
        buf.q.append(entry)

    def buffers(self) -> List[_Buf]:
        """Every live thread's buffer (a snapshot of the registry)."""
        with self._reg_lock:
            return list(self._buffers)

    def drain(self) -> List[tuple]:
        buffers = self.buffers()
        out: List[tuple] = []
        for buf in buffers:
            # popleft until empty: concurrent appends land at the right
            # and are picked up by this or the next drain — never lost,
            # never double-read
            while True:
                try:
                    out.append(buf.q.popleft())
                except IndexError:
                    break
        dropped = 0
        dead: List[_Buf] = []
        # drop-count folding and dead-thread pruning under the registry
        # lock: concurrent drainers (two scrapes) must not both read the
        # same buf.drops and double-count it.  Recording threads never
        # touch this lock (registration is the documented once-per-thread
        # exception); an owner increment landing exactly between the read
        # and the reset below is lost — approximate under overload, by
        # design, never a crash.
        with self._reg_lock:
            for buf in buffers:
                if buf.drops:
                    dropped += buf.drops
                    buf.drops = 0
                # prune buffers of exited threads (a churning MQTT fleet
                # is a thread per connection: without this the registry
                # grows one dead deque per reconnect, forever).  Just
                # drained empty + owner dead = nothing can land in it.
                if not buf.q and not buf.thread.is_alive() and (
                        not buf.phases or buf.phases[-1].id <= buf.logged):
                    dead.append(buf)
            if dead:
                self._buffers = [b for b in self._buffers
                                 if b not in dead]
        if dropped:
            spans_dropped.inc(dropped)
        return out


_collector = _Collector()

#: stage → monotonic time of the newest drained span: per-stage liveness
#: for the /healthz status section (age = now - value).
_last_seen: Dict[str, float] = {}

#: current-trace slot for synchronous fan-out propagation (the MQTT
#: broker delivers on the publisher's thread, so the bridge reads the
#: publisher's context without any header slot in the MQTT PUBLISH).
_current = threading.local()

_log_lock = threading.Lock()  # serializes span-log file appends (drain only)


class TraceContext:
    """One record's journey.  `mark(stage)` records the span since the
    previous mark; `close(closer)` marks the final stage and the e2e
    span.  All durations are monotonic-clock."""

    __slots__ = ("trace_id", "t0", "t_last", "wall0_ns", "closed")

    def __init__(self, trace_id: Optional[int] = None,
                 t0: Optional[float] = None,
                 wall0_ns: Optional[int] = None):
        self.trace_id = trace_id if trace_id is not None \
            else random.getrandbits(64)
        self.t0 = t0 if t0 is not None else time.monotonic()
        self.t_last = self.t0
        self.wall0_ns = wall0_ns if wall0_ns is not None \
            else time.time_ns()  # wallclock-ok: trace birth timestamp for the span log, not a deadline
        self.closed = False

    # ------------------------------------------------------------ spans
    def mark(self, stage: str) -> None:
        """Record the span from the previous mark to now as `stage`.

        A closed context records nothing more: an epoch re-read polls the
        same header-carried context again, and re-marking it would book
        the inter-epoch gap as pipeline latency."""
        if self.closed:
            return
        now = time.monotonic()
        # `now` rides along so liveness() can report the span's MARK
        # time, not the drain time — a stalled stage probed much later
        # must show its true age
        _collector.record(("span", self.trace_id, stage,
                           self.t_last - self.t0, now - self.t_last,
                           self.wall0_ns, now))
        self.t_last = now

    def close(self, closer: str) -> None:
        """Final stage (`train` / `score`) + the end-to-end span."""
        if self.closed:
            return
        self.mark(closer)
        self.closed = True
        _collector.record(("e2e", self.trace_id, closer,
                           self.t_last - self.t0, self.wall0_ns))

    def fork(self) -> "TraceContext":
        """Per-consumer continuation of a shared upstream context.

        The header-carried object is read by EVERY consumer group of the
        topic (a train pipeline and a serve pipeline routinely poll the
        same log).  Each reader forks at its consume boundary and closes
        only its fork — same trace id, birth instant and elapsed-so-far,
        private t_last/closed — so one pipeline's close can neither
        steal the trace from another (the first-closer-wins bug) nor
        race its marks on the shared t_last."""
        child = TraceContext(trace_id=self.trace_id, t0=self.t0,
                             wall0_ns=self.wall0_ns)
        child.t_last = self.t_last
        return child

    # ---------------------------------------------------------- headers
    def encode(self) -> bytes:
        """Byte form for transports: id, birth wall time, elapsed."""
        elapsed_ns = int((time.monotonic() - self.t0) * 1e9)
        return (f"{_WIRE_PREFIX};{self.trace_id:016x};{self.wall0_ns};"
                f"{elapsed_ns}").encode()

    @classmethod
    def decode(cls, raw: bytes) -> Optional["TraceContext"]:
        """Rebase a wire-carried context into this process's monotonic
        domain (elapsed-so-far is preserved; clock skew between hosts is
        the usual distributed-tracing caveat)."""
        try:
            prefix, tid, wall0, elapsed = raw.decode().split(";")
            if prefix != _WIRE_PREFIX:
                return None
            ctx = cls(trace_id=int(tid, 16),
                      t0=time.monotonic() - int(elapsed) / 1e9,
                      wall0_ns=int(wall0))
            ctx.t_last = time.monotonic()
            return ctx
        except (ValueError, UnicodeDecodeError):
            return None


# ------------------------------------------------------------ public API
def configure(enabled: Optional[bool] = None,
              sample: Optional[float] = None,
              path: Optional[str] = None) -> None:
    global ENABLED, _SAMPLE, _PATH
    if enabled is not None:
        ENABLED = bool(enabled)
    if sample is not None:
        _SAMPLE = min(max(float(sample), 0.0), 1.0)
    if path is not None:
        _PATH = path or None


def configure_from_env(env: Optional[Dict[str, str]] = None) -> None:
    env = os.environ if env is None else env
    raw = env.get("IOTML_TRACE")
    if raw is not None:
        configure(enabled=raw.strip().lower() in ("1", "true", "yes", "on"))
    raw = env.get("IOTML_TRACE_SAMPLE")
    if raw:
        configure(sample=float(raw))
    raw = env.get("IOTML_TRACE_PATH")
    if raw:
        configure(path=raw)


def start(stage: str) -> Optional[TraceContext]:
    """Begin a trace at a record's birth (sampling decision happens
    here); returns None when disabled or not sampled."""
    if not ENABLED:
        return None
    if _SAMPLE < 1.0 and random.random() >= _SAMPLE:
        return None
    ctx = TraceContext()
    ctx.mark(stage)
    return ctx


def current() -> Optional[TraceContext]:
    """The publisher-thread context (synchronous fan-out propagation)."""
    return getattr(_current, "ctx", None)


def set_current(ctx: Optional[TraceContext]):
    prev = getattr(_current, "ctx", None)
    _current.ctx = ctx
    return prev


def touch(stage: str) -> None:
    """Mark `stage` live WITHOUT a span — the batch-granular liveness
    beat for the columnar plane (ISSUE 13 satellite): ``poll_into``
    materialises zero records, so an untraced-record columnar consumer
    emits no consume-stage spans and the /healthz stage-age view would
    report a perfectly healthy pipeline as stalled.  A plain dict store
    under the GIL; racing a concurrent drain is benign (both write
    "recent")."""
    if ENABLED:
        _last_seen[stage] = time.monotonic()


# ----------------------------------------------------------- phase spans
class PhaseSpan(NamedTuple):
    """One phase of one round of a loop.  `start`/`end` are monotonic
    seconds (`wall_ns()` maps them onto the wall clock through the
    module's anchor); `parent` is the enclosing phase's `id` on this
    thread, None for a root; ids count up per process.  `note` says
    which one of a phase's kind this was where the name cannot (an
    import's module): it rides the ring and the span log and is no
    label of the histogram, whose label set stays closed."""

    name: str
    start: float
    end: float
    parent: Optional[int]
    round: Optional[int]
    thread: str
    id: int
    note: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def wall_ns(self) -> int:
        return _ANCHOR_WALL_NS + int((self.start - _ANCHOR_MONO) * 1e9)


_phase_ids = itertools.count(1)  # next() is GIL-atomic


def annotation(name: str, **kw):
    """`jax.profiler.TraceAnnotation(name, **kw)` where this process has
    imported jax, else None: the one place the obs package reaches the
    profiler.  Importing jax here would put launchers and the host
    plane's children on it (and on the chip's lock); a process that
    never imported it has no profiler session to annotate."""
    jax = sys.modules.get("jax")
    prof = getattr(jax, "profiler", None)
    return None if prof is None else prof.TraceAnnotation(name, **kw)


class phase:
    """``with tracing.phase("train", "fit", round=n):`` — the one idiom
    for timing a phase of a loop.  On exit it observes
    ``iotml_step_seconds{loop,phase}``, appends one `PhaseSpan` named
    ``iotml.<loop>.<phase>`` to this thread's ring, and for its duration
    it is a profiler `TraceAnnotation` of that name (see `annotation`),
    so the program's spans sit in the profiler's host plane on the
    clock the device plane is aligned to.

    `loop` and `round` are inherited from the enclosing phase of this
    thread where None.  Unconditional, and round-granular by contract:
    open one per round or per consumer call, never per record and never
    inside a jitted function.  `note` names the instance (`PhaseSpan`).
    A phase that ends before `floor` seconds leaves nothing behind, no
    span and no observation: for work that is worth a span only where
    it turns out long (an import, a build that found nothing to do)."""

    __slots__ = ("loop", "phase", "round", "note", "floor", "id", "name",
                 "_buf", "_parent", "_note", "_t0")

    def __init__(self, loop: Optional[str], phase: str,
                 round: Optional[int] = None, note: Optional[str] = None,
                 floor: float = 0.0):
        self.loop, self.phase, self.round = loop, phase, round
        self.note, self.floor = note, floor

    def __enter__(self) -> "phase":
        buf = self._buf = _collector.buffer()
        parent = buf.stack[-1] if buf.stack else None
        if self.loop is None:
            self.loop = parent.loop if parent is not None else _NO_LOOP
        if self.round is None and parent is not None:
            self.round = parent.round
        self._parent = parent.id if parent is not None else None
        self.id = next(_phase_ids)
        buf.stack.append(self)
        name = self.name = f"iotml.{self.loop}.{self.phase}"
        kw = {} if self.round is None else {"round": self.round}
        if self.note is not None:
            kw["note"] = self.note
        self._note = annotation(name, **kw)
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        if self._note is not None:
            self._note.__exit__(*exc)
        buf = self._buf
        buf.stack.pop()
        if t1 - self._t0 < self.floor:
            return
        _metrics.step_seconds.observe(t1 - self._t0, loop=self.loop,
                                      phase=self.phase)
        buf.phases.append(PhaseSpan(
            self.name, self._t0, t1, self._parent, self.round,
            buf.thread.name, self.id, self.note))


def phases() -> List[PhaseSpan]:
    """Every phase span still in the rings, oldest first.  Reading
    empties nothing: the rings are bounded and drop their oldest."""
    out: List[PhaseSpan] = []
    for buf in _collector.buffers():
        out.extend(buf.phases)  # C-level copy: atomic under the GIL
    out.sort(key=lambda s: s.id)
    return out


def self_seconds(spans) -> Dict[int, float]:
    """Span id → its duration less the cover of its direct children:
    the time a phase spent in no phase of its own."""
    kids: Dict[int, list] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start, s.end))
    out = {}
    for s in spans:
        cover, edge = 0.0, s.start
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, edge), min(b, s.end)
            if b > a:
                cover, edge = cover + (b - a), b
        out[s.id] = (s.end - s.start) - cover
    return out


# ------------------------------------------------------ a process's start
def _nested(tls, name: str) -> bool:
    """Whether an import of `name` by this thread, now, is the outermost
    import's own time: a module of its package, or anything under a
    module of another package that is being timed already."""
    own = getattr(tls, "own", None)
    return own is not None and (tls.abroad
                                or name.partition(".")[0] == own)


class _TimedLoader:
    """Stands in for a module's loader until the module runs: the
    outermost `exec_module` of a thread is a `start.import` phase with a
    floor.  What the outermost's `note` says beside its own name is
    where its seconds went: the imports of OTHER packages it pulled in
    first-hand, by seconds (`iotml.train (orbax.checkpoint 2.58, optax
    0.36)`), which are the only nested imports that meet this object.
    The module gets its own loader back before it runs, so nothing that
    reads `__loader__` or `__spec__.loader` later meets it either."""

    __slots__ = ("_inner",)

    def __init__(self, inner):
        self._inner = inner

    def create_module(self, spec):
        return self._inner.create_module(spec)

    def exec_module(self, module) -> None:
        inner, tls = self._inner, _importing
        spec = getattr(module, "__spec__", None)
        if spec is not None and spec.loader is self:
            spec.loader = inner
        if getattr(module, "__loader__", None) is self:
            module.__loader__ = inner
        name = module.__name__
        if _nested(tls, name):
            # (found outside an import and run inside one: as it was)
            return inner.exec_module(module)
        if getattr(tls, "own", None) is not None:
            # pulled in first-hand from another package: timed, no span
            tls.abroad, t0 = True, time.monotonic()
            try:
                return inner.exec_module(module)
            finally:
                tls.abroad = False
                tls.pulled.append((time.monotonic() - t0, name))
        tls.own, tls.abroad, tls.pulled = name.partition(".")[0], False, []
        span = phase("start", "import", note=name, floor=IMPORT_FLOOR_S)
        span.__enter__()
        try:
            inner.exec_module(module)
        finally:
            tls.own = None
            heavy = [f"{n} {s:.2f}" for s, n in
                     sorted(tls.pulled, reverse=True)[:3]
                     if s >= IMPORT_FLOOR_S / 2]
            if heavy:
                span.note = f"{name} ({', '.join(heavy)})"
            span.__exit__(*sys.exc_info())

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _ImportTimer:
    """A finder ahead of `sys.meta_path` that finds nothing of its own.
    Outside any import it asks the finders behind it and hands their
    spec on with the loader wrapped; inside one it does the same only
    for a module the outermost pulls in first-hand from another package,
    and answers None for every other — most of a start's two thousand —
    so that those are found and loaded as if it were not here: a
    wrapped loader is one more interpreter frame a level of nesting for
    as long as the module runs, and a dozen of them moved a loop deep
    inside `orbax.checkpoint`'s import (`google.api_core`'s scan of the
    installed distributions) across a boundary of CPython's frame
    stack, where every call allocates a chunk and frees it again: five
    times as long on the chip's host (PR 34).  The import
    system consults `sys.meta_path` only for a module that is not in
    `sys.modules`, so an import statement that finds its module there —
    every one after the first — never comes here."""

    def find_spec(self, name, path=None, target=None):
        if _nested(_importing, name):
            return None
        for finder in sys.meta_path:
            find = getattr(finder, "find_spec", None)
            if finder is self or find is None:
                continue
            spec = find(name, path, target)
            if spec is not None:
                break
        else:
            return None
        _ImportTimer.seen += 1
        if hasattr(spec.loader, "exec_module"):
            spec.loader = _TimedLoader(spec.loader)
        return spec

    #: modules handed on with a wrapped loader: what the bookkeeping
    #: beyond one thread-local read was paid for
    seen = 0


#: per thread: `own`, the top-level package of the outermost import in
#: progress (None outside any), `abroad`, whether a nested import of
#: another package is running, and `pulled`, those imports' seconds
_importing = threading.local()


def time_imports() -> None:
    """From here on every outermost import of this process that takes
    `IMPORT_FLOOR_S` or more is an `iotml.start.import` span in the
    importing thread's ring, the module's name in its `note`.  Once a
    process; the package's `__init__` calls it before it imports
    anything heavy."""
    if not any(isinstance(f, _ImportTimer) for f in sys.meta_path):
        sys.meta_path.insert(0, _ImportTimer())


def start_report(spans=None, unit: float = 1.0) -> dict:
    """A trainer's start from its spans: `start`, the seconds of each
    `iotml.start.*` phase up to the end of the first `iotml.train.fit`;
    that fit (`first_fit_s`) with its `dispatch_s` (tracing, lowering,
    the cache read or the compilation) and `sync_s` (the first
    execution); `imports`, the five slowest by module; `ready_s`, the
    fit's end.  {} before a first fit has ended.  `spans` defaults to
    this process's rings; `unit` is a second in the spans' time unit (a
    span log's are microseconds since the writer's anchor, which is
    where `ready_s` counts from: the process's first import of this
    package)."""
    own = spans is None
    spans = phases() if own else sorted(spans, key=lambda s: s.id)
    fit = next((s for s in spans if s.name == "iotml.train.fit"), None)
    if fit is None:
        return {}
    out = {"ready_s": (fit.end - (_ANCHOR_MONO if own else 0)) * unit,
           "first_fit_s": (fit.end - fit.start) * unit,
           "dispatch_s": 0.0, "sync_s": 0.0, "start": {}}
    imports = []
    for s in spans:
        if s.start > fit.end:
            continue
        loop, _, what = s.name.partition(".")[2].partition(".")
        seconds = (s.end - s.start) * unit
        if loop == "start":
            out["start"][what] = out["start"].get(what, 0.0) + seconds
            if what == "import":
                imports.append((seconds, s.note or "?"))
        elif s.name in ("iotml.train.dispatch", "iotml.train.sync") \
                and s.thread == fit.thread and s.start >= fit.start \
                and not out[what + "_s"]:
            out[what + "_s"] = seconds
    out["imports"] = [(name, sec) for sec, name in
                      sorted(imports, reverse=True)[:5]]
    return out


def start_line(report: dict, compiles: Optional[dict] = None) -> str:
    """One line of `start_report()` for an operator: where the seconds
    from the first import to a trained job went.  `compiles` is
    `utils.device.compile_report()`'s dict where the process listened
    to JAX's compilations: what tracing, lowering and building or
    loading the program's own programs took, and which of them the
    persistent cache served."""
    if not report:
        return "start: no fit yet"
    by_phase = dict(report["start"])
    parts = [f"import {by_phase.pop('import', 0.0):.2f}" + "".join(
        f"; {name} {sec:.2f}" for name, sec in report["imports"])]
    parts += [f"{what.replace('_', ' ')} {sec:.2f}"
              for what, sec in sorted(by_phase.items())]
    parts.append(f"first fit {report['first_fit_s']:.2f} (dispatch "
                 f"{report['dispatch_s']:.2f}, run {report['sync_s']:.2f})")
    if compiles is not None:
        by = compiles["seconds"]
        parts.append(
            f"the program's own programs: trace {by['trace']:.2f}, lower "
            f"{by['lower']:.2f}, build or load {by['backend']:.2f} (cache "
            f"read {by['cache_read']:.2f}); from the cache: "
            f"{', '.join(compiles['hit']) or 'none'}; compiled (cache "
            f"miss): {', '.join(compiles['missed']) or 'none'}")
    return (f"start: ready {report['ready_s']:.2f} s after the first "
            f"import | " + " | ".join(parts))


def mark_batch(ctx: Optional[TraceContext], stage: str,
               topic: Optional[str] = None, partition: int = -1,
               first_offset: int = -1, last_offset: int = -1,
               n: int = 0) -> None:
    """One span for a whole RAW batch (ISSUE 13 wire-trace leg): marks
    `stage` on `ctx` (the timing span, like mark()) and records a batch
    annotation — topic/partition, offset range, record count — that the
    span log carries so ``python -m iotml.obs trace`` can show which
    bytes the cross-process span covered.  Batch-granular by contract:
    one call per raw batch, never per record."""
    if ctx is None or ctx.closed:
        return
    ctx.mark(stage)
    _collector.record(("batch", ctx.trace_id, stage, topic or "",
                       int(partition), int(first_offset),
                       int(last_offset), int(n), ctx.wall0_ns))


def headers_for(ctx: Optional[TraceContext]) -> Optional[Tuple]:
    """Record headers carrying `ctx` (None stays None: untraced records
    pay no header tuple)."""
    if ctx is None:
        return None
    return ((HEADER_KEY, ctx),)


def birth_headers(stage: str) -> Optional[Tuple]:
    """start() + headers_for() in one: the trace-birth idiom for
    producers that attach the context straight to the produced record.
    Call sites still guard on `tracing.ENABLED` so the disabled hot
    path makes no function call at all."""
    return headers_for(start(stage))


def from_headers(headers) -> Optional[TraceContext]:
    """Extract a context from record headers: the live object on the
    in-process path, the byte form off a transport."""
    if not headers:
        return None
    for key, value in headers:
        if key != HEADER_KEY:
            continue
        if isinstance(value, TraceContext):
            return value
        if isinstance(value, (bytes, bytearray)):
            return TraceContext.decode(bytes(value))
    return None


# ---------------------------------------------------------------- drain
def flush() -> Dict[str, int]:
    """Drain the collector into the Prometheus histograms, the liveness
    table and (when configured) the JSONL span log.  Returns counts
    (of record-level spans; phase spans go to the log alone).
    Exporting happens HERE, never on the record path — the histograms'
    internal locks are only ever taken by drainers."""
    entries = _collector.drain()
    n_span = n_e2e = 0
    proc = proc_name()
    # phase spans: to the span log only (`phase()` observed the
    # histogram itself), each once, and the rings stay as they are
    lines: List[str] = []
    for buf in _collector.buffers():
        if not buf.phases or buf.phases[-1].id <= buf.logged:
            continue
        new = [s for s in list(buf.phases) if s.id > buf.logged]
        buf.logged = new[-1].id
        if _PATH:
            lines += [json.dumps(
                {"kind": "phase", "name": s.name, "id": s.id,
                 "parent": s.parent, "round": s.round, "thread": s.thread,
                 "start_us": int((s.start - _ANCHOR_MONO) * 1e6),
                 "dur_us": int((s.end - s.start) * 1e6),
                 "wall0_ns": _ANCHOR_WALL_NS, "proc": proc,
                 **({} if s.note is None else {"note": s.note})})
                for s in new]
    for e in entries:
        if e[0] == "span":
            _, tid, stage, start_s, dur_s, wall0_ns, t_mark = e
            n_span += 1
            stage_seconds.observe(dur_s, stage=stage)
            # the MARK instant, not the drain instant: liveness ages
            # must keep growing for a stalled stage even when the first
            # probe in a long while is what triggers this drain
            if t_mark > _last_seen.get(stage, float("-inf")):
                _last_seen[stage] = t_mark
            if _PATH:
                lines.append(json.dumps(
                    {"kind": "span", "trace": f"{tid:016x}", "stage": stage,
                     "start_us": int(start_s * 1e6),
                     "dur_us": int(dur_s * 1e6), "wall0_ns": wall0_ns,
                     "proc": proc}))
        elif e[0] == "batch":
            # batch annotation (mark_batch): the timing span was already
            # recorded by the mark() inside mark_batch — this line
            # carries the WHAT (topic/partition/offset range/count) for
            # the cross-process trace reconstruction
            _, tid, stage, topic, part, first, last, n, wall0_ns = e
            if _PATH:
                lines.append(json.dumps(
                    {"kind": "batch", "trace": f"{tid:016x}",
                     "stage": stage, "topic": topic, "partition": part,
                     "first_offset": first, "last_offset": last,
                     "n": n, "wall0_ns": wall0_ns, "proc": proc}))
        else:
            _, tid, closer, dur_s, wall0_ns = e
            n_e2e += 1
            if closer == "score":
                e2e_ingest_to_score_seconds.observe(dur_s)
            elif closer == "train":
                e2e_ingest_to_train_seconds.observe(dur_s)
            if _PATH:
                lines.append(json.dumps(
                    {"kind": "e2e", "trace": f"{tid:016x}", "closer": closer,
                     "dur_us": int(dur_s * 1e6), "wall0_ns": wall0_ns,
                     "proc": proc}))
    if lines and _PATH:
        try:
            with _log_lock:
                with open(_PATH, "a", encoding="utf-8") as fh:
                    fh.write("\n".join(lines) + "\n")
        except OSError:
            # an unwritable span-log path (permissions, full disk) must
            # not turn into a /metrics scrape outage or an atexit crash —
            # the histograms above already have the spans; count the loss
            # under its own family (distinct from collector overload)
            log_write_errors.inc(len(lines))
    return {"spans": n_span, "e2e": n_e2e}


def liveness() -> Dict[str, float]:
    """Stage → seconds since its newest span (drains first).  The
    /healthz status section: a stage whose age keeps growing while
    upstream stages stay fresh is the stalled one."""
    flush()
    now = time.monotonic()
    # snapshot first: a concurrent flush() (ThreadingHTTPServer: /metrics
    # scrape vs /healthz probe) may insert a first-seen stage key, and
    # iterating the live dict would raise mid-probe
    snapshot = dict(_last_seen)
    return {stage: round(now - t, 3) for stage, t in sorted(snapshot.items())}


def reset() -> None:
    """Test hook: drop collected spans, liveness and current-trace state
    (the module flag and sampling survive — configure() owns those)."""
    _collector.drain()
    for buf in _collector.buffers():
        buf.phases.clear()
        buf.logged = 0
    _last_seen.clear()
    _current.ctx = None


configure_from_env()
atexit.register(flush)
