"""Phase spans (ISSUE 24): `tracing.phase` — one span per phase per
round of a loop, nested, on the profiler's clock — inside the train,
batching and score loops; stable names on the device programs and
kernels; the compile counters; consumer lag refreshed at poll."""

import ast
import glob
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from iotml.core.schema import KSQL_CAR_SCHEMA
from iotml.data.dataset import Batch, SensorBatches
from iotml.obs import metrics as obs_metrics, tracing
from iotml.obs.__main__ import main as obs_main
from iotml.ops import framing
from iotml.ops.avro import AvroCodec
from iotml.stream import native as native_mod
from iotml.stream.broker import Broker
from iotml.stream.consumer import StreamConsumer
from iotml.stream.kafka_wire import KafkaWireBroker, KafkaWireServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODEC = AvroCodec(KSQL_CAR_SCHEMA)
needs_native = pytest.mark.skipif(not native_mod.available(),
                                  reason="C++ engine not built")


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.configure(enabled=False, path="")
    tracing.reset()


def _step_sums() -> dict:
    return {k: v for k, v in obs_metrics.default_registry.collect().items()
            if k.startswith("iotml_step_seconds_sum")}


def _phase_sum(reg: dict, loop: str, phase: str) -> float:
    return reg.get(
        f'iotml_step_seconds_sum{{loop="{loop}",phase="{phase}"}}', 0.0)


def _tiny_batches(n=2, rows=8, seed=1):
    rng = np.random.default_rng(seed)
    return [Batch(rng.normal(size=(rows, 18)).astype(np.float32), rows,
                  i * rows) for i in range(n)]


def _fill(broker, topic="T", n=64):
    broker.create_topic(topic, partitions=1)
    rng = np.random.default_rng(3)
    fields = KSQL_CAR_SCHEMA.fields

    def record():
        return {f.name: ("false" if f.avro_type == "string"
                         else float(rng.normal())) for f in fields}

    broker.produce_many(
        topic, [(f"car-{i % 5}".encode(),
                 framing.frame(CODEC.encode(record()), 1),
                 1_700_000_000_000 + i) for i in range(n)], partition=0)


# ------------------------------------------------------------ (a) nesting
def test_phases_nest_inherit_and_self_time():
    with tracing.phase("train", "round", round=7):
        with tracing.phase(None, "fetch"):
            time.sleep(0.01)
        with tracing.phase("train", "fit", round=3):
            with tracing.phase(None, "stack"):
                time.sleep(0.005)
        time.sleep(0.01)
    spans = {s.name: s for s in tracing.phases()}
    assert set(spans) == {"iotml.train.round", "iotml.train.fetch",
                          "iotml.train.fit", "iotml.train.stack"}
    root, fetch = spans["iotml.train.round"], spans["iotml.train.fetch"]
    fit, stack = spans["iotml.train.fit"], spans["iotml.train.stack"]
    # parent links; loop and round inherited where not given
    assert root.parent is None
    assert fetch.parent == root.id and fit.parent == root.id
    assert stack.parent == fit.id
    assert (root.round, fetch.round, fit.round, stack.round) == (7, 7, 3, 3)
    assert all(s.thread == "MainThread" for s in spans.values())
    assert root.start <= fetch.start <= fetch.end <= fit.start \
        <= stack.start <= stack.end <= fit.end <= root.end
    # self time = duration less the children's cover
    own = tracing.self_seconds(tracing.phases())
    assert own[root.id] == pytest.approx(
        root.seconds - fetch.seconds - fit.seconds)
    assert own[root.id] >= 0.01
    assert own[fit.id] == pytest.approx(fit.seconds - stack.seconds)
    assert own[stack.id] == pytest.approx(stack.seconds)
    # a phase outside any loop takes the neutral loop label
    with tracing.phase(None, "fetch"):
        pass
    assert tracing.phases()[-1].name == "iotml.stream.fetch"
    assert tracing.phases()[-1].parent is None
    # reading empties nothing; the span's wall clock is the anchor's
    assert len(tracing.phases()) == 5
    assert abs(root.wall_ns() - time.time_ns()) < 60e9


def test_phase_ring_is_bounded_and_survives_an_exception():
    with pytest.raises(ValueError):
        with tracing.phase("train", "round", round=1):
            with tracing.phase(None, "fit"):
                raise ValueError("boom")
    assert [s.name for s in tracing.phases()] == [  # in opening order
        "iotml.train.round", "iotml.train.fit"]
    # the stack unwound: the next phase is a root again
    with tracing.phase("score", "drain"):
        pass
    assert tracing.phases()[-1].parent is None
    for _ in range(tracing._PHASE_BOUND + 10):
        with tracing.phase("score", "drain"):
            pass
    assert len(tracing.phases()) == tracing._PHASE_BOUND


# ------------------------------------------------- (b, c) the fit's spans
def test_fit_compiled_leaves_one_span_tree_per_call():
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.train.loop import Trainer

    trainer = Trainer(CAR_AUTOENCODER)
    before = _step_sums()
    for _ in range(2):
        trainer.fit_compiled(_tiny_batches(), epochs=2, fused="never")
    after = _step_sums()
    spans = tracing.phases()
    fits = [s for s in spans if s.name == "iotml.train.fit"]
    assert [f.round for f in fits] == [1, 2] and trainer.fits == 2
    assert all(f.parent is None for f in fits)
    # a Trainer's first fit is also where its state is made (ISSUE 34:
    # the `start` loop's spans lie where the work happens)
    assert [s.parent for s in spans
            if s.name == "iotml.start.state_init"] == [fits[0].id]
    for fit in fits:
        kids = {s.name: s for s in spans if s.parent == fit.id
                and not s.name.startswith("iotml.start.")}
        assert set(kids) == {"iotml.train.host_pipeline",
                             "iotml.train.stack",
                             "iotml.train.device_compute"}
        dev = kids["iotml.train.device_compute"]
        parts = [s for s in spans if s.parent == dev.id]
        assert [p.name for p in parts] == [
            "iotml.train.transfer", "iotml.train.dispatch",
            "iotml.train.sync"]
        assert all(s.round == fit.round for s in [*kids.values(), *parts])
        assert sum(p.seconds for p in parts) <= dev.seconds <= fit.seconds

    def moved(phase):
        return _phase_sum(after, "train", phase) \
            - _phase_sum(before, "train", phase)

    # the histogram's series nest the same way
    assert 0 < moved("transfer") + moved("dispatch") + moved("sync") \
        <= moved("device_compute") <= moved("fit")
    assert moved("stack") > 0 and moved("host_pipeline") > 0
    # an empty slice still closes its fit span, and nothing below it
    # but the host pipeline's
    assert trainer.fit_compiled([], epochs=1)["loss"] == []
    last = [s for s in tracing.phases() if s.round == 3]
    assert [s.name for s in last] == ["iotml.train.fit",
                                      "iotml.train.host_pipeline"]


@pytest.mark.parametrize("fused", ["never", "auto"])
def test_step_seconds_series_keep_their_meaning(fused):
    """`host_pipeline` and `device_compute` move once per fit, as the
    hand-rolled sites moved them (tests/test_obs2.py reads them too),
    on the scanned and on the fused fit."""
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.train.loop import Trainer

    def counts():
        reg = obs_metrics.default_registry.collect()
        return [reg.get('iotml_step_seconds_count{loop="train",'
                        f'phase="{p}"}}', 0.0)
                for p in ("host_pipeline", "device_compute", "fit")]

    before = counts()
    history = Trainer(CAR_AUTOENCODER).fit_compiled(
        _tiny_batches(), epochs=1, fused=fused)
    assert history["fit"] == ("scanned" if fused == "never" else "fused")
    assert [a - b for a, b in zip(counts(), before)] == [1.0, 1.0, 1.0]


# -------------------------------------- the batcher's and the scorer's
def test_batcher_fetch_and_decode_are_phases_of_the_callers_loop():
    broker = Broker()
    _fill(broker, n=48)
    batches = SensorBatches(StreamConsumer(broker, ["T:0:0"], group="ph"),
                            batch_size=16, poll_chunk=32)
    with tracing.phase("train", "host_pipeline", round=5) as host:
        assert len(list(batches)) == 3
    spans = tracing.phases()
    inner = [s for s in spans if s.parent == host.id]
    names = {s.name for s in inner}
    # the in-memory broker has no fused native leg: the wire call and
    # the decode are calls of their own
    assert names == {"iotml.train.fetch", "iotml.train.decode"}
    assert all(s.round == 5 for s in inner)
    # one span per consumer call (a chunk), never per record: 48
    # records in chunks of 32 are two polls with data and one empty
    fetches = [s for s in inner if s.name.endswith(".fetch")]
    assert len(fetches) == 3
    assert len([s for s in inner if s.name.endswith(".decode")]) == 2


@needs_native
def test_the_read_aheads_fetch_is_the_streams_and_the_hit_the_trainers():
    """The consumer's read-ahead thread (ISSUE 31) opens its consumer
    calls outside any loop's phase — `iotml.stream.fetch` — so
    `loop="train"` reads only what the trainer's thread still waits
    for: its own poll, served from the buffer."""
    from iotml.stream.native_kafka import NativeKafkaBroker

    broker = Broker()
    _fill(broker, n=400)
    with KafkaWireServer(broker) as srv:
        nb = NativeKafkaBroker(f"127.0.0.1:{srv.port}")
        cons = StreamConsumer(nb, ["T:0:0"], group="ahead")
        batches = SensorBatches(cons, batch_size=10, take=3, window=16,
                                poll_chunk=32)
        reg0 = obs_metrics.default_registry.collect()
        hosts = []
        for job in range(3):
            with tracing.phase("train", "host_pipeline", round=job) as h:
                assert len(list(batches)) == 3
            hosts.append(h.id)
            # armed on the second end, not on the first
            assert (cons._ahead is None) == (job == 0)
        cons._ahead.thread.join(60)
        reg1 = obs_metrics.default_registry.collect()
        nb.close()

    def count(reg, loop):
        return reg.get("iotml_step_seconds_count"
                       f'{{loop="{loop}",phase="fetch"}}', 0.0)

    # a take is 46 rows in polls of 32 and 14: two read-aheads of two
    # consumer calls each under `stream`, three takes' own under `train`
    assert count(reg1, "stream") - count(reg0, "stream") == 4
    assert count(reg1, "train") - count(reg0, "train") == 6
    hit = 'iotml_consumer_readahead_rows_total{result="hit"}'
    assert reg1.get(hit, 0.0) - reg0.get(hit, 0.0) == 46
    spans = tracing.phases()
    ahead = [s for s in spans if s.name == "iotml.stream.fetch"]
    assert len(ahead) == 4 and all(
        s.parent is None and s.thread == "iotml-consumer-read-ahead"
        for s in ahead)
    # the third job's polls came out of the buffer and are still its own
    # `fetch` phases, on its own thread, inside its host pipeline
    mine = [s for s in spans if s.parent == hosts[2]]
    assert [s.name for s in mine] == ["iotml.train.fetch"] * 2
    assert all(s.thread != "iotml-consumer-read-ahead" for s in mine)


def test_exited_threads_do_not_keep_a_ring_each_for_good(monkeypatch):
    """A thread a read-ahead, in a process nobody drains: a new thread's
    registration prunes the exited threads' buffers past the bound."""
    import threading

    monkeypatch.setattr(tracing, "_THREADS_BOUND", 8)

    def one():
        with tracing.phase(None, "fetch"):
            pass

    for _ in range(40):
        t = threading.Thread(target=one, name="short-lived", daemon=True)
        t.start()
        t.join()
    assert len(tracing._collector.buffers()) <= 9
    # the newest spans are still there to read; the histogram has all
    assert any(s.thread == "short-lived" for s in tracing.phases())


def test_scorer_drain_span_tree():
    import jax

    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.serve.scorer import StreamScorer
    from iotml.stream.producer import OutputSequence

    broker = Broker()
    _fill(broker, n=250)
    broker.create_topic("preds", partitions=1)
    params = CAR_AUTOENCODER.init(jax.random.PRNGKey(0),
                                  np.zeros((1, 18), np.float32))["params"]
    scorer = StreamScorer(
        CAR_AUTOENCODER, params,
        SensorBatches(StreamConsumer(broker, ["T:0:0"], group="phs"),
                      batch_size=100),
        OutputSequence(broker, "preds", partition=0))
    before = _step_sums()
    assert scorer.score_available() == 250
    after = _step_sums()
    spans = tracing.phases()
    drains = [s for s in spans if s.name == "iotml.score.drain"]
    assert len(drains) == 1 and drains[0].round == scorer.drains == 1
    kids = [s.name for s in spans if s.parent == drains[0].id]
    # one super-batch of three batches, then the empty poll that ends
    # the drain
    assert kids == ["iotml.score.host_pipeline",
                    "iotml.score.device_compute",
                    "iotml.score.writeback",
                    "iotml.score.host_pipeline"]
    assert {s.name for s in spans} >= {"iotml.score.fetch"}
    assert _phase_sum(after, "score", "writeback") \
        > _phase_sum(before, "score", "writeback")
    own = tracing.self_seconds(spans)
    assert 0 <= own[drains[0].id] <= drains[0].seconds


# ------------------------------------------- (d) the profiler round trip
def test_phases_ride_the_profilers_host_plane(tmp_path):
    import jax

    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.obs.profile import trace, trace_files
    from iotml.train.loop import Trainer

    trainer = Trainer(CAR_AUTOENCODER)
    trainer.fit_compiled(_tiny_batches(), epochs=1, fused="never")  # warm
    tracing.reset()
    with trace(str(tmp_path)):
        trainer.fit_compiled(_tiny_batches(), epochs=1, fused="never")
    pbs = [f for f in trace_files(str(tmp_path))
           if f.endswith(".xplane.pb")]
    assert pbs, "no xplane trace written"
    data = jax.profiler.ProfileData.from_file(pbs[0])
    host = next(p for p in data.planes if p.name == "/host:CPU")
    events = [(ev.name, ev.start_ns, ev.duration_ns)
              for line in host.lines for ev in line.events
              if ev.name.startswith("iotml.train.")]
    spans = {s.name: s for s in tracing.phases()}
    assert {n for n, _s, _d in events} == set(spans) == {
        "iotml.train.fit", "iotml.train.host_pipeline",
        "iotml.train.stack", "iotml.train.device_compute",
        "iotml.train.transfer", "iotml.train.dispatch", "iotml.train.sync"}
    by_name = {n: (s, s + d) for n, s, d in events}
    for name, span in spans.items():
        a, b = by_name[name]
        # the same interval on both clocks, to within a millisecond
        assert abs((b - a) / 1e9 - span.seconds) < 1e-3, name
        if span.parent is not None:
            parent = next(p for p in spans.values()
                          if p.id == span.parent)
            pa, pb = by_name[parent.name]
            assert pa <= a and b <= pb, (name, parent.name)
    # the annotation carries the round
    fit_ev = next(ev for line in host.lines for ev in line.events
                  if ev.name == "iotml.train.fit")
    assert dict(fit_ev.stats).get("round") == 2


# --------------------------------------------- (e) no JAX in the module
def test_tracing_module_imports_no_jax_and_works_without_it():
    """`import iotml` itself reaches JAX (core.normalize), so the module
    is loaded under stub parents: what it and `obs.metrics` import
    themselves must leave jax out, and `phase()` must work there."""
    code = f"""
import importlib, os, sys, types
for name, path in (("iotml", "iotml"), ("iotml.obs", "iotml/obs")):
    mod = types.ModuleType(name)
    mod.__path__ = [os.path.join({ROOT!r}, path)]
    sys.modules[name] = mod
tracing = importlib.import_module("iotml.obs.tracing")
assert "jax" not in sys.modules, "tracing imported jax"
with tracing.phase("train", "round", round=1):
    with tracing.phase(None, "fetch"):
        pass
assert [s.name for s in tracing.phases()] == [
    "iotml.train.round", "iotml.train.fetch"]
assert tracing.annotation("x") is None
assert "jax" not in sys.modules
"""
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT)


# ------------------------------------------------ (f) compile counters
def test_compile_counters_by_stage_and_program():
    import jax
    import jax.numpy as jnp

    from iotml.utils.device import claim_device, listen_for_compiles

    claim_device()
    listen_for_compiles()  # idempotent: no second listener

    @jax.jit
    def iotml_probe_program(x):
        return x * 2 + 1

    def counts(program):
        reg = obs_metrics.default_registry.collect()
        return {st: reg.get(
            f'iotml_compile_seconds_count{{program="{program}",'
            f'stage="{st}"}}', 0.0)
            for st in ("trace", "lower", "backend")}

    before = counts("iotml_probe_program")
    iotml_probe_program(jnp.ones(3))
    first = counts("iotml_probe_program")
    assert all(first[st] - before[st] == 1.0 for st in first)
    # a second call of the same program compiles nothing
    iotml_probe_program(jnp.ones(3))
    assert counts("iotml_probe_program") == first
    # a new input shape is one more, with the program named
    iotml_probe_program(jnp.ones(5))
    assert counts("iotml_probe_program")["backend"] \
        == first["backend"] + 1.0
    # any other function lands under the one bounded label (making its
    # argument may compile a program of its own)
    other = counts("other")["backend"]
    jax.jit(lambda x: x - 3)(jnp.ones(7))
    assert counts("other")["backend"] >= other + 1.0
    reg = obs_metrics.default_registry.collect()
    assert reg['iotml_compile_seconds_sum{program="iotml_probe_program",'
               'stage="backend"}'] > 0


def test_jitted_programs_carry_iotml_names():
    from iotml.models.autoencoder import CAR_AUTOENCODER
    from iotml.train import loop

    tx = loop.adam_cached(1e-3)
    named = {
        "iotml_scanned_fit": loop.make_scanned_fit(CAR_AUTOENCODER, tx),
        "iotml_train_step": loop.make_train_step(CAR_AUTOENCODER, tx),
        "iotml_window_steps": loop.make_scanned_window_steps(
            CAR_AUTOENCODER, tx),
        "iotml_eval_step": loop.make_eval_step(CAR_AUTOENCODER),
        "iotml_state_init": loop.jitted_state_init(CAR_AUTOENCODER, tx,
                                                   tx_key="names"),
    }
    for want, fn in named.items():
        assert fn.__name__ == want


# ------------------------------------------------- (g) names on kernels
def test_every_pallas_call_in_ops_is_named():
    from iotml.analysis.lint import lint_file

    calls = 0
    for path in sorted(glob.glob(os.path.join(ROOT, "iotml", "ops",
                                              "*.py"))):
        tree = ast.parse(open(path).read())
        calls += sum(isinstance(n, ast.Call)
                     and getattr(n.func, "attr", None) == "pallas_call"
                     for n in ast.walk(tree))
        assert [f for f in lint_file(path) if f.rule == "R17"] == [], path
    # the four flash kernels (a dense and a triangular grid each behind
    # one call since PR 25) and the fused fit
    assert calls >= 5
    from iotml.ops import attention

    assert (attention.FWD_KERNEL, attention.BWD_DKV_KERNEL,
            attention.BWD_DQ_KERNEL, attention.BWD_FUSED_KERNEL) == (
        "iotml_flash_fwd", "iotml_flash_bwd_dkv", "iotml_flash_bwd_dq",
        "iotml_flash_bwd_fused")


def test_lint_r17_and_phase_under_trace(tmp_path):
    from iotml.analysis import tracecheck
    from iotml.analysis.lint import lint_file

    fixture = os.path.join(ROOT, "tests", "fixtures", "analysis",
                           "bad_kernel.py")
    findings = lint_file(fixture)
    # unnamed, a foreign literal, a foreign constant, a computed name;
    # the `iotml_` literal and the `iotml_` constant stay clean
    assert [(f.rule, f.line) for f in findings] == [
        ("R17", 10), ("R17", 14), ("R17", 18), ("R17", 22)]
    bad = tmp_path / "phase_under_jit.py"
    bad.write_text(
        "import jax\n"
        "from iotml.obs import tracing\n\n\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    with tracing.phase('train', 'dispatch'):\n"
        "        return x * 2\n")
    found = tracecheck.analyze(paths=[str(bad)])
    assert [(f.rule, f.line) for f in found] == [("T2", 7)]
    # a phase opened while a lock is held is R6's (span recording)
    locked = tmp_path / "phase_under_lock.py"
    locked.write_text(
        "import threading\n"
        "from iotml.obs import tracing\n\n"
        "_lock = threading.Lock()\n\n\n"
        "def fetch():\n"
        "    with _lock:\n"
        "        with tracing.phase(None, 'fetch'):\n"
        "            pass\n")
    assert [f.rule for f in lint_file(str(locked))] == ["R6"]


# ------------------------------------------------------------- (h) cost
def test_an_empty_phase_is_cheap():
    n = 10_000
    t0, own0 = time.perf_counter(), time.thread_time()
    for _ in range(n):
        with tracing.phase("score", "drain"):
            pass
    per = (time.perf_counter() - t0) / n
    own = (time.thread_time() - own0) / n
    # the thread's own clock holds the code to what it held on the wall's
    # alone; the wall's, over the same whole loop, also counts what the
    # worker's other threads and five more workers take under `-n 6`
    # (150.1 us read there once, 4 us alone: ROADMAP D10)
    assert own < 20e-6, f"{own * 1e6:.1f} us of its thread per empty phase"
    assert per < 250e-6, f"{per * 1e6:.1f} us per empty phase"


# ------------------------------------------- (i) the span log and the CLI
def test_span_log_round_trips_parent_and_round(tmp_path, capsys):
    path = str(tmp_path / "spans.jsonl")
    tracing.configure(path=path)
    for rnd, nap in ((1, 0.0), (2, 0.02)):
        with tracing.phase("train", "fit", round=rnd):
            with tracing.phase(None, "host_pipeline"):
                with tracing.phase(None, "fetch"):
                    time.sleep(nap)
            with tracing.phase(None, "stack"):
                pass
    assert tracing.flush() == {"spans": 0, "e2e": 0}
    tracing.flush()  # a second flush writes nothing twice
    docs = [json.loads(ln) for ln in open(path)]
    assert len(docs) == 8 and all(d["kind"] == "phase" for d in docs)
    spans = tracing.phases()  # the export emptied nothing
    assert len(spans) == 8
    by_id = {d["id"]: d for d in docs}
    for s in spans:
        d = by_id[s.id]
        assert (d["name"], d["parent"], d["round"], d["thread"]) == (
            s.name, s.parent, s.round, s.thread)
        assert d["dur_us"] == int(s.seconds * 1e6)
        assert d["wall0_ns"] + d["start_us"] * 1000 == pytest.approx(
            s.wall_ns(), abs=2000)
    # the CLI: self time per phase, and the slowest round with its phases
    assert obs_main(["trace", path]) == 0
    out = capsys.readouterr().out
    assert "no spans found" not in out
    assert "self_ms" in out and "iotml.train.host_pipeline" in out
    assert "slowest iotml.train.fit: round 2" in out
    assert obs_main(["trace", path, "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    rows = {r["phase"]: r for r in summary["phases"]}
    assert rows["iotml.train.fit"]["count"] == 2
    # host_pipeline's self time excludes the fetch it waited in
    assert rows["iotml.train.host_pipeline"]["self_ms"] < 5 \
        < rows["iotml.train.fetch"]["self_ms"] \
        <= rows["iotml.train.host_pipeline"]["total_ms"]
    slow = summary["slowest_rounds"][0]
    assert (slow["root"], slow["round"]) == ("iotml.train.fit", 2)
    assert [p["phase"] for p in slow["phases"]] == [
        "iotml.train.fit", "iotml.train.host_pipeline",
        "iotml.train.fetch", "iotml.train.stack"]
    assert [p["depth"] for p in slow["phases"]] == [0, 1, 2, 1]


# ---------------------------------------------- consumer lag at the poll
def test_consumer_lag_moves_at_poll_on_the_wire_without_a_request(
        tmp_path):
    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=50)

    def lag(group):
        return obs_metrics.consumer_lag_records.value(
            group=group, topic="T", partition=0)

    with KafkaWireServer(broker) as srv:
        wb = KafkaWireBroker(f"127.0.0.1:{srv.port}")
        cons = StreamConsumer(wb, ["T:0:0"], group="lagpoll", eof=False)
        asked = []
        real = wb.end_offset
        wb.end_offset = lambda *a, **kw: asked.append(a) or real(*a, **kw)
        assert len(cons.poll(20)) == 20
        # mid-window, behind its log, no commit yet: the gauge has moved
        assert lag("lagpoll") == 30
        assert len(cons.poll(20)) == 20
        assert lag("lagpoll") == 10
        # from the hwm each fetch response carried, not from a request
        assert asked == []
        wb.close()
    # an in-process broker answers from its own log
    cons2 = StreamConsumer(broker, ["T:0:0"], group="lagpoll2")
    cons2.poll(5)
    assert lag("lagpoll2") == 45
    broker.close()


@needs_native
def test_native_client_keeps_the_fetch_responses_hwm(tmp_path):
    from iotml.stream.native_kafka import NativeKafkaBroker

    broker = Broker(store_dir=str(tmp_path))
    _fill(broker, n=40)
    nc = native_mod.NativeCodec(KSQL_CAR_SCHEMA)
    with KafkaWireServer(broker) as srv:
        nb = NativeKafkaBroker(f"127.0.0.1:{srv.port}")
        assert nb.last_hwm("T", 0) is None
        cons = StreamConsumer(nb, ["T:0:0"], group="lagnative")
        num, _lab = cons.poll_decoded(nc, max_messages=16)
        assert len(num) == 16
        assert nb.last_hwm("T", 0) == 40
        assert obs_metrics.consumer_lag_records.value(
            group="lagnative", topic="T", partition=0) == 24
        nb.close()
    broker.close()
