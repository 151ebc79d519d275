"""A process's way to its first fit (ISSUE 34): the `start` loop of
`tracing.phase` — `backend`, `import` (one span an outermost import of
0.1 s or more, its module in the span's `note`), `state_init` — beside
the first `iotml.train.fit`; the compile cache's hits and misses by
program; the line an operator reads at readiness."""

import importlib
import io
import json
import re
import sys
import textwrap
import threading
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.data.dataset import Batch
from iotml.models.transformer import SensorFormer
from iotml.obs import metrics as obs_metrics, tracing
from iotml.obs.__main__ import main as obs_main
from iotml.train.loop import Trainer
from iotml.utils import device

PROGRAMS = r'program="(iotml_\w+|other)"'


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.configure(enabled=False, path="")
    tracing.reset()


def _reg(prefix: str) -> dict:
    return {k: v for k, v in obs_metrics.default_registry.collect().items()
            if k.startswith(prefix)}


def _start_count(phase: str) -> float:
    return _reg("iotml_step_seconds_count").get(
        f'iotml_step_seconds_count{{loop="start",phase="{phase}"}}', 0.0)


def _compile_s(*stages: str) -> float:
    return sum(v for k, v in _reg("iotml_compile_seconds_sum").items()
               if any(f'stage="{s}"' in k for s in stages))


def _named(name: str) -> list:
    return [s for s in tracing.phases() if s.name == name]


def _windows(n=2, rows=2, t=8, seed=1):
    rng = np.random.default_rng(seed)
    return [Batch(rng.normal(size=(rows, t, 18)).astype(np.float32), rows,
                  i * rows, y=rng.normal(size=(rows, 1, 18))
                  .astype(np.float32)) for i in range(n)]


def _tiny_trainer() -> Trainer:
    return Trainer(SensorFormer(features=18, d_model=16, num_heads=2,
                                num_layers=1, max_len=8, attn_mode="dense"),
                   supervised=True, learning_rate=1e-3)


@pytest.fixture
def started():
    """claim_device(), _ensure_state and two fits of a tiny SensorFormer:
    what each left in the ring and in the registry."""
    counts0 = {p: _start_count(p) for p in ("backend", "state_init")}
    device.claim_device()
    trainer = _tiny_trainer()
    trainer._ensure_state(_windows()[0].x)
    trainer.fit_compiled(_windows(), epochs=1)
    spans1, traced1 = tracing.phases(), _compile_s("trace", "lower")
    trainer.fit_compiled(_windows(seed=2), epochs=1)
    return {"counts0": counts0, "spans1": spans1, "traced1": traced1,
            "traced2": _compile_s("trace", "lower"), "trainer": trainer}


# ------------------------------------------------- backend and state init
def test_the_ring_holds_one_backend_and_one_state_init_span(started):
    backend, init = _named("iotml.start.backend"), \
        _named("iotml.start.state_init")
    assert len(backend) == 1 and len(init) == 1
    assert backend[0].parent is None and backend[0].round is None
    assert backend[0].end <= init[0].start
    # both are series of the one histogram, under the loop `start`
    for phase, span in (("backend", backend[0]), ("state_init", init[0])):
        assert _start_count(phase) == started["counts0"][phase] + 1
        assert span.seconds > 0


def test_state_init_ends_with_the_state_on_the_device(started):
    init = _named("iotml.start.state_init")[0]
    fits = _named("iotml.train.fit")
    assert [f.round for f in fits] == [1, 2]
    # seeded ahead of the first fit, as the benchmark's adapter does, the
    # state's span lies outside the fit's
    assert init.end <= fits[0].start
    leaves = jax.tree.leaves(started["trainer"].state.params)
    assert leaves and all(isinstance(a, jax.Array) for a in leaves)


def test_the_second_fit_opens_no_start_span_and_traces_nothing(started):
    after_one = [s for s in started["spans1"]
                 if s.name.startswith("iotml.start.")]
    after_two = [s for s in tracing.phases()
                 if s.name.startswith("iotml.start.")]
    assert [s.id for s in after_two] == [s.id for s in after_one]
    # the first fit traced and lowered `iotml_scanned_fit`; the second
    # books not one second more of either, for any program
    assert started["traced1"] > 0
    assert started["traced2"] == started["traced1"]


def test_a_second_trainer_is_a_second_state_init(started):
    _tiny_trainer()._ensure_state(_windows()[0].x)
    assert len(_named("iotml.start.state_init")) == 2
    started["trainer"]._ensure_state(_windows()[0].x)   # has its state
    assert len(_named("iotml.start.state_init")) == 2


# --------------------------------------------------------------- imports
@pytest.fixture
def slow_modules(tmp_path, monkeypatch):
    """Modules that sleep while they import: `<tag>_slow` 0.15 s,
    `<tag>_quick` not at all, `<tag>_outer` pulls `<tag>_inner` (0.15 s)
    in; names no other test or run of this one has imported."""
    tag = f"iotml_t34_{tmp_path.name.replace('-', '_')}"
    files = {
        "slow": "import time\ntime.sleep(0.15)\n",
        "quick": "X = 1\n",
        "inner": "import time\ntime.sleep(0.15)\n",
        "outer": f"import {tag}_inner\n",
    }
    for name, body in files.items():
        (tmp_path / f"{tag}_{name}.py").write_text(textwrap.dedent(body))
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    yield tag
    for name in files:
        sys.modules.pop(f"{tag}_{name}", None)


def _imports() -> list:
    return _named("iotml.start.import")


def test_the_package_put_its_finder_ahead_of_the_others():
    assert isinstance(sys.meta_path[0], tracing._ImportTimer)
    tracing.time_imports()   # once a process
    assert sum(isinstance(f, tracing._ImportTimer)
               for f in sys.meta_path) == 1


def test_a_slow_import_makes_one_span_with_its_module_readable(slow_modules):
    count0 = _start_count("import")
    importlib.import_module(f"{slow_modules}_slow")
    (span,) = _imports()
    assert span.note == f"{slow_modules}_slow"
    assert 0.15 <= span.seconds < 5
    assert span.thread == threading.current_thread().name
    assert _start_count("import") == count0 + 1
    # the module's name is no label of the histogram
    assert not [k for k in _reg("iotml_step_seconds") if slow_modules in k]


def test_an_import_under_the_floor_makes_none(slow_modules, monkeypatch):
    assert tracing.IMPORT_FLOOR_S == 0.1
    # (a floor no stall of a loaded test machine reaches)
    monkeypatch.setattr(tracing, "IMPORT_FLOOR_S", 30.0)
    count0 = _start_count("import")
    mod = importlib.import_module(f"{slow_modules}_quick")
    importlib.import_module(f"{slow_modules}_slow")
    assert mod.X == 1 and not _imports()
    assert _start_count("import") == count0


def test_a_nested_import_is_its_outermosts_time_not_a_span(slow_modules):
    importlib.import_module(f"{slow_modules}_outer")
    (span,) = _imports()
    # the outermost's note says where its seconds went: what it pulled
    # in from outside its own package, first-hand
    assert span.note.startswith(f"{slow_modules}_outer ({slow_modules}"
                                f"_inner 0.")
    assert span.seconds >= 0.15
    assert f"{slow_modules}_inner" in sys.modules


def test_the_outermosts_own_modules_are_loaded_as_if_no_finder_were_there(
        tmp_path, monkeypatch):
    """Only the outermost import and what it pulls in first-hand from
    another package get the timing loader; the finder answers None for
    every other nested module, so the finders behind it load those
    untouched and no frame of the timer's lies between them."""
    pkg = tmp_path / "iotml_t34_pkg"
    (pkg / "deep").mkdir(parents=True)
    (pkg / "__init__.py").write_text(
        "import time\nfrom . import sub\nfrom .deep import leaf\n"
        "time.sleep(0.15)\n")
    (pkg / "sub.py").write_text("X = 1\n")
    (pkg / "deep" / "__init__.py").write_text("")
    (pkg / "deep" / "leaf.py").write_text("import iotml_t34_other\n")
    (tmp_path / "iotml_t34_other.py").write_text(
        "import iotml_t34_others_own\n")
    (tmp_path / "iotml_t34_others_own.py").write_text("Y = 2\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    timer = sys.meta_path[0]
    seen = tracing._ImportTimer.seen
    try:
        importlib.import_module("iotml_t34_pkg")
        # wrapped: the package, and the other package it pulled in
        assert tracing._ImportTimer.seen == seen + 2
        (span,) = _imports()
        assert span.note == "iotml_t34_pkg"   # (the other took no time)
        for name in ("iotml_t34_pkg.sub", "iotml_t34_pkg.deep.leaf",
                     "iotml_t34_other", "iotml_t34_others_own"):
            assert type(sys.modules[name].__loader__).__name__ \
                == "SourceFileLoader"
        # inside an import the finder answers None for the package's own
        tls = tracing._importing
        tls.own, tls.abroad = "iotml_t34_pkg", False
        assert timer.find_spec("iotml_t34_pkg.sub") is None
        assert timer.find_spec("iotml_t34_other") is not None
        tls.abroad = True
        assert timer.find_spec("iotml_t34_other") is None
    finally:
        tracing._importing.own = None
        for name in [m for m in sys.modules if m.startswith("iotml_t34_")]:
            sys.modules.pop(name)


def test_a_second_import_never_reaches_the_finder(slow_modules):
    importlib.import_module(f"{slow_modules}_slow")
    seen, spans = tracing._ImportTimer.seen, len(_imports())
    for _ in range(3):
        importlib.import_module(f"{slow_modules}_slow")
        import json as _again  # noqa: F401  (in sys.modules: no finder)
    assert tracing._ImportTimer.seen == seen
    assert len(_imports()) == spans == 1


def test_a_timed_module_keeps_its_own_loader(slow_modules):
    mod = importlib.import_module(f"{slow_modules}_slow")
    assert type(mod.__loader__).__name__ == "SourceFileLoader"
    assert mod.__spec__.loader is mod.__loader__
    assert importlib.reload(mod) is mod


def test_two_threads_imports_land_in_their_own_rings(slow_modules):
    def pull(name):
        importlib.import_module(f"{slow_modules}_{name}")

    side = threading.Thread(target=pull, args=("slow",),
                            name="iotml-t34-preload")
    side.start()
    pull("outer")
    side.join()
    by_thread = {s.thread: s.note for s in _imports()}
    assert by_thread["iotml-t34-preload"] == f"{slow_modules}_slow"
    assert by_thread[threading.current_thread().name].startswith(
        f"{slow_modules}_outer")
    # side by side, not one after the other
    a, b = _imports()
    assert max(a.start, b.start) < min(a.end, b.end)


def test_a_failed_import_raises_as_it_would_have(tmp_path, monkeypatch):
    (tmp_path / "iotml_t34_broken.py").write_text("raise ValueError('x')\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    importlib.invalidate_caches()
    with pytest.raises(ValueError):
        importlib.import_module("iotml_t34_broken")
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("iotml_t34_no_such_module")
    assert "iotml_t34_broken" not in sys.modules
    # the thread is outside any import again: the next one is outermost
    assert getattr(tracing._importing, "own", None) is None


# ------------------------------------------------------ a phase's floor
def test_a_phase_under_its_floor_leaves_nothing():
    count0 = _start_count("engine")
    with tracing.phase("start", "engine", floor=0.5):
        pass
    assert not _named("iotml.start.engine")
    assert _start_count("engine") == count0
    with tracing.phase("start", "engine", floor=0.0, note="built"):
        pass
    (span,) = _named("iotml.start.engine")
    assert span.note == "built" and _start_count("engine") == count0 + 1


# ------------------------------------------------- the cache by program
@pytest.fixture
def cache_dir(tmp_path):
    from jax._src import compilation_cache as cc

    device.listen_for_compiles()
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    yield str(tmp_path)
    for k, v in keep.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _cache_counts() -> dict:
    return _reg("iotml_compile_cache_total{")


@pytest.mark.parametrize("result", ["miss", "hit"])
def test_the_compile_cache_counts_by_program(cache_dir, result):
    def iotml_probe(x):
        return jnp.sin(x) * (2.0 if result == "hit" else 3.0)

    def plain(x):
        return jnp.cos(x) * (5.0 if result == "hit" else 7.0)

    x = jnp.ones((8,))
    x.block_until_ready()
    if result == "hit":   # the entries a later process would find
        jax.jit(iotml_probe)(x), jax.jit(plain)(x)
        jax.clear_caches()
    before = _cache_counts()
    jax.jit(iotml_probe)(x), jax.jit(plain)(x)
    moved = {k: v - before.get(k, 0.0) for k, v in _cache_counts().items()
             if v != before.get(k, 0.0)}
    assert moved == {
        f'iotml_compile_cache_total{{program="iotml_probe",'
        f'result="{result}"}}': 1.0,
        f'iotml_compile_cache_total{{program="other",'
        f'result="{result}"}}': 1.0}
    # the label draws from the closed set listen_for_compiles has
    for key in _cache_counts():
        assert re.search(PROGRAMS, key), key
    report = device.compile_report()
    assert "iotml_probe" in report["missed"]
    assert ("iotml_probe" in report["hit"]) == (result == "hit")
    assert "other" not in report["hit"] + report["missed"]


def test_the_label_vocabulary_knows_the_program_of_a_lookup(
        one_deployments_registry):
    assert obs_metrics.DECLARED_METRIC_LABELS["compile_cache"] == (
        "program", "result")
    assert obs_metrics.DECLARED_METRIC_LABELS["step_seconds"] == (
        "loop", "phase")
    assert not obs_metrics.cardinality_violations()


# --------------------------------------------------- the readiness line
def test_start_report_lays_the_start_beside_the_first_fit(started):
    report = tracing.start_report()
    fit = _named("iotml.train.fit")[0]
    assert report["first_fit_s"] == pytest.approx(fit.seconds)
    assert report["start"]["backend"] == pytest.approx(
        _named("iotml.start.backend")[0].seconds)
    assert report["start"]["state_init"] == pytest.approx(
        _named("iotml.start.state_init")[0].seconds)
    assert 0 < report["dispatch_s"] + report["sync_s"] \
        <= report["first_fit_s"]
    assert report["ready_s"] >= report["first_fit_s"]
    line = tracing.start_line(report, device.compile_report())
    assert line.startswith("start: ready ")
    for word in ("import ", "backend ", "state init ", "first fit ",
                 "trace ", "lower ", "compiled (cache miss): "):
        assert word in line, line


def test_no_fit_yet_is_said_so():
    assert tracing.start_report() == {}
    assert tracing.start_line({}) == "start: no fit yet"


def test_the_span_log_carries_the_note_and_the_cli_prints_the_start(
        tmp_path, slow_modules):
    path = str(tmp_path / "spans.jsonl")
    tracing.configure(path=path)
    importlib.import_module(f"{slow_modules}_slow")
    with tracing.phase("start", "backend"):
        pass
    trainer = _tiny_trainer()
    trainer.fit_compiled(_windows(), epochs=1)
    tracing.flush()
    docs = [json.loads(ln) for ln in open(path)]
    # (a worker whose first fit this is imports Pallas inside it: one
    # more `import` span, so look the module up, not the only one)
    notes = {}
    for d in docs:
        if d["kind"] == "phase":
            notes.setdefault(d["name"], []).append(d.get("note"))
    assert f"{slow_modules}_slow" in notes["iotml.start.import"]
    assert notes["iotml.train.fit"] == [None]
    out = io.StringIO()
    with redirect_stdout(out):
        assert obs_main(["trace", path]) == 0
    (line,) = [ln for ln in out.getvalue().splitlines() if "start: " in ln]
    assert f"{slow_modules}_slow 0." in line and "first fit " in line
    out = io.StringIO()
    with redirect_stdout(out):
        assert obs_main(["trace", path, "--json"]) == 0
    (start,) = json.loads(out.getvalue())["starts"].values()
    # inside the first fit here: the state's init is part of it
    assert start["start"]["state_init"] < start["first_fit_s"]
    assert f"{slow_modules}_slow" in [name for name, _ in start["imports"]]
